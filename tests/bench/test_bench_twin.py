"""The benchmark's copies of the data and channel generators agree bit
for bit with the program's originals, so the benchmark's inputs are the
program's, yet no change to the program can move them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import twin
from benchmarks.paper_common import straggler_edge_channel
from repro.data.libsvm_like import PAPER_DATASETS, make_classification

_STATIC = ("n", "dim", "spectrum_decay", "label_noise", "dtype")


@pytest.mark.parametrize("name", ["susy", "phishing"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_twin_generator_matches_the_program(name, dtype):
    spec = PAPER_DATASETS[name]
    key = jax.random.PRNGKey(7)
    kw = dict(n=3000, dim=spec.dim, spectrum_decay=spec.spectrum_decay,
              label_noise=spec.label_noise, dtype=dtype)
    ours = twin.make_classification(key, **kw)
    # the copy runs as one jitted call; so does the original here
    theirs = jax.jit(make_classification, static_argnames=_STATIC)(key, **kw)
    for a, b in zip(ours, theirs):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("m", [1, 40, 1000])
def test_straggler_channel_matches_the_program(m):
    ours = twin.straggler_edge_channel(m)
    theirs = straggler_edge_channel(m)
    for field, value in ours.items():
        np.testing.assert_array_equal(np.asarray(value),
                                      np.asarray(getattr(theirs, field)))
    assert theirs.dropout_prob == 0.0
