"""FWHT Pallas kernel vs pure-jnp oracle: shape/dtype sweeps + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.fwht import fwht_pallas
from repro.kernels.ops import fwht


@pytest.mark.parametrize("n", [2, 8, 64, 128, 256, 2048, 4096])
@pytest.mark.parametrize("rows", [1, 3, 8, 17])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_matches_oracle(n, rows, dtype):
    x = jax.random.normal(jax.random.PRNGKey(n + rows), (rows, n), dtype)
    got = fwht_pallas(x, interpret=True)
    if dtype == jnp.bfloat16:
        # the kernel accumulates in f32, so it is *closer* to the f32 truth
        # than the bf16 butterfly oracle — compare against the f32 oracle
        want = ref.fwht(x.astype(jnp.float32))
        tol, atol = 5e-2, 2e-2 * max(1.0, n**0.5)
    else:
        want = ref.fwht(x)
        tol, atol = 1e-4, 1e-4 * n**0.5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=atol,
    )


@pytest.mark.parametrize("normalize", [False, True])
def test_fwht_normalized(normalize):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 256), jnp.float32)
    got = fwht_pallas(x, normalize=normalize, interpret=True)
    want = ref.fwht(x, normalize=normalize)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_fwht_batched_shape():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 128), jnp.float32)
    got = fwht_pallas(x, interpret=True)
    assert got.shape == x.shape
    np.testing.assert_allclose(got, ref.fwht(x), rtol=1e-4, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(
    logn=st.integers(1, 11),
    rows=st.integers(1, 9),
    seed=st.integers(0, 2**30),
)
def test_fwht_involution_property(logn, rows, seed):
    """H(H(x))/n == x — the WHT is an involution up to scale."""
    n = 2**logn
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, n), jnp.float32)
    y = fwht_pallas(fwht_pallas(x, interpret=True), interpret=True) / n
    np.testing.assert_allclose(y, x, rtol=1e-3, atol=1e-3)


@settings(max_examples=15, deadline=None)
@given(logn=st.integers(1, 10), seed=st.integers(0, 2**30))
def test_fwht_orthogonality_property(logn, seed):
    """Normalized WHT preserves L2 norms (orthogonal transform)."""
    n = 2**logn
    x = jax.random.normal(jax.random.PRNGKey(seed), (4, n), jnp.float32)
    y = fwht_pallas(x, normalize=True, interpret=True)
    np.testing.assert_allclose(
        jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1),
        rtol=1e-4,
    )


def test_ops_dispatch_reference_matches_interpret():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 512), jnp.float32)
    a = fwht(x, impl="reference")
    b = fwht(x, impl="interpret")
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_width_above_kernel_limit_raises():
    """A width the kernels cannot hold in VMEM is refused by name, on
    every path — never served by a silent fallback."""
    from repro.kernels.fwht import MAX_WIDTH

    x = jnp.ones((1, 2 * MAX_WIDTH), jnp.float32)
    with pytest.raises(ValueError, match="MAX_WIDTH=65536"):
        fwht_pallas(x, interpret=True)
