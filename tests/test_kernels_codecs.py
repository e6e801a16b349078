"""Fused codec kernels vs reference: value parity + identical byte bills.

The transport hot loop (top-k select+pack, qint8 quantize) dispatches
through ``repro.kernels.ops``; the Pallas bodies (interpret mode on CPU
CI) must agree with the ``repro.kernels.ref`` oracles — which are
op-for-op the pre-kernel ``repro.comm.codecs`` bodies — and the codec
classes must bill exactly the same encoded bytes whichever impl serves
the values.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.codecs import QInt8Codec, TopKCodec, make_codec
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# top-k select
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(64,), (13, 17), (7, 9, 5)])
@pytest.mark.parametrize("frac", [0.01, 0.25, 1.0])
def test_topk_parity_exact(shape, frac):
    """Interpret-mode kernel selects the identical index SET (bitwise
    equal dense mask) as the jax.lax.top_k reference."""
    size = math.prod(shape)
    kept = max(1, int(math.ceil(frac * size)))
    x = jax.random.normal(jax.random.PRNGKey(size + kept), shape, jnp.float32)
    want = kops.topk_mask(x, kept, impl="ref")
    got = kops.topk_mask(x, kept, impl="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.count_nonzero(np.asarray(got))) <= kept


def test_topk_tie_breaking_matches_lax_top_k():
    """Ties at the threshold keep the LOWEST flat indices — the
    jax.lax.top_k convention the byte accounting assumes."""
    x = jnp.asarray([[1.0, -1.0, 0.5, 1.0], [0.5, -0.5, 0.5, 0.25]])
    for kept in range(1, 9):
        want = kops.topk_mask(x, kept, impl="ref")
        got = kops.topk_mask(x, kept, impl="interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kept", [1, 37, 500, 1999])
def test_topk_many_ties_lowest_index_wins(kept):
    """A payload wider than one lane tile with few distinct magnitudes:
    the lane-index bisection keeps exactly the lowest-index ties, as
    jax.lax.top_k does."""
    levels = jnp.asarray([0.0, 0.5, -0.5, 1.0, -1.0, 2.0], jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(kept), (2000,), 0, 6)
    x = levels[idx]
    want = kops.topk_mask(x, kept, impl="ref")
    got = kops.topk_mask(x, kept, impl="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_topk_all_zero_payload():
    x = jnp.zeros((5, 5), jnp.float32)
    got = kops.topk_mask(x, 3, impl="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.zeros((5, 5)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_dtypes(dtype):
    x = jax.random.normal(jax.random.PRNGKey(3), (40,), dtype)
    want = kops.topk_mask(x, 7, impl="ref")
    got = kops.topk_mask(x, 7, impl="interpret")
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# ---------------------------------------------------------------------------
# qint8 quantize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(64,), (13, 17), (3, 5, 7)])
def test_qint8_parity(shape):
    x = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32) * 3.0
    u = jax.random.uniform(jax.random.PRNGKey(2), shape, jnp.float32)
    want = kops.qint8_roundtrip(x, u, impl="ref")
    got = kops.qint8_roundtrip(x, u, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


def test_qint8_all_zero_payload_is_finite():
    """The subnormal-flush guard (scale clamped to tiny) must hold in
    the kernel too: an all-zero payload decodes to zeros, not NaN."""
    x = jnp.zeros((9,), jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(4), (9,), jnp.float32)
    got = np.asarray(kops.qint8_roundtrip(x, u, impl="interpret"))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, np.zeros(9))


def test_qint8_unbiasedness_survives_kernel():
    """Stochastic rounding stays unbiased through the fused body."""
    x = jnp.full((4096,), 0.3, jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(5), (4096,), jnp.float32)
    got = np.asarray(kops.qint8_roundtrip(x, u, impl="interpret"))
    assert abs(got.mean() - 0.3) < 5e-4


# ---------------------------------------------------------------------------
# codec classes: same values through dispatch, identical byte bills
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["qint8", "topk0.1", "topk@5",
                                  "topk0.25+qint8", "sympack+topk0.5+qint8"])
def test_codec_roundtrip_equivalent_across_impls(spec):
    codec = make_codec(spec)
    shape = (16, 16)
    x = jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float64)
    if spec.startswith("sympack"):
        x = 0.5 * (x + x.T)
    key = jax.random.PRNGKey(8)
    with kops.use_impl("ref"):
        want = codec.roundtrip(key, x)
    # f64 payloads (the convex experiments run x64) exercise the ref
    # path only; the kernel body is checked at f32
    with kops.use_impl("ref"):
        again = codec.roundtrip(key, x)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(again))
    xf = x.astype(jnp.float32)
    with kops.use_impl("ref"):
        want32 = codec.roundtrip(key, xf)
    with kops.use_impl("interpret"):
        got32 = codec.roundtrip(key, xf)
    np.testing.assert_allclose(np.asarray(got32), np.asarray(want32),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("spec,shape", [
    ("qint8", (32, 8)),
    ("topk0.1", (257,)),
    ("topk@9+qint8", (64,)),
    ("sympack+qint8", (24, 24)),
])
def test_codec_bytes_identical_across_impls(spec, shape):
    """nbytes is static Python — the fused path must bill exactly the
    bytes the existing Codec wire formats define, impl-independent."""
    codec = make_codec(spec)
    with kops.use_impl("ref"):
        ref_bytes = codec.nbytes(shape, jnp.float32)
    with kops.use_impl("interpret"):
        fused_bytes = codec.nbytes(shape, jnp.float32)
    assert ref_bytes == fused_bytes


def test_topk_codec_keeps_exactly_k_through_kernel():
    codec = TopKCodec(k=9)
    x = jax.random.normal(jax.random.PRNGKey(9), (100,), jnp.float32)
    with kops.use_impl("interpret"):
        out = np.asarray(codec.roundtrip(jax.random.PRNGKey(0), x))
    assert int(np.count_nonzero(out)) == 9
    assert codec.nbytes((100,), jnp.float32) == 9 * 4 + 9 * 4


def test_qint8_codec_jit_and_vmap_through_dispatch():
    """Codecs run inside jitted, vmapped rounds — both impls must trace."""
    codec = QInt8Codec()
    xs = jax.random.normal(jax.random.PRNGKey(10), (4, 33), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    with kops.use_impl("ref"):
        want = jax.jit(jax.vmap(codec.roundtrip))(keys, xs)
    with kops.use_impl("interpret"):
        got = jax.jit(jax.vmap(codec.roundtrip))(keys, xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)
