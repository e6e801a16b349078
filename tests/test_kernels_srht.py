"""Fused SRHT Pallas kernel vs reference: parity + dispatch API.

The kernel body runs in interpret mode (CPU CI); ``impl="ref"`` is the
pure-jnp oracle every golden trajectory is pinned to. Parity covers
pow2/non-pow2 dims, fp32/bf16, forward and transpose, batched/vmapped
callers, row counts whose last block is ragged, the shape-chosen row
block against the 8-row block, and the redesigned ``repro.kernels.ops``
selection API (per-call > config > env > auto).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sketch import SrhtSketch, make_sketch
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.fwht import COMPILER_PARAMS, _F32_TEMPS, srht_block_rows
from repro.kernels.srht import srht_apply_pallas, srht_apply_t_pallas


def _srht(dim, k=8, dtype=jnp.float32, seed=0):
    s = make_sketch(jax.random.PRNGKey(seed), "srht", k, dim, dtype=dtype)
    assert isinstance(s, SrhtSketch)
    return s


def _tol(n, dtype):
    if dtype == jnp.bfloat16:
        return dict(rtol=5e-2, atol=2e-2 * max(1.0, n ** 0.5))
    return dict(rtol=2e-4, atol=2e-4 * n ** 0.5)


# (rows, block_rows): 5 rows fit one block; 1003 rows take one block
# that overhangs the array by 5, or, at an explicit 64, sixteen blocks
# whose last is ragged (43 rows)
ROW_BLOCKS = [(5, None), (1003, None), (1003, 64)]


def _interpret(op, block_rows):
    """The interpret-mode kernel: through the dispatch at the chosen
    block, or straight at an explicit ``block_rows``."""
    if block_rows is None:
        return functools.partial(getattr(kops, op), impl="interpret")
    fn = {"srht_apply": srht_apply_pallas,
          "srht_apply_t": srht_apply_t_pallas}[op]
    return functools.partial(fn, block_rows=block_rows, interpret=True)


@pytest.mark.parametrize("rows,block_rows", ROW_BLOCKS)
@pytest.mark.parametrize("dim", [16, 24, 37, 64, 100, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_srht_forward_parity(dim, dtype, rows, block_rows):
    s = _srht(dim, dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, dim), dtype)
    want = kops.srht_apply(x, s.signs, s.rows, impl="ref")
    got = _interpret("srht_apply", block_rows)(x, s.signs, s.rows)
    n = s.signs.shape[-1]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(n, dtype))


@pytest.mark.parametrize("rows,block_rows", ROW_BLOCKS)
@pytest.mark.parametrize("dim", [16, 24, 37, 64, 100, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_srht_transpose_parity(dim, dtype, rows, block_rows):
    s = _srht(dim, dtype=dtype)
    y = jax.random.normal(jax.random.PRNGKey(2), (rows, s.k), dtype)
    want = kops.srht_apply_t(y, s.signs, s.rows, dim, impl="ref")
    got = _interpret("srht_apply_t", block_rows)(y, s.signs, s.rows, dim)
    assert got.shape == (rows, dim)
    n = s.signs.shape[-1]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(n, dtype))


def test_srht_fused_scatter_zeroes_unsampled_lanes():
    """The transpose's in-kernel masked write: on the pow2 domain the
    padded-domain image of S^T y is exactly zero outside span(H D e_r),
    equivalently S(S^T y) = (n/k) y — check through the fused path."""
    dim, k = 64, 8
    s = _srht(dim, k=k)
    y = jax.random.normal(jax.random.PRNGKey(3), (3, k), jnp.float32)
    z = kops.srht_apply(
        kops.srht_apply_t(y, s.signs, s.rows, dim, impl="interpret"),
        s.signs, s.rows, impl="interpret")
    np.testing.assert_allclose(z, (dim / k) * y, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(37,), (2, 3, 37)])
def test_srht_batched_shapes(shape):
    """1-D and deep-batched callers (flens applies S to vectors and
    stacked matrices alike)."""
    dim = shape[-1]
    s = _srht(dim)
    x = jax.random.normal(jax.random.PRNGKey(4), shape, jnp.float32)
    want = s.apply(x, impl="ref")
    got = s.apply(x, impl="interpret")
    assert got.shape == shape[:-1] + (s.k,)
    np.testing.assert_allclose(got, want, **_tol(s.signs.shape[-1], jnp.float32))


@pytest.mark.parametrize("slab", [(), (37,), (277,)])
@pytest.mark.parametrize("transpose", [False, True])
def test_srht_vmap_through_dispatch(slab, transpose):
    """jax.vmap(s.apply) is how every optimizer maps clients — over
    gradient vectors and over (rows, dim) slabs, 277 rows as a phishing
    client holds; both impls must batch, and the interpret kernel under
    vmap must match ``ref``."""
    s = _srht(24)
    width = s.k if transpose else 24
    g = jax.random.normal(jax.random.PRNGKey(5), (6, *slab, width),
                          jnp.float32)
    if transpose:
        want = jax.vmap(lambda y: ref.srht_apply_t(y, s.signs, s.rows, 24))(g)
        auto = jax.vmap(s.apply_t)(g)
        got = jax.vmap(lambda y: s.apply_t(y, impl="interpret"))(g)
    else:
        want = jax.vmap(lambda x: ref.srht_apply(x, s.signs, s.rows))(g)
        auto = jax.vmap(s.apply)(g)
        got = jax.vmap(lambda x: s.apply(x, impl="interpret"))(g)
    np.testing.assert_allclose(auto, want, **_tol(32, jnp.float32))
    np.testing.assert_allclose(got, want, **_tol(32, jnp.float32))


@pytest.mark.parametrize("rows", [1, 37, 277, 1003, 5000])
@pytest.mark.parametrize("transpose", [False, True])
def test_srht_chosen_block_matches_8_row_blocks(rows, transpose):
    """The shape-chosen block (one overhanging block, or balanced blocks
    with a ragged last one) gives the 8-row blocks' result. On the chip
    the two are bit-equal; the CPU's dot sums in an order that depends
    on the block's row count, hence a few float32 ulps."""
    dim, k = 18, 10
    s = _srht(dim, k=k)
    if transpose:
        v = jax.random.normal(jax.random.PRNGKey(14), (rows, k), jnp.float32)
        run = functools.partial(srht_apply_t_pallas, v, s.signs, s.rows,
                                dim, interpret=True)
    else:
        v = jax.random.normal(jax.random.PRNGKey(14), (rows, dim),
                              jnp.float32)
        run = functools.partial(srht_apply_pallas, v, s.signs, s.rows,
                                interpret=True)
    got = np.asarray(run())
    want = np.asarray(run(block_rows=8))
    np.testing.assert_allclose(
        got, want, rtol=0,
        atol=16 * np.finfo(np.float32).eps * np.abs(want).max())


@pytest.mark.parametrize("rows", [1, 7, 37, 277, 1003, 5000, 10 ** 6])
@pytest.mark.parametrize("n,k", [(16, 8), (32, 10), (128, 17), (2048, 64),
                                 (2048, 1024), (8192, 128), (8192, 0),
                                 (65536, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_srht_block_rows_fits_and_is_capped(rows, n, k, dtype):
    """The helper's block: a multiple of the dtype's sublane count, never
    past the rows rounded up to it, and (above the 8-row floor) blocks
    and intermediates inside the kernels' VMEM limit."""
    sub = 8 if dtype == jnp.float32 else 16
    block = srht_block_rows(rows, n, k, dtype)
    assert block % sub == 0 and block >= sub
    assert block <= -(-rows // sub) * sub
    lanes = max(n, 128)
    if block > sub:
        per_row = (4 + _F32_TEMPS) * 4 * lanes
        assert block * per_row <= COMPILER_PARAMS.vmem_limit_bytes


def test_srht_block_rows_at_the_cells_shapes():
    """The blocks the benchmark cells run: a SUSY client's 5000 rows at
    n = 32, k = 10 in a few large blocks (was 625 blocks of 8); a
    phishing client's 277 rows at n = 128, k = 17 in one; FedNS's
    data-axis sketch at n = 8192 stays at small blocks."""
    susy = srht_block_rows(5000, 32, 10)
    assert susy >= 512
    assert -(-5000 // susy) <= 10
    assert -(-277 // srht_block_rows(277, 128, 17)) == 1
    assert srht_block_rows(10 ** 6, 8192, 128) <= 64
    assert srht_block_rows(10 ** 6, 65536, 0) == 8


def test_srht_sketch_matches_dense_through_interpret():
    """Fused kernel agrees with the materialized (k, dim) matrix."""
    s = _srht(37)
    mat = np.asarray(s.dense(), np.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 37), jnp.float32)
    got = s.apply(x, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(x) @ mat.T,
                               rtol=2e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# dispatch API
# ---------------------------------------------------------------------------

def test_registry_lists_impls():
    for op in ("fwht", "srht_apply", "srht_apply_t", "topk_mask",
               "qint8_roundtrip", "flash_attention"):
        assert kops.available_impls(op) == ("interpret", "pallas", "ref")


def test_resolve_precedence_call_config_env(monkeypatch):
    # env alone
    monkeypatch.setenv(kops.ENV_VAR, "interpret")
    assert kops.resolve_impl() == "interpret"
    # config beats env
    with kops.use_impl("ref"):
        assert kops.resolve_impl() == "ref"
        # per-call beats config
        assert kops.resolve_impl("interpret") == "interpret"
    # config cleared again -> env
    assert kops.resolve_impl() == "interpret"
    monkeypatch.delenv(kops.ENV_VAR)
    # auto resolves to ref off-TPU
    assert kops.resolve_impl() in ("ref", "pallas")
    if jax.default_backend() != "tpu":
        assert kops.resolve_impl() == "ref"


def test_env_var_routes_ops(monkeypatch):
    """REPRO_KERNEL_IMPL steers an un-annotated call site (the CI leg)."""
    s = _srht(24)
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 24), jnp.float32)
    monkeypatch.setenv(kops.ENV_VAR, "ref")
    want = s.apply(x)
    monkeypatch.setenv(kops.ENV_VAR, "interpret")
    got = s.apply(x)
    np.testing.assert_allclose(got, want, **_tol(32, jnp.float32))


def test_reference_alias_and_unknown_impl():
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16), jnp.float32)
    np.testing.assert_array_equal(kops.fwht(x, impl="reference"),
                                  kops.fwht(x, impl="ref"))
    with pytest.raises(ValueError, match="unknown kernel impl"):
        kops.fwht(x, impl="vulkan")


def test_forcing_pallas_off_tpu_raises():
    if jax.default_backend() == "tpu":
        pytest.skip("compiled path is legitimate on TPU")
    s = _srht(16)
    x = jnp.ones((2, 16), jnp.float32)
    with pytest.raises(RuntimeError, match="requires a TPU backend"):
        s.apply(x, impl="pallas")


def test_ref_impl_is_bit_identical_to_sketch_default_on_cpu():
    """On CPU, auto == ref: the dispatch rework must not perturb the
    jaxpr the goldens were recorded through."""
    if jax.default_backend() == "tpu":
        pytest.skip("auto resolves to pallas on TPU")
    s = _srht(37, dtype=jnp.float64)
    x = jax.random.normal(jax.random.PRNGKey(9), (5, 37), jnp.float64)
    np.testing.assert_array_equal(np.asarray(s.apply(x)),
                                  np.asarray(s.apply(x, impl="ref")))
    y = jax.random.normal(jax.random.PRNGKey(10), (5, s.k), jnp.float64)
    np.testing.assert_array_equal(np.asarray(s.apply_t(y)),
                                  np.asarray(s.apply_t(y, impl="ref")))


def test_ref_oracle_matches_pre_refactor_inline_graph():
    """ref.srht_apply/_t reproduce the exact pad->sign->fwht->take /
    scatter->fwht->sign->slice pipeline the pre-kernel Sketch traced."""
    dim, k = 37, 8
    s = _srht(dim, k=k, dtype=jnp.float64)
    n = s.signs.shape[-1]
    x = jax.random.normal(jax.random.PRNGKey(11), (5, dim), jnp.float64)
    xp = jnp.pad(x, ((0, 0), (0, n - dim))) * s.signs
    h = ref.fwht(xp, normalize=True)
    want = jnp.take(h, s.rows, axis=-1) * jnp.sqrt(jnp.asarray(n / k, h.dtype))
    np.testing.assert_array_equal(
        np.asarray(ref.srht_apply(x, s.signs, s.rows)), np.asarray(want))

    y = jax.random.normal(jax.random.PRNGKey(12), (5, k), jnp.float64)
    z = jnp.zeros((5, n), y.dtype).at[..., s.rows].set(
        y * jnp.sqrt(jnp.asarray(n / k, y.dtype)))
    want_t = (ref.fwht(z, normalize=True) * s.signs)[..., :dim]
    np.testing.assert_array_equal(
        np.asarray(ref.srht_apply_t(y, s.signs, s.rows, dim)),
        np.asarray(want_t))


def test_default_impl_none_clears_config(monkeypatch):
    """set_default_impl(None) clears the config layer back to env/auto."""
    monkeypatch.delenv(kops.ENV_VAR, raising=False)
    kops.set_default_impl("interpret")
    try:
        assert kops.resolve_impl() == "interpret"
    finally:
        kops.set_default_impl(None)
    if jax.default_backend() != "tpu":
        assert kops.resolve_impl() == "ref"


@pytest.mark.parametrize("transpose", [False, True])
def test_srht_index_select_path_parity(transpose):
    """Above the one-hot budget (n·k·4 bytes > ONEHOT_MAX_BYTES) the
    subsample runs by index outside the kernel; same values as ref."""
    from repro.kernels.srht import ONEHOT_MAX_BYTES

    dim, k = 3000, 1024
    n = 4096
    assert n * k * 4 > ONEHOT_MAX_BYTES
    s = _srht(dim, k=k)
    if transpose:
        y = jax.random.normal(jax.random.PRNGKey(13), (3, k), jnp.float32)
        want = kops.srht_apply_t(y, s.signs, s.rows, dim, impl="ref")
        got = kops.srht_apply_t(y, s.signs, s.rows, dim, impl="interpret")
    else:
        x = jax.random.normal(jax.random.PRNGKey(13), (3, dim), jnp.float32)
        want = kops.srht_apply(x, s.signs, s.rows, impl="ref")
        got = kops.srht_apply(x, s.signs, s.rows, impl="interpret")
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(n, jnp.float32))


def test_compiled_path_refuses_64bit_operands():
    """The compiled kernels never cast a float64 operand silently: the
    wrapper raises before Mosaic sees it, naming the dtype."""
    from repro.kernels.codec_kernels import (
        qint8_roundtrip_pallas,
        topk_mask_pallas,
    )
    from repro.kernels.fwht import fwht_pallas
    from repro.kernels.srht import srht_apply_pallas, srht_apply_t_pallas

    s = _srht(37, dtype=jnp.float64)
    x = jnp.ones((2, 37), jnp.float64)
    calls = [
        lambda: fwht_pallas(jnp.ones((2, 64), jnp.float64)),
        lambda: srht_apply_pallas(x, s.signs, s.rows),
        lambda: srht_apply_t_pallas(jnp.ones((2, s.k)), s.signs, s.rows, 37),
        lambda: topk_mask_pallas(x, 3),
        lambda: qint8_roundtrip_pallas(x, x),
    ]
    for call in calls:
        with pytest.raises(TypeError, match="float64"):
            call()
