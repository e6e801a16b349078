"""Pallas TPU kernels for the transport codec hot loop.

The byte-accurate wire simulation (``repro.comm.codecs``) runs two
value-path inner loops inside every jitted round: magnitude top-k
selection (a ``top_k`` + gather + scatter jit-graph today) and per-tensor
int8 quantization with stochastic rounding. Both are fused here into
single VMEM-resident kernel bodies; byte *accounting* stays in Python
(``Codec.nbytes``) and is identical for every implementation — the fused
path bills exactly the same wire bytes as the reference path (tested).

``topk_mask``: selecting the k largest |x| needs a global threshold, and
Mosaic has no sort/top_k primitive — so the kernel finds the threshold
itself by bisection on the *bit patterns* of |x|: non-negative IEEE-754
floats are order-isomorphic to their int32 bit patterns, so 31 rounds of
count-above-midpoint binary search recover the exact k-th largest value
with nothing but VPU compares and reductions. Ties at the threshold are
broken by lowest flat index (a second bisection, over the lane index of
the equal lanes) — matching ``jax.lax.top_k``'s stable tie-breaking, so
reference and fused paths keep the identical index SET, not just the
same count.

``qint8_roundtrip``: scale = max|x|/127 (clamped off the subnormal
range), stochastic rounding noise ``u`` is generated by the *caller*
from the codec's PRNG key, so every implementation consumes identical
random bits and the quantization error is reproducible across backends.

Validated against ``repro.kernels.ref`` in interpret mode (CPU) by
``tests/test_kernels_codecs.py``; compiled for a v5e by
``tests/test_tpu_compile.py``; dispatch via ``repro.kernels.ops``. The
compiled path takes 32- and 16-bit floats only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.fwht import check_compiled

_LANES = 128  # last-axis tile width on TPU


def _pad_flat(x: jax.Array) -> tuple[jax.Array, int]:
    """Flatten to (1, P) with P a multiple of the lane width."""
    flat = x.reshape(1, -1)
    size = flat.shape[1]
    pad = (-size) % _LANES
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat, size


# ---------------------------------------------------------------------------
# top-k select (dense mask)
# ---------------------------------------------------------------------------

def _count(mask) -> jax.Array:
    """(1, 1) int32 count of a (1, P) mask. Kept 2-D and int32: under
    x64 a reduction to a 0-d integer does not lower in Mosaic."""
    return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True,
                   dtype=jnp.int32)


def _i32(v: int) -> jax.Array:
    """A (1, 1) int32 loop carry or bound: a Python int becomes an int64
    constant under x64, and its in-kernel narrowing does not lower."""
    return jnp.full((1, 1), v, jnp.int32)


_I1, _I2 = np.int32(1), np.int32(2)


def _topk_kernel(x_ref, o_ref, *, kept: int, valid: int):
    x = x_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    in_range = lane < np.int32(valid)
    absx = jnp.where(in_range, jnp.abs(x), 0.0)
    # |x| >= 0, so the int32 view of its bits is non-negative and
    # order-isomorphic to the value: threshold search runs in bit space.
    bits = jax.lax.bitcast_convert_type(absx, jnp.int32)
    bits = jnp.where(in_range, bits, np.int32(-1))  # padding sorts lowest

    def bisect(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo + _I1) // _I2
        big_enough = _count(bits >= mid) >= np.int32(kept)
        return (jnp.where(big_enough, mid, lo),
                jnp.where(big_enough, hi, mid - _I1))

    # invariant: count(bits >= lo) >= kept; 0x7f800000 is +inf's pattern
    thr, _ = jax.lax.fori_loop(
        np.int32(0), np.int32(31), bisect, (_i32(0), _i32(0x7F800000)))
    above = bits > thr
    at = bits == thr
    # stable tie-break: keep the (kept - n_above) lowest-index threshold
    # lanes, i.e. those at or below the least lane bound j with
    # count(at & lane <= j) >= kept - n_above — a second bisection, over
    # the lane index (Mosaic has no prefix sum). count(bits > thr) < kept
    # by the first bisection, so at least one threshold lane is needed.
    need = np.int32(kept) - _count(above)

    def bisect_lane(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // _I2
        enough = _count(at & (lane <= mid)) >= need
        return jnp.where(enough, lo, mid + _I1), jnp.where(enough, mid, hi)

    steps = max(1, (x.shape[1] - 1).bit_length())
    bound, _ = jax.lax.fori_loop(
        np.int32(0), np.int32(steps), bisect_lane,
        (_i32(0), _i32(x.shape[1] - 1)))
    keep = above | (at & (lane <= bound))
    o_ref[...] = jnp.where(keep, x_ref[...], 0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kept", "interpret"))
def topk_mask_pallas(x: jax.Array, kept: int, *,
                     interpret: bool = False) -> jax.Array:
    """Zero all but the ``kept`` largest-|.| entries of ``x`` (any shape)."""
    check_compiled(x, interpret=interpret)
    flat, size = _pad_flat(x)
    out = pl.pallas_call(
        functools.partial(_topk_kernel, kept=kept, valid=size),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        interpret=interpret,
    )(flat)
    return out[0, :size].reshape(x.shape)


# ---------------------------------------------------------------------------
# qint8 quantize -> dequantize
# ---------------------------------------------------------------------------

def _qint8_kernel(x_ref, u_ref, o_ref, *, valid: int, tiny: float):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    absx = jnp.where(lane < valid, jnp.abs(x), 0.0)
    scale = jnp.maximum(jnp.max(absx) / 127.0, tiny)
    q = jnp.clip(jnp.floor(x / scale + u), -127.0, 127.0)
    o_ref[...] = (q * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qint8_roundtrip_pallas(x: jax.Array, u: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """Fused int8 quantize->dequantize; ``u ~ U[0,1)`` has x's shape."""
    check_compiled(x, u, interpret=interpret)
    flat, size = _pad_flat(x)
    uf, _ = _pad_flat(u)
    out = pl.pallas_call(
        functools.partial(_qint8_kernel, valid=size,
                          tiny=float(jnp.finfo(jnp.float32).tiny)),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        interpret=interpret,
    )(flat, uf)
    return out[0, :size].reshape(x.shape)
