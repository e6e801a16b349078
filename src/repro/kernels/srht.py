"""Pallas TPU kernel: the fused SRHT hot loop (forward and transpose).

The SRHT sketch ``S = sqrt(n/k) * P * H_n * D`` is the per-round compute
hot spot of every sketched optimizer (FLeNS/FLeNS+, FedNS, FedNDES,
DistributedFLeNS). The reference path traces it as a jit-graph of four
primitives — pad, sign multiply, ``fwht``, ``take`` (and a scatter for
the transpose) — each of which round-trips the full padded row through
memory. This kernel fuses the whole pipeline into one VMEM-resident
Pallas body:

  forward   : out = (x * D) H  P^T * (1/sqrt(k))          (rows, k)
  transpose : out = ((y * sqrt(n/k)) P) H * (1/sqrt(n)) D (rows, dim)

Structure (same TPU adaptation as ``repro.kernels.fwht``): the length-n
Hadamard factorizes as ``H_n = (H_A (x) I_B) . (I_A (x) H_B)``, so the
transform is two dense MXU matmuls against tiny Hadamard factors. The
row-subsample ``P`` (a gather in the reference path) and its transpose
(a scatter) both become matmuls against a one-hot selection matrix built
in-kernel from a ``broadcasted_iota`` comparison — the transpose's
scatter is therefore an in-kernel masked write: lanes whose iota matches
no sampled row receive exactly zero. The two normalizations (orthonormal
FWHT's 1/sqrt(n) and the SRHT's sqrt(n/k)) fold into a single 1/sqrt(k)
applied once at the output.

The one-hot matrix holds n·k floats. Above ``ONEHOT_MAX_BYTES`` it would
crowd the row blocks out of VMEM, so the subsample (and the transpose's
scatter) runs by index outside the kernel, and the kernel keeps the
sign flip, the transform and the scale: VMEM then grows with n only.

Validated against ``repro.kernels.ref.srht_apply``/``srht_apply_t`` in
interpret mode (CPU) by ``tests/test_kernels_srht.py``; compiled for a
v5e by ``tests/test_tpu_compile.py``. Dispatch via
``repro.kernels.ops.srht_apply``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fwht import (
    COMPILER_PARAMS,
    EXACT,
    _factor,
    check_compiled,
    fwht_rows,
    row_block,
    whole_block,
)
from repro.kernels.ref import hadamard_matrix

# largest in-kernel one-hot selection matrix (f32 bytes); n=2048, k=1024
# sits exactly at it
ONEHOT_MAX_BYTES = 8 * 1024 * 1024


def _onehot_fits(n: int, k: int) -> bool:
    return n * k * 4 <= ONEHOT_MAX_BYTES


def _srht_fwd_kernel(x_ref, signs_ref, rows_ref, ha_ref, hb_ref, o_ref,
                     *, a: int, b: int, k: int, out_scale: float):
    n = a * b
    x = x_ref[...].astype(jnp.float32) * signs_ref[...].astype(jnp.float32)
    h = fwht_rows(x, ha_ref[...].astype(jnp.float32),
                  hb_ref[...].astype(jnp.float32), a, b)
    # row subsample as a one-hot matmul (MXU-shaped gather):
    # sel[i, j] = 1 iff lane i is the j-th sampled row
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, k), 0)
    sel = (lane == rows_ref[...]).astype(jnp.float32)  # rows_ref (1, k)
    out = jax.lax.dot_general(h, sel, (((1,), (0,)), ((), ())),
                              precision=EXACT,
                              preferred_element_type=jnp.float32)
    o_ref[...] = (out * out_scale).astype(o_ref.dtype)


def _srht_t_kernel(y_ref, signs_ref, rows_ref, ha_ref, hb_ref, o_ref,
                   *, a: int, b: int, k: int, out_scale: float):
    n = a * b
    y = y_ref[...].astype(jnp.float32)
    # transpose subsample: scatter the k entries into the n-wide padded
    # domain as an in-kernel masked write — sel_t[j, i] is one-hot per
    # sampled row j, so lanes no row maps to are written exactly zero
    lane = jax.lax.broadcasted_iota(jnp.int32, (k, n), 1)
    sel_t = (lane == rows_ref[...].reshape(k, 1)).astype(jnp.float32)
    z = jax.lax.dot_general(y, sel_t, (((1,), (0,)), ((), ())),
                            precision=EXACT,
                            preferred_element_type=jnp.float32)
    h = fwht_rows(z, ha_ref[...].astype(jnp.float32),
                  hb_ref[...].astype(jnp.float32), a, b)
    out = h * out_scale * signs_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def _signed_fwht_kernel(x_ref, signs_ref, ha_ref, hb_ref, o_ref,
                        *, a: int, b: int, out_scale: float,
                        signs_first: bool):
    """The one-hot-free body: (x D) H * scale, or (x H) * scale * D."""
    x = x_ref[...].astype(jnp.float32)
    d = signs_ref[...].astype(jnp.float32)
    if signs_first:
        x = x * d
    h = fwht_rows(x, ha_ref[...].astype(jnp.float32),
                  hb_ref[...].astype(jnp.float32), a, b) * out_scale
    if not signs_first:
        h = h * d
    o_ref[...] = h.astype(o_ref.dtype)


def _flatten_rows(x: jax.Array, last: int, block_rows: int):
    """(..., last) -> ((rows_padded, last), rows) for row-tiled grids."""
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    xm = x.reshape(rows, last)
    pad = (-rows) % block_rows
    if pad:
        xm = jnp.pad(xm, ((0, pad), (0, 0)))
    return xm, rows


def _signed_fwht(xm, signs, *, a, b, k, block_rows, interpret, signs_first):
    n = a * b
    return pl.pallas_call(
        functools.partial(_signed_fwht_kernel, a=a, b=b,
                          out_scale=1.0 / k ** 0.5, signs_first=signs_first),
        grid=(xm.shape[0] // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, n), row_block),
            pl.BlockSpec((1, n), whole_block),
            pl.BlockSpec((a, a), whole_block),
            pl.BlockSpec((b, b), whole_block),
        ],
        out_specs=pl.BlockSpec((block_rows, n), row_block),
        out_shape=jax.ShapeDtypeStruct(xm.shape, xm.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(xm, signs.reshape(1, n), hadamard_matrix(a), hadamard_matrix(b))


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def srht_apply_pallas(x: jax.Array, signs: jax.Array, rows: jax.Array, *,
                      block_rows: int = 8, interpret: bool = False
                      ) -> jax.Array:
    """Fused S @ x: x (..., dim) -> (..., k); n = signs.shape[-1].

    ``rows`` are indices below n and run as int32 whatever their dtype."""
    check_compiled(x, signs, interpret=interpret)
    n = signs.shape[-1]
    k = rows.shape[-1]
    dim = x.shape[-1]
    a, b = _factor(n)
    pad = n - dim
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x
    xm, nrows = _flatten_rows(xp, n, block_rows)
    rows = rows.astype(jnp.int32)
    if not _onehot_fits(n, k):
        h = _signed_fwht(xm, signs, a=a, b=b, k=k, block_rows=block_rows,
                         interpret=interpret, signs_first=True)
        out = jnp.take(h[:nrows], rows, axis=-1)
        return out.reshape(x.shape[:-1] + (k,))
    out = pl.pallas_call(
        functools.partial(_srht_fwd_kernel, a=a, b=b, k=k,
                          out_scale=1.0 / k ** 0.5),
        grid=(xm.shape[0] // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, n), row_block),
            pl.BlockSpec((1, n), whole_block),
            pl.BlockSpec((1, k), whole_block),
            pl.BlockSpec((a, a), whole_block),
            pl.BlockSpec((b, b), whole_block),
        ],
        out_specs=pl.BlockSpec((block_rows, k), row_block),
        out_shape=jax.ShapeDtypeStruct((xm.shape[0], k), x.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(xm, signs.reshape(1, n), rows.reshape(1, k),
      hadamard_matrix(a), hadamard_matrix(b))
    return out[:nrows].reshape(x.shape[:-1] + (k,))


@functools.partial(jax.jit,
                   static_argnames=("dim", "block_rows", "interpret"))
def srht_apply_t_pallas(y: jax.Array, signs: jax.Array, rows: jax.Array,
                        dim: int, *, block_rows: int = 8,
                        interpret: bool = False) -> jax.Array:
    """Fused S^T @ y: y (..., k) -> (..., dim)."""
    check_compiled(y, signs, interpret=interpret)
    n = signs.shape[-1]
    k = rows.shape[-1]
    a, b = _factor(n)
    rows = rows.astype(jnp.int32)
    ym, nrows = _flatten_rows(y, k, block_rows)
    if not _onehot_fits(n, k):
        z = jnp.zeros((ym.shape[0], n), y.dtype).at[:, rows].set(ym)
        h = _signed_fwht(z, signs, a=a, b=b, k=k, block_rows=block_rows,
                         interpret=interpret, signs_first=False)
        return h[:nrows, :dim].reshape(y.shape[:-1] + (dim,))
    out = pl.pallas_call(
        functools.partial(_srht_t_kernel, a=a, b=b, k=k,
                          out_scale=1.0 / k ** 0.5),
        grid=(ym.shape[0] // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, k), row_block),
            pl.BlockSpec((1, n), whole_block),
            pl.BlockSpec((1, k), whole_block),
            pl.BlockSpec((a, a), whole_block),
            pl.BlockSpec((b, b), whole_block),
        ],
        out_specs=pl.BlockSpec((block_rows, n), row_block),
        out_shape=jax.ShapeDtypeStruct((ym.shape[0], n), y.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(ym, signs.reshape(1, n), rows.reshape(1, k),
      hadamard_matrix(a), hadamard_matrix(b))
    return out[:nrows, :dim].reshape(y.shape[:-1] + (dim,))
