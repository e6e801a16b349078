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

Tiling: the rows are cut into blocks of ``srht_block_rows`` rows, chosen
from the call's shapes (thousands of rows for SUSY's n = 32, one block
for a phishing client, 8 at the widest n), over a ``cdiv`` grid whose
last block is ragged; rows never mix, so what that block reads past the
array's end reaches no real row, and its writes there are dropped. No
operand is padded along its rows. For n <= 128 the kernels also read and
write the caller's rows at their own width ``dim``: the sign flip and
the zero-extension to n lanes ride in one matmul against the first
``dim`` rows of D·H_n (the transpose: the first ``dim`` columns of
H_n·D), whose ±1 entries leave every product exact. Wider rows are
padded to n lanes in the wrapper.

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
    srht_block_rows,
    whole_block,
)
from repro.kernels.ref import hadamard_matrix

# largest in-kernel one-hot selection matrix (f32 bytes); n=2048, k=1024
# sits exactly at it
ONEHOT_MAX_BYTES = 8 * 1024 * 1024


def _onehot_fits(n: int, k: int) -> bool:
    return n * k * 4 <= ONEHOT_MAX_BYTES


def _signed_wht(x, h_refs, *, a: int, b: int, signs_first: bool):
    """(x D) H_n, or (x H_n) D, of the f32 rows of x.

    For n <= 128 ``h_refs`` holds the one matrix D H_n cut to x's width
    (``signs_first``) or H_n D cut to the output's; wider, it holds
    (signs, H_a, H_b) and x comes n wide."""
    if a == 1:
        (m_ref,) = h_refs
        return jax.lax.dot_general(x, m_ref[...].astype(jnp.float32),
                                   (((1,), (0,)), ((), ())),
                                   precision=EXACT,
                                   preferred_element_type=jnp.float32)
    signs_ref, ha_ref, hb_ref = h_refs
    d = signs_ref[...].astype(jnp.float32)
    if signs_first:
        x = x * d
    h = fwht_rows(x, ha_ref[...].astype(jnp.float32),
                  hb_ref[...].astype(jnp.float32), a, b)
    return h if signs_first else h * d


def _srht_fwd_kernel(x_ref, rows_ref, *refs, a: int, b: int, k: int,
                     out_scale: float):
    *h_refs, o_ref = refs
    n = a * b
    h = _signed_wht(x_ref[...].astype(jnp.float32), h_refs, a=a, b=b,
                    signs_first=True)
    # row subsample as a one-hot matmul (MXU-shaped gather):
    # sel[i, j] = 1 iff lane i is the j-th sampled row
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, k), 0)
    sel = (lane == rows_ref[...]).astype(jnp.float32)  # rows_ref (1, k)
    out = jax.lax.dot_general(h, sel, (((1,), (0,)), ((), ())),
                              precision=EXACT,
                              preferred_element_type=jnp.float32)
    o_ref[...] = (out * out_scale).astype(o_ref.dtype)


def _srht_t_kernel(y_ref, rows_ref, *refs, a: int, b: int, k: int,
                   out_scale: float):
    *h_refs, o_ref = refs
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
    h = _signed_wht(z, h_refs, a=a, b=b, signs_first=False)
    o_ref[...] = (h * out_scale).astype(o_ref.dtype)


def _signed_fwht_kernel(x_ref, *refs, a: int, b: int, out_scale: float,
                        signs_first: bool):
    """The one-hot-free body: (x D) H * scale, or (x H) D * scale."""
    *h_refs, o_ref = refs
    h = _signed_wht(x_ref[...].astype(jnp.float32), h_refs, a=a, b=b,
                    signs_first=signs_first)
    o_ref[...] = (h * out_scale).astype(o_ref.dtype)


def _transform_operands(signs, width: int, *, a: int, b: int,
                        signs_first: bool) -> list:
    """The ``h_refs`` operands of ``_signed_wht`` (see there)."""
    n = a * b
    if a == 1:
        dh = signs.astype(jnp.float32)[:, None] * hadamard_matrix(n)
        return [dh[:width] if signs_first else dh.T[:, :width]]
    return [signs.reshape(1, n), hadamard_matrix(a), hadamard_matrix(b)]


def _row_grid(xm, block_rows: int, h_ops: list, *front):
    """Grid and in_specs of a row-tiled kernel: ``xm`` cut into blocks
    of ``block_rows`` (the last one ragged), then ``front`` and
    ``h_ops`` read whole by every step."""
    whole = [pl.BlockSpec(op.shape, whole_block) for op in (*front, *h_ops)]
    return ((pl.cdiv(xm.shape[0], block_rows),),
            [pl.BlockSpec((block_rows, xm.shape[1]), row_block), *whole])


def _signed_fwht(xm, signs, *, a, b, k, block_rows, interpret, signs_first):
    n = a * b
    h_ops = _transform_operands(signs, n, a=a, b=b, signs_first=signs_first)
    grid, in_specs = _row_grid(xm, block_rows, h_ops)
    return pl.pallas_call(
        functools.partial(_signed_fwht_kernel, a=a, b=b,
                          out_scale=1.0 / k ** 0.5, signs_first=signs_first),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, n), row_block),
        out_shape=jax.ShapeDtypeStruct(xm.shape, xm.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(xm, *h_ops)


def _block(block_rows, nrows: int, n: int, k: int, dtype) -> int:
    """``block_rows`` if given, else the shape-chosen block."""
    return block_rows or srht_block_rows(
        nrows, n, k if _onehot_fits(n, k) else 0, dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def srht_apply_pallas(x: jax.Array, signs: jax.Array, rows: jax.Array, *,
                      block_rows: int | None = None, interpret: bool = False
                      ) -> jax.Array:
    """Fused S @ x: x (..., dim) -> (..., k); n = signs.shape[-1].

    ``rows`` are indices below n and run as int32 whatever their dtype.
    ``block_rows`` overrides the shape-chosen row block."""
    check_compiled(x, signs, interpret=interpret)
    n = signs.shape[-1]
    k = rows.shape[-1]
    dim = x.shape[-1]
    a, b = _factor(n)
    if a > 1 and dim < n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n - dim)])
    xm = x.reshape(-1, x.shape[-1])
    block_rows = _block(block_rows, xm.shape[0], n, k, x.dtype)
    rows = rows.astype(jnp.int32)
    if not _onehot_fits(n, k):
        h = _signed_fwht(xm, signs, a=a, b=b, k=k, block_rows=block_rows,
                         interpret=interpret, signs_first=True)
        return jnp.take(h, rows, axis=-1).reshape(x.shape[:-1] + (k,))
    h_ops = _transform_operands(signs, dim, a=a, b=b, signs_first=True)
    grid, in_specs = _row_grid(xm, block_rows, h_ops, rows.reshape(1, k))
    out = pl.pallas_call(
        functools.partial(_srht_fwd_kernel, a=a, b=b, k=k,
                          out_scale=1.0 / k ** 0.5),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, k), row_block),
        out_shape=jax.ShapeDtypeStruct((xm.shape[0], k), x.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(xm, rows.reshape(1, k), *h_ops)
    return out.reshape(x.shape[:-1] + (k,))


@functools.partial(jax.jit,
                   static_argnames=("dim", "block_rows", "interpret"))
def srht_apply_t_pallas(y: jax.Array, signs: jax.Array, rows: jax.Array,
                        dim: int, *, block_rows: int | None = None,
                        interpret: bool = False) -> jax.Array:
    """Fused S^T @ y: y (..., k) -> (..., dim).

    ``block_rows`` overrides the shape-chosen row block."""
    check_compiled(y, signs, interpret=interpret)
    n = signs.shape[-1]
    k = rows.shape[-1]
    a, b = _factor(n)
    rows = rows.astype(jnp.int32)
    ym = y.reshape(-1, k)
    block_rows = _block(block_rows, ym.shape[0], n, k, y.dtype)
    if not _onehot_fits(n, k):
        z = jnp.zeros((ym.shape[0], n), y.dtype).at[:, rows].set(ym)
        h = _signed_fwht(z, signs, a=a, b=b, k=k, block_rows=block_rows,
                         interpret=interpret, signs_first=False)
        return h[:, :dim].reshape(y.shape[:-1] + (dim,))
    h_ops = _transform_operands(signs, dim, a=a, b=b, signs_first=False)
    width = dim if a == 1 else n  # n <= 128: written at its own width
    grid, in_specs = _row_grid(ym, block_rows, h_ops, rows.reshape(1, k))
    out = pl.pallas_call(
        functools.partial(_srht_t_kernel, a=a, b=b, k=k,
                          out_scale=1.0 / k ** 0.5),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, width), row_block),
        out_shape=jax.ShapeDtypeStruct((ym.shape[0], width), y.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(ym, rows.reshape(1, k), *h_ops)
    return out[:, :dim].reshape(y.shape[:-1] + (dim,))
