"""Pallas TPU kernel: Fast Walsh-Hadamard Transform (the SRHT hot loop).

TPU adaptation (DESIGN.md §3): instead of emulating the GPU butterfly
(warp shuffles) on the VPU, the length-N transform is factored as

    H_N = (H_A (x) I_B) . (I_A (x) H_B),      N = A * B

so a row reshaped to (A, B) is transformed by two *dense matmuls* with
small Hadamard matrices:  Y = H_A @ X @ H_B. Both factors are <=128 wide,
i.e. exactly MXU-shaped. Rows are tiled into VMEM blocks; the Hadamard
factors ride along as (tiny) kernel inputs. For N <= 128 the row fits one
lane tile and the transform is the single matmul X @ H_N (Mosaic cannot
reshape a row narrower than a lane tile).

The compiled path takes 32- and 16-bit floats only (``check_compiled``);
index maps return int32 so the kernels lower with x64 on or off.

Validated against ``repro.kernels.ref.fwht`` in interpret mode (CPU) by
``tests/test_kernels_fwht.py``; compiled for a v5e by
``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import hadamard_matrix

# widest transform whose row block (plus the two-matmul intermediates)
# fits the kernels' VMEM budget; compiled for a v5e in the test suite
MAX_WIDTH = 65536

# full-f32 MXU passes: the one-pass bf16 default would round every input
# to 8 bits, which the reference butterfly does not
EXACT = jax.lax.Precision.HIGHEST

# the VMEM budget of the row-tiled transform kernels: at MAX_WIDTH the
# f32 passes of an 8-row block need more than the 16 MiB default scope
# (a v5e core has 128 MiB)
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 1024 * 1024)


def _factor(n: int) -> tuple[int, int]:
    """n = a * b with both <= 128 when possible (n a power of two)."""
    if n <= 0 or n & (n - 1) != 0:
        raise ValueError(f"FWHT length must be a power of two, got {n}")
    if n > MAX_WIDTH:
        raise ValueError(
            f"FWHT length {n} exceeds the kernel limit MAX_WIDTH={MAX_WIDTH}")
    b = min(n, 128)
    a = n // b
    while a > 128:  # n > 16384: grow b beyond 128 (still a power of 2)
        b *= 2
        a = n // b
    return a, b


def check_compiled(*operands: jax.Array, interpret: bool) -> None:
    """Refuse 64-bit operands on the compiled path: Mosaic has no 64-bit
    vector types, and a silent cast would change the arithmetic."""
    if interpret:
        return
    for x in operands:
        if jnp.dtype(x.dtype).itemsize == 8:
            raise TypeError(
                f"compiled Pallas kernels take float32/bfloat16 operands, "
                f"got {jnp.dtype(x.dtype).name}; run with x64 off or build "
                f"the data in float32")


def row_block(i):
    """Index map of a row-tiled operand: block (i, 0), as int32."""
    return i, jnp.int32(0)


def whole_block(i):
    """Index map of an operand every grid step reads whole."""
    del i
    return jnp.int32(0), jnp.int32(0)


def fwht_rows(x, ha, hb, a: int, b: int):
    """Unnormalized length-(a*b) WHT of the f32 rows of x (rows, a*b)."""
    if a == 1:
        return jax.lax.dot_general(x, hb, (((1,), (0,)), ((), ())),
                                   precision=EXACT,
                                   preferred_element_type=jnp.float32)
    rows = x.shape[0]
    y = x.reshape(rows, a, b)
    y = jax.lax.dot_general(y, hb, (((2,), (0,)), ((), ())),
                            precision=EXACT,
                            preferred_element_type=jnp.float32)
    y = jnp.einsum("rab,ca->rcb", y, ha, precision=EXACT)
    return y.reshape(rows, a * b)


def _fwht_kernel(x_ref, ha_ref, hb_ref, o_ref, *, a: int, b: int, norm: float):
    y = fwht_rows(x_ref[...].astype(jnp.float32),
                  ha_ref[...].astype(jnp.float32),
                  hb_ref[...].astype(jnp.float32), a, b)
    o_ref[...] = (y * norm).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("normalize", "block_rows", "interpret"))
def fwht_pallas(x: jax.Array, *, normalize: bool = False, block_rows: int = 8,
                interpret: bool = False) -> jax.Array:
    """WHT along the last axis. x (..., N), N a power of two."""
    check_compiled(x, interpret=interpret)
    orig_shape = x.shape
    n = orig_shape[-1]
    a, b = _factor(n)
    rows = 1
    for d in orig_shape[:-1]:
        rows *= d
    xm = x.reshape(rows, n)
    pad = (-rows) % block_rows
    if pad:
        xm = jnp.pad(xm, ((0, pad), (0, 0)))
    ha = hadamard_matrix(a, jnp.float32)
    hb = hadamard_matrix(b, jnp.float32)
    norm = (1.0 / n**0.5) if normalize else 1.0

    out = pl.pallas_call(
        functools.partial(_fwht_kernel, a=a, b=b, norm=norm),
        grid=(xm.shape[0] // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, n), row_block),
            pl.BlockSpec((a, a), whole_block),
            pl.BlockSpec((b, b), whole_block),
        ],
        out_specs=pl.BlockSpec((block_rows, n), row_block),
        out_shape=jax.ShapeDtypeStruct(xm.shape, x.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
    )(xm, ha, hb)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
