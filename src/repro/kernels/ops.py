"""Kernel dispatch: a backend registry with explicit impl selection.

Every compute hot spot in the stack is exposed here as a named *op* with
interchangeable implementations registered per backend:

  * ``"ref"``       — pure-jnp oracle from ``repro.kernels.ref``
                      (portable; the path every golden trajectory and
                      committed baseline is pinned to; alias
                      ``"reference"``)
  * ``"pallas"``    — compiled Pallas TPU kernel (TPU only; forcing it
                      off-TPU raises)
  * ``"interpret"`` — the Pallas kernel body interpreted on CPU (the
                      parity-test path: same body, no TPU)
  * ``"auto"``      — ``"pallas"`` on TPU, ``"ref"`` everywhere else

Selection precedence, most local wins:

  1. the per-call ``impl=`` argument,
  2. the process default set by ``set_default_impl`` / the ``use_impl``
     context manager,
  3. the ``REPRO_KERNEL_IMPL`` environment variable (CI job legs force
     ``REPRO_KERNEL_IMPL=ref`` to prove the reference path stays green),
  4. ``"auto"``.

Ops: ``fwht``, ``srht_apply``, ``srht_apply_t`` (the fused sketch hot
loop consumed by ``repro.core.sketch``), ``topk_mask``,
``qint8_roundtrip`` (the transport codec inner loops consumed by
``repro.comm.codecs``), and ``flash_attention``. Implementations are
registered lazily — Pallas modules import only when a pallas/interpret
impl is actually selected.

NOTE: resolution happens at Python trace time. Inside an already-traced
jit cache entry the choice is baked in; set the env var / default before
the first call (CI does).

Under a current mesh (``jax.set_mesh``, as population rounds over a
``client_mesh`` run) the compiler would have to partition a Pallas
kernel, which it cannot; the compiled kernel then runs whole on every
device, inside a ``shard_map`` with replicated operands. Per-client
calls avoid that replication by mapping each device's own clients
(``repro.sharding.rules.vmap_clients``).
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels import ref

ENV_VAR = "REPRO_KERNEL_IMPL"
IMPLS = ("auto", "pallas", "interpret", "ref")
_ALIASES = {"reference": "ref"}

_default_impl: str | None = None


def _canonical(impl: str) -> str:
    impl = _ALIASES.get(impl, impl)
    if impl not in IMPLS:
        raise ValueError(
            f"unknown kernel impl {impl!r}; expected one of {IMPLS} "
            f"(or alias {tuple(_ALIASES)})")
    return impl


def set_default_impl(impl: str | None) -> None:
    """Set the process-wide implementation default (``None`` clears it,
    falling back to ``REPRO_KERNEL_IMPL`` / ``"auto"``)."""
    global _default_impl
    _default_impl = None if impl is None else _canonical(impl)


@contextlib.contextmanager
def use_impl(impl: str | None):
    """Scoped ``set_default_impl`` — the config hook for tests and
    experiment drivers."""
    prev = _default_impl
    set_default_impl(impl)
    try:
        yield
    finally:
        set_default_impl(prev)


def resolve_impl(impl: str | None = None) -> str:
    """Resolve per-call > config > env > auto down to a concrete impl."""
    choice = impl or _default_impl or os.environ.get(ENV_VAR) or "auto"
    choice = _canonical(choice)
    if choice == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return choice


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

# op -> impl -> loader() -> callable. Loaders keep Pallas imports lazy;
# resolved callables are cached on first use.
_REGISTRY: dict[str, dict[str, Callable[[], Callable]]] = {}


def register_impl(op: str, impl: str):
    """Register ``loader() -> callable`` as ``op``'s ``impl`` backend."""
    def deco(loader: Callable[[], Callable]):
        _REGISTRY.setdefault(op, {})[_canonical(impl)] = loader
        return loader
    return deco


def available_impls(op: str) -> tuple[str, ...]:
    if op not in _REGISTRY:
        raise KeyError(f"unknown kernel op {op!r}; have {sorted(_REGISTRY)}")
    return tuple(sorted(_REGISTRY[op]))


@functools.lru_cache(maxsize=None)
def get_impl(op: str, impl: str) -> Callable:
    """The concrete callable for (op, impl); raises with the available
    backends when the combination is not registered."""
    impls = _REGISTRY.get(op)
    if impls is None:
        raise KeyError(f"unknown kernel op {op!r}; have {sorted(_REGISTRY)}")
    if impl == "pallas" and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"impl='pallas' for op {op!r} requires a TPU backend (running "
            f"on {jax.default_backend()!r}); use impl='interpret' to run "
            "the kernel body here, or impl='ref' for the oracle")
    loader = impls.get(impl)
    if loader is None:
        raise KeyError(
            f"op {op!r} has no {impl!r} implementation; "
            f"available: {available_impls(op)}")
    return loader()


def _dispatch(op: str, impl: str | None) -> Callable:
    resolved = resolve_impl(impl)
    fn = get_impl(op, resolved)
    mesh = jax.sharding.get_abstract_mesh()
    if resolved != "pallas" or mesh.size <= 1 or mesh.are_all_axes_manual:
        return fn

    def on_every_device(*arrays, **static):
        return jax.shard_map(functools.partial(fn, **static), in_specs=P(),
                             out_specs=P(), check_vma=False)(*arrays)
    return on_every_device


# ---------------------------------------------------------------------------
# public ops
# ---------------------------------------------------------------------------

def fwht(x: jax.Array, *, normalize: bool = False,
         impl: str | None = None) -> jax.Array:
    """Walsh-Hadamard transform along the last axis."""
    return _dispatch("fwht", impl)(x, normalize=normalize)


def srht_apply(x: jax.Array, signs: jax.Array, rows: jax.Array, *,
               impl: str | None = None) -> jax.Array:
    """Fused SRHT forward: sign-flip -> FWHT -> row-subsample.
    x (..., dim) -> (..., k); n = signs.shape[-1], k = rows.shape[-1]."""
    return _dispatch("srht_apply", impl)(x, signs, rows)


def srht_apply_t(y: jax.Array, signs: jax.Array, rows: jax.Array,
                 dim: int, *, impl: str | None = None) -> jax.Array:
    """Fused SRHT transpose: scatter -> FWHT -> sign-flip -> restrict.
    y (..., k) -> (..., dim)."""
    return _dispatch("srht_apply_t", impl)(y, signs, rows, dim=dim)


def topk_mask(x: jax.Array, kept: int, *,
              impl: str | None = None) -> jax.Array:
    """Keep the ``kept`` largest-|.| entries of ``x`` (dense mask; ties
    broken by lowest flat index, as ``jax.lax.top_k``)."""
    return _dispatch("topk_mask", impl)(x, kept=kept)


def qint8_roundtrip(x: jax.Array, u: jax.Array, *,
                    impl: str | None = None) -> jax.Array:
    """Per-tensor symmetric int8 quantize->dequantize; ``u ~ U[0,1)``
    (x's shape) is the caller-supplied stochastic-rounding noise."""
    return _dispatch("qint8_roundtrip", impl)(x, u)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window=None,  # None | int | traced scalar (per-layer metadata)
    q_offset: int = 0,
    impl: str | None = None,
    block_q: int = 512,
    block_k: int = 1024,
) -> jax.Array:
    """Grouped-query flash attention, (B, T, H, D) layout.

    Not jitted here (callers jit the whole step); ``window`` may be a
    traced scalar so it cannot be a static argument.
    """
    return _dispatch("flash_attention", impl)(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k)


# ---------------------------------------------------------------------------
# implementation registrations
# ---------------------------------------------------------------------------

@register_impl("fwht", "ref")
def _fwht_ref():
    return jax.jit(ref.fwht, static_argnames=("normalize",))


@register_impl("fwht", "pallas")
def _fwht_pallas():
    from repro.kernels.fwht import fwht_pallas
    return fwht_pallas


@register_impl("fwht", "interpret")
def _fwht_interpret():
    from repro.kernels.fwht import fwht_pallas
    return functools.partial(fwht_pallas, interpret=True)


@register_impl("srht_apply", "ref")
def _srht_apply_ref():
    return jax.jit(ref.srht_apply)


@register_impl("srht_apply", "pallas")
def _srht_apply_pallas():
    from repro.kernels.srht import srht_apply_pallas
    return srht_apply_pallas


@register_impl("srht_apply", "interpret")
def _srht_apply_interpret():
    from repro.kernels.srht import srht_apply_pallas
    return functools.partial(srht_apply_pallas, interpret=True)


@register_impl("srht_apply_t", "ref")
def _srht_apply_t_ref():
    return jax.jit(ref.srht_apply_t, static_argnames=("dim",))


@register_impl("srht_apply_t", "pallas")
def _srht_apply_t_pallas():
    from repro.kernels.srht import srht_apply_t_pallas
    return srht_apply_t_pallas


@register_impl("srht_apply_t", "interpret")
def _srht_apply_t_interpret():
    from repro.kernels.srht import srht_apply_t_pallas
    return functools.partial(srht_apply_t_pallas, interpret=True)


@register_impl("topk_mask", "ref")
def _topk_mask_ref():
    return jax.jit(ref.topk_mask, static_argnames=("kept",))


@register_impl("topk_mask", "pallas")
def _topk_mask_pallas():
    from repro.kernels.codec_kernels import topk_mask_pallas
    return topk_mask_pallas


@register_impl("topk_mask", "interpret")
def _topk_mask_interpret():
    from repro.kernels.codec_kernels import topk_mask_pallas
    return functools.partial(topk_mask_pallas, interpret=True)


@register_impl("qint8_roundtrip", "ref")
def _qint8_ref():
    return jax.jit(ref.qint8_roundtrip)


@register_impl("qint8_roundtrip", "pallas")
def _qint8_pallas():
    from repro.kernels.codec_kernels import qint8_roundtrip_pallas
    return qint8_roundtrip_pallas


@register_impl("qint8_roundtrip", "interpret")
def _qint8_interpret():
    from repro.kernels.codec_kernels import qint8_roundtrip_pallas
    return functools.partial(qint8_roundtrip_pallas, interpret=True)


@register_impl("flash_attention", "ref")
def _flash_attention_ref():
    return ref.mha_blocked


@register_impl("flash_attention", "pallas")
def _flash_attention_pallas():
    from repro.kernels import flash_attention as fa

    def run(q, k, v, *, causal, window, q_offset, block_q, block_k):
        return fa.flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            block_q=min(block_q, 128), block_k=min(block_k, 128))
    return run


@register_impl("flash_attention", "interpret")
def _flash_attention_interpret():
    from repro.kernels import flash_attention as fa

    def run(q, k, v, *, causal, window, q_offset, block_q, block_k):
        return fa.flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            block_q=min(block_q, 128), block_k=min(block_k, 128),
            interpret=True)
    return run
