"""Logical sharding rules: param/state/batch pytrees -> PartitionSpecs.

Strategy (baseline; alternatives measured in EXPERIMENTS.md §Perf):
  * batch dims            -> ("pod", "data")
  * vocab / heads / d_ff / experts (parallelizable width) -> "model"
  * weight d_model dims   -> "data"   (FSDP: all-gather on use,
                                       reduce-scatter on grad)
  * KV-cache sequence     -> "model"  (sequence-parallel decode attention);
                             batch=1 long-context shards seq over
                             ("data", "model") as well
  * every assignment is divisibility-guarded: a dim that does not divide
    by the mesh axis product falls back to replication (e.g. 8 KV heads
    on a 16-way model axis).

The rules are name-based over the param tree paths produced by the model
zoo — the single place where layout policy lives.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, GetAttrKey, SequenceKey


def _axsize(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return n


def _guard(mesh: Mesh, shape, spec_axes) -> P:
    """Drop axis assignments that don't divide or aren't in the mesh."""
    out = []
    for dim, axes in zip(shape, spec_axes):
        if axes is None:
            out.append(None)
            continue
        cand = (axes,) if isinstance(axes, str) else tuple(axes)
        cand = tuple(a for a in cand if a in mesh.axis_names)
        # progressively drop trailing axes until divisible
        while cand and dim % _axsize(mesh, cand) != 0:
            cand = cand[:-1]
        if not cand:
            out.append(None)
        elif len(cand) == 1:
            out.append(cand[0])
        else:
            out.append(cand)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _path_names(path) -> list[str]:
    names = []
    for k in path:
        if isinstance(k, DictKey):
            names.append(str(k.key))
        elif isinstance(k, SequenceKey):
            names.append(str(k.idx))
        elif isinstance(k, GetAttrKey):
            names.append(k.name)
    return names


# -- parameter rules ----------------------------------------------------------

def param_spec(mesh: Mesh, path, leaf) -> P:
    names = _path_names(path)
    name = names[-1]
    shape = leaf.shape
    nd = len(shape)
    fsdp, mdl = "data", "model"

    if nd <= 1:
        return P()
    if name == "table":  # (V, D)
        return _guard(mesh, shape, (mdl, fsdp))
    if name == "lm_head":  # (D, V)
        return _guard(mesh, shape, (fsdp, mdl))
    if name in ("wq",):  # (L?, D, H, Dh)
        base = (fsdp, mdl, None)
        return _guard(mesh, shape, (None,) * (nd - 3) + base)
    if name in ("wk", "wv"):  # (L?, Dkv_in, Hkv, Dh)
        base = (fsdp, mdl, None)
        return _guard(mesh, shape, (None,) * (nd - 3) + base)
    if name == "wo":  # (L?, H, Dh, D)
        base = (mdl, None, fsdp)
        return _guard(mesh, shape, (None,) * (nd - 3) + base)
    if name in ("w_gate", "w_up"):
        # expert tensors are direct children of "moe": (L?, E, D, F);
        # plain mlp (incl. the moe *shared* expert) is (L?, D, F)
        if nd >= 3 and len(names) >= 2 and names[-2] == "moe":
            base = (mdl, fsdp, None)  # (E, D, F)
            return _guard(mesh, shape, (None,) * (nd - 3) + base)
        base = (fsdp, mdl)  # (D, F)
        return _guard(mesh, shape, (None,) * (nd - 2) + base)
    if name == "w_down":
        if nd >= 3 and len(names) >= 2 and names[-2] == "moe":
            base = (mdl, None, fsdp)  # (E, F, D)
            return _guard(mesh, shape, (None,) * (nd - 3) + base)
        base = (mdl, fsdp)  # (F, D)
        return _guard(mesh, shape, (None,) * (nd - 2) + base)
    if name == "router":
        return P()
    if name == "w_in":  # ssd (L?, D, X)
        base = (fsdp, mdl)
        return _guard(mesh, shape, (None,) * (nd - 2) + base)
    if name in ("w_x", "w_gate2", "w_a", "w_i"):  # rglru (L?, D, D)
        base = (fsdp, mdl)
        return _guard(mesh, shape, (None,) * (nd - 2) + base)
    if name == "w_out":  # (L?, Din, D)
        base = (mdl, fsdp)
        return _guard(mesh, shape, (None,) * (nd - 2) + base)
    if name == "conv_w":
        return P()
    if name == "vision_proj":  # (Dv, D)
        return _guard(mesh, shape, (None, fsdp))
    # default: replicate trailing structure, fsdp on the largest dim if big
    if nd >= 2 and int(np.prod(shape)) > 1_000_000:
        base = [None] * nd
        base[-2] = fsdp
        base[-1] = mdl
        return _guard(mesh, shape, tuple(base))
    return P()


# -- decode-state rules ---------------------------------------------------------

def state_spec(mesh: Mesh, path, leaf, *, batch: int) -> P:
    names = _path_names(path)
    name = names[-1]
    shape = leaf.shape
    nd = len(shape)
    batch_axes = "data" if batch > 1 else None
    seq_axes = ("model",) if batch > 1 else ("data", "model")

    if name in ("k", "v"):  # (G, [per,] B, S, Hkv, Dh)
        lead = nd - 4  # layer (and vlm per-layer) dims stay replicated
        spec = (None,) * lead + (batch_axes, seq_axes, None, None)
        return _guard(mesh, shape, spec)
    if name in ("cross_k", "cross_v"):  # (G, B, Sv, Hkv, Dh)
        return _guard(mesh, shape, (None, batch_axes, None, None, None))
    if name == "pos":  # (G, [per,] B, S) — follows the cache sharding
        return _guard(mesh, shape,
                      (None,) * (nd - 2) + (batch_axes, seq_axes))
    if name == "ssm":  # (G, B, H, N, P)
        return _guard(mesh, shape, (None, batch_axes, "model", None, None))
    if name == "conv":  # (G, B, K-1, C)
        return _guard(mesh, shape, (None, batch_axes, None, None))
    if name in ("h", "h0", "h1"):  # (G, B, D)
        return _guard(mesh, shape, (None, batch_axes, None))
    if name in ("conv0", "conv1"):
        return _guard(mesh, shape, (None, batch_axes, None, None))
    if name == "index":
        return P()
    return P()


# -- batch rules -----------------------------------------------------------------

def batch_spec(mesh: Mesh, path, leaf) -> P:
    shape = leaf.shape
    return _guard(mesh, shape, (("pod", "data"),) + (None,) * (len(shape) - 1))


def tree_shardings(mesh: Mesh, tree, rule, **kw):
    """Map a spec rule over a pytree -> NamedSharding pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, rule(mesh, path, leaf, **kw)),
        tree,
    )


# -- federated cohort rules ---------------------------------------------------

def _client_axis(mesh) -> "str | None":
    """The first client-capable axis of ``mesh`` (``clients``, else
    ``data``), or None."""
    axes = [a for a in ("clients", "data") if a in mesh.axis_names]
    return axes[0] if axes else None


def cohort_spec(mesh: Mesh, leaf) -> P:
    """PartitionSpec for one cohort-stacked array: shard the leading
    (client) axis over the first available client-capable mesh axis,
    replicate everything else. A cohort whose client axis does not
    divide that mesh axis raises: replicating it instead would run every
    client on every device while the caller asked for a split."""
    axis = _client_axis(mesh)
    if axis is None or leaf.ndim == 0:
        return P()
    ways = mesh.shape[axis]
    if leaf.shape[0] % ways:
        raise ValueError(
            f"a cohort of {leaf.shape[0]} clients does not divide the "
            f"{ways}-way {axis!r} mesh axis; use a cohort size that is "
            f"a multiple of {ways}")
    return P(axis)


def shard_cohort(mesh: Mesh, cohort):
    """Place a cohort pytree (``FederatedProblem`` of one sampled
    cohort) with its client axis sharded over ``mesh``.

    This is how a population-mode round spreads over devices: the jitted
    round is vmapped over the client axis, so GSPMD partitions every
    per-client computation along the mesh and the server aggregation
    becomes a cross-device reduction. The sessions trace the round under
    ``client_mesh_scope`` so that ``vmap_clients`` maps the per-client
    kernel calls device by device. A 1-device mesh is the identity
    placement.
    """
    return jax.tree_util.tree_map(
        lambda leaf: jax.device_put(
            leaf, NamedSharding(mesh, cohort_spec(mesh, leaf))),
        cohort,
    )


def client_mesh_scope(mesh: "Mesh | None"):
    """Context in which a round over a ``shard_cohort`` cohort is traced
    and run: sets ``mesh`` as JAX's current mesh (nothing without one)."""
    return contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh)


def vmap_clients(fn):
    """``jax.vmap(fn)`` over the leading client axis of every argument.

    Under a current mesh with a client axis (``client_mesh_scope``) each
    device maps only its own clients, inside a ``shard_map``: the
    compiler cannot partition a Pallas kernel, so a vmapped kernel over
    a sharded client axis would not compile for the chip. Without such
    a mesh this is exactly ``jax.vmap(fn)``.
    """
    axis = _client_axis(jax.sharding.get_abstract_mesh())
    if axis is None:
        return jax.vmap(fn)
    return jax.shard_map(jax.vmap(fn), in_specs=P(axis), out_specs=P(axis),
                         check_vma=False)
