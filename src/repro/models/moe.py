"""Mixture-of-Experts FFN with expert-parallel sharding.

Baseline distribution strategy (see DESIGN.md §5 and EXPERIMENTS.md §Perf
for the measured alternatives):

  * tokens enter replicated across the ``model`` axis (the residual
    stream is sharded over batch only);
  * expert weights are sharded E -> ``model`` (and D -> ``data`` FSDP on
    the big configs, all-gathered per layer inside the block);
  * every model-shard routes all of its data-shard's tokens, keeps the
    assignments that belong to its local experts, computes them with a
    capacity-bounded gather -> grouped-matmul -> scatter-add, and the
    partial outputs are ``psum``'d over ``model``.

Routing is top-k softmax with a Switch-style load-balance auxiliary loss
and capacity-factor token dropping (drop fraction returned for tests /
telemetry). A dense fallback path (no mesh) runs the identical math on
one shard so smoke tests exercise the same code.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig, dense_init, residual_out_init
from repro.sharding.ctx import get_mesh


def moe_init(key, cfg: ModelConfig):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    params = {
        "router": dense_init(ks[0], d, e, cfg),
        "w_gate": dense_init(ks[1], d, f, cfg, shape=(e, d, f)),
        "w_up": dense_init(ks[2], d, f, cfg, shape=(e, d, f)),
        "w_down": residual_out_init(ks[3], f, d, cfg, shape=(e, f, d), fan_in=f),
    }
    if cfg.n_shared_experts:
        from repro.models.common import mlp_init

        params["shared"] = mlp_init(ks[4], cfg, d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return params


def _route(router_w, x_flat, cfg: ModelConfig):
    """Top-k routing. Returns (ids (T,k), weights (T,k), aux_loss, probs)."""
    logits = (x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    weights, ids = jax.lax.top_k(probs, cfg.top_k)  # (T, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch load-balance loss: E * sum_e f_e * p_e
    e = cfg.n_experts
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=1), axis=0
    )  # fraction of tokens routed to e
    aux = e * jnp.sum(me * ce)
    return ids, weights, aux


def _expert_compute(w_gate, w_up, w_down, xs, cfg: ModelConfig):
    """Grouped gated-MLP over per-expert capacity buffers.

    xs: (E_loc, C, D) -> (E_loc, C, D)
    """
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(jnp.einsum("ecd,edf->ecf", xs, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", xs, w_up
    )
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_shard_body(x_flat, router_w, w_gate, w_up, w_down, *,
                    cfg: ModelConfig, n_exp_shards: int, shard_idx,
                    capacity: int, model_axis: str | None):
    """Per-(data, model)-shard MoE. x_flat (T, D) replicated over model."""
    t, d = x_flat.shape
    e = cfg.n_experts
    e_loc = e // n_exp_shards
    ids, weights, aux = _route(router_w, x_flat, cfg)  # (T,k)

    flat_ids = ids.reshape(-1)  # (T*k,)
    flat_w = weights.reshape(-1)
    tok_of = jnp.repeat(jnp.arange(t), cfg.top_k)  # (T*k,)

    local_e = flat_ids - shard_idx * e_loc  # local expert index or OOB
    is_local = (local_e >= 0) & (local_e < e_loc)
    # position within each local expert: cumsum over one-hot assignment
    onehot = jax.nn.one_hot(jnp.where(is_local, local_e, e_loc), e_loc + 1,
                            dtype=jnp.int32)[:, :e_loc]  # (T*k, E_loc)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot  # exclusive prefix count
    pos = jnp.sum(pos_in_e * onehot, axis=1)  # (T*k,)
    keep = is_local & (pos < capacity)

    # scatter token rows into (E_loc, C, D)
    slot = jnp.where(keep, local_e * capacity + pos, e_loc * capacity)
    buf = jnp.zeros((e_loc * capacity + 1, d), x_flat.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], x_flat[tok_of], 0.0))
    xs = buf[:-1].reshape(e_loc, capacity, d)

    ys = _expert_compute(w_gate, w_up, w_down, xs, cfg)  # (E_loc, C, D)

    # combine: weighted scatter-add back to tokens
    ys_flat = ys.reshape(e_loc * capacity, d)
    contrib = jnp.where(
        keep[:, None], ys_flat[jnp.minimum(slot, e_loc * capacity - 1)], 0.0
    ) * flat_w[:, None].astype(x_flat.dtype)
    out = jnp.zeros_like(x_flat).at[tok_of].add(contrib)

    drop_frac = 1.0 - jnp.sum(keep.astype(jnp.float32)) / (
        jnp.sum(is_local.astype(jnp.float32)) + 1e-9
    )
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
        aux = aux  # identical on every model shard (same tokens)
        drop_frac = jax.lax.pmean(drop_frac, model_axis)
    return out, aux, drop_frac


def moe_apply(params, x, cfg: ModelConfig, *, capacity: int | None = None):
    """MoE FFN. x (B, T, D) -> (out (B,T,D), aux_loss, drop_frac)."""
    b, t, d = x.shape
    mesh = get_mesh()
    n_exp_shards = (
        mesh.shape["model"] if (mesh is not None and "model" in mesh.axis_names) else 1
    )
    # per-shard token count (tokens replicated over model; sharded over data/pod)
    n_data_shards = 1
    if mesh is not None:
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                n_data_shards *= mesh.shape[ax]
    t_shard = (b // n_data_shards) * t
    if capacity is None:
        capacity = max(
            4,
            int(cfg.capacity_factor * cfg.top_k * t_shard
                / max(cfg.n_experts, 1)),
        )
    capacity = min(capacity, t_shard * cfg.top_k)

    x_flat_shape_batch = x.reshape(b * t, d)

    if mesh is None or n_exp_shards == 1 and n_data_shards == 1:
        out, aux, drop = _moe_shard_body(
            x_flat_shape_batch, params["router"], params["w_gate"],
            params["w_up"], params["w_down"], cfg=cfg, n_exp_shards=1,
            shard_idx=0, capacity=capacity, model_axis=None,
        )
        out = out.reshape(b, t, d)
    elif b * t <= 4096 and "data" in mesh.axis_names:
        # DECODE path (EXPERIMENTS.md SS-Perf extra iteration): tokens are
        # tiny (B x 1) while the fsdp-sharded expert weights are huge, so
        # gather the ACTIVATIONS over the fsdp axis (KBs) instead of the
        # weights (GBs per layer): every data shard computes all tokens
        # against its F-slice of the local experts (the gated MLP is
        # elementwise in F), partial outputs psum over ("data", "model"),
        # and each shard keeps its own token rows again. Expert weights
        # must arrive F-sharded over "data" (serving layout,
        # input_specs._serving_param_shardings).
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        n_data = mesh.shape["data"]
        cap_dec = max(4, int(cfg.capacity_factor * cfg.top_k * b * t
                             / max(cfg.n_experts, 1)))
        cap_dec = min(cap_dec, b * t * cfg.top_k)

        def body(xb, router_w, wg, wu, wd):
            bl, tl, dl = xb.shape
            midx = jax.lax.axis_index("model")
            didx = jax.lax.axis_index("data")
            x_all = jax.lax.all_gather(xb.reshape(bl * tl, dl), "data",
                                       tiled=True)  # (n_data*bl*tl, D)
            o, aux, drop = _moe_shard_body(
                x_all, router_w, wg, wu, wd, cfg=cfg,
                n_exp_shards=n_exp_shards, shard_idx=midx,
                capacity=cap_dec, model_axis=None,
            )
            # o is partial over BOTH the F-slice ("data") and the local
            # experts ("model")
            o = jax.lax.psum(o, ("data", "model"))
            o_mine = jax.lax.dynamic_slice_in_dim(
                o, didx * bl * tl, bl * tl, axis=0)
            aux = jax.lax.pmean(aux, ("data", "model"))
            drop = jax.lax.pmean(drop, ("data", "model"))
            if "pod" in mesh.axis_names:
                aux = jax.lax.pmean(aux, "pod")
                drop = jax.lax.pmean(drop, "pod")
            return o_mine.reshape(bl, tl, dl), aux, drop

        out, aux, drop = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(batch_axes, None, None),  # x
                P(None, None),  # router
                P("model", None, "data"),  # w_gate (E, D, F): F fsdp-sharded
                P("model", None, "data"),  # w_up
                P("model", "data", None),  # w_down (E, F, D)
            ),
            out_specs=(P(batch_axes, None, None), P(), P()),
            check_vma=False,
        )(x, params["router"], params["w_gate"], params["w_up"],
          params["w_down"])
    else:
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

        def body(xb, router_w, wg, wu, wd):
            bl, tl, dl = xb.shape
            idx = jax.lax.axis_index("model")
            o, aux, drop = _moe_shard_body(
                xb.reshape(bl * tl, dl), router_w, wg, wu, wd, cfg=cfg,
                n_exp_shards=n_exp_shards, shard_idx=idx,
                capacity=capacity, model_axis="model",
            )
            # aux/drop: average over data shards for logging
            for ax in batch_axes:
                aux = jax.lax.pmean(aux, ax)
                drop = jax.lax.pmean(drop, ax)
            return o.reshape(bl, tl, dl), aux, drop

        out, aux, drop = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(batch_axes, None, None),  # x
                P(None, None),  # router
                P("model", None, None),  # w_gate (E, D, F)
                P("model", None, None),  # w_up
                P("model", None, None),  # w_down
            ),
            out_specs=(P(batch_axes, None, None), P(), P()),
            check_vma=False,
        )(x, params["router"], params["w_gate"], params["w_up"],
          params["w_down"])

    if cfg.n_shared_experts:
        from repro.models.common import mlp_apply

        out = out + mlp_apply(params["shared"], x, cfg)
    return out, aux, drop
