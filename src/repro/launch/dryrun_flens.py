import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
).strip()

"""Dry-run of the PAPER'S OWN communication pattern on the production mesh.

Clients are data-parallel mesh slices (one shard of the global dataset per
(pod, data) slice); one FLeNS round is lowered with pjit so the uplink
aggregation appears as an explicit cross-client collective in the HLO:

  * flens      — all-reduce of the k x k sketched Hessian + k-dim sketched
                 gradient  (the O(k^2) wire cost of the paper's Table I)
  * fedns      — all-reduce of the (k x M) sketched sqrt-Hessian + M-dim
                 gradient  (O(kM))
  * fednewton  — all-reduce of the full M x M Hessian + M-dim gradient
                 (O(M^2))

The measured collective bytes per round reproduce Table I's communication
column structurally — on the compiled production topology rather than on
paper. Results land in results/dryrun_flens/.

  PYTHONPATH=src python -m repro.launch.dryrun_flens --dim 4096 --k 256
"""
import argparse
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.base import root_key


def build_round(method: str, dim: int, k: int, n_per_client: int, lam: float):
    """Returns fn(X, y, w, seed_signs, rows) -> w_next for one round."""

    def hess_sqrt(X, y, w):
        margins = y * (X @ w)
        pr = jax.nn.sigmoid(margins)
        d = pr * (1 - pr)
        return X * jnp.sqrt(d / X.shape[0])[:, None]

    def grad(X, y, w):
        margins = y * (X @ w)
        s = jax.nn.sigmoid(-margins)
        return -(X.T @ (s * y)) / X.shape[0] + lam * w

    def flens_round(X, y, w, signs, rows):
        # per-client (= per data shard) quantities; mean over the client
        # axis IS the server aggregation (psum emitted by pjit).
        # The SRHT is the shared repro.core.sketch operator (dim a power
        # of two here, so the padded domain is the native one): the
        # roofline dry-run lowers the SAME srht_apply/srht_apply_t code
        # path — repro.kernels.ops dispatch included — that the bench
        # gate times, instead of a private inline copy.
        from repro.core.sketch import SrhtSketch

        s = SrhtSketch(k=k, dim=dim, signs=signs, rows=rows)
        a = hess_sqrt(X, y, w)  # (n, dim)
        b = s.apply(a)  # (n, k)
        h_sk = b.T @ b  # (k, k)  <- k^2 floats on the wire
        g_sk = s.apply(grad(X, y, w))  # (k,)
        h_sk = jax.lax.pmean(h_sk, ("pod", "data"))
        g_sk = jax.lax.pmean(g_sk, ("pod", "data"))
        sst = s.apply(s.apply_t(jnp.eye(k, dtype=w.dtype)))
        delta_k = jnp.linalg.solve(h_sk + lam * sst + 1e-8 * jnp.eye(k), g_sk)
        return w - s.apply_t(delta_k)

    return flens_round


def lower_method(method: str, mesh, dim: int, k: int, n_per_client: int,
                 lam: float = 1e-3):
    from repro.launch.hlo_stats import collective_stats

    n_clients = int(np.prod(mesh.devices.shape))
    if method == "flens":
        fn = build_round("flens", dim, k, n_per_client, lam)
        wire = k * k + k
    elif method == "fednewton":
        def fn(X, y, w, signs, rows):
            margins = y * (X @ w)
            pr = jax.nn.sigmoid(margins)
            d = pr * (1 - pr)
            h = (X.T * d) @ X / X.shape[0] + lam * jnp.eye(dim, dtype=w.dtype)
            s = jax.nn.sigmoid(-margins)
            g = -(X.T @ (s * y)) / X.shape[0] + lam * w
            h = jax.lax.pmean(h, ("pod", "data"))  # M x M on the wire
            g = jax.lax.pmean(g, ("pod", "data"))
            return w - jnp.linalg.solve(h, g)
        wire = dim * dim + dim
    elif method == "fedns":
        def fn(X, y, w, signs, rows):
            margins = y * (X @ w)
            pr = jax.nn.sigmoid(margins)
            d = pr * (1 - pr)
            a = X * jnp.sqrt(d / X.shape[0])[:, None]
            # per-client gaussian data-axis sketch (k x n) @ (n, dim):
            # every client shares one FIXED sketch seed (the FedNS wire
            # contract — the server must re-materialize the same S)
            key = root_key(0)
            s_mat = jax.random.normal(key, (k, X.shape[0]), w.dtype) / jnp.sqrt(
                jnp.asarray(k, w.dtype))
            sa = s_mat @ a  # (k, dim) on the wire per client
            s = jax.nn.sigmoid(-margins)
            g = -(X.T @ (s * y)) / X.shape[0] + lam * w
            # FedNS semantics: the server receives every client's (k, M)
            # sketch and sums the outer products — on the mesh this is an
            # all-gather over the client axis (a star-topology uplink has
            # no cheaper collective equivalent on a torus; see EXPERIMENTS)
            sa_all = jax.lax.all_gather(sa, "data")  # (n_data, k, dim)
            sa_all = jax.lax.all_gather(sa_all, "pod")  # (n_pod, n_data, k, dim)
            sa_flat = sa_all.reshape(-1, dim)
            h = (jnp.einsum("ka,kb->ab", sa_flat, sa_flat)
                 / (sa_all.shape[0] * sa_all.shape[1])
                 + lam * jnp.eye(dim, dtype=w.dtype))
            g = jax.lax.pmean(g, ("pod", "data"))
            return w - jnp.linalg.solve(h, g)
        wire = k * dim + dim
    else:
        raise ValueError(method)

    n2 = dim  # power-of-two dim assumed
    X = jax.ShapeDtypeStruct((n_clients * n_per_client, dim), jnp.float32)
    yv = jax.ShapeDtypeStruct((n_clients * n_per_client,), jnp.float32)
    w = jax.ShapeDtypeStruct((dim,), jnp.float32)
    signs = jax.ShapeDtypeStruct((n2,), jnp.float32)
    rows = jax.ShapeDtypeStruct((k,), jnp.int32)

    data_axes = P(("pod", "data"), None)
    shardings = (
        NamedSharding(mesh, data_axes),
        NamedSharding(mesh, P(("pod", "data"))),
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P()),
    )

    wrapped = jax.shard_map(
        lambda X, y, w, signs, rows: fn(X, y, w[0], signs[0], rows[0])[None],
        mesh=mesh,
        in_specs=(P(("pod", "data"), None), P(("pod", "data")), P(None),
                  P(None), P(None)),
        out_specs=P(None),
        check_vma=False,
    )
    # broadcast-shaped w/signs/rows so shard_map replicates them
    args = (X, yv,
            jax.ShapeDtypeStruct((1, dim), jnp.float32),
            jax.ShapeDtypeStruct((1, n2), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.int32))
    lowered = jax.jit(wrapped).lower(*args)
    compiled = lowered.compile()
    coll = collective_stats(compiled.as_text())
    return {
        "method": method,
        "theory_wire_floats_per_client": wire,
        "collective_bytes_per_device": coll["total_bytes"],
        "collectives": coll["per_kind"],
        "flops_per_device": float(compiled.cost_analysis().get("flops", 0.0)),
    }


def main() -> None:
    from repro.launch.mesh import make_production_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=4096)
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--n-per-client", type=int, default=2048)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun_flens")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=True)  # clients = pod x data = 32
    out = []
    for method in ("flens", "fedns", "fednewton"):
        rec = lower_method(method, mesh, args.dim, args.k, args.n_per_client)
        out.append(rec)
        print(f"{method:>10}: theory={rec['theory_wire_floats_per_client']:,} "
              f"floats/client; measured collective "
              f"{rec['collective_bytes_per_device']/1e6:.2f} MB/device",
              flush=True)
    pathlib.Path(args.out).mkdir(parents=True, exist_ok=True)
    (pathlib.Path(args.out) / "comm_rounds.json").write_text(
        json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
