"""Smoke run of FLeNS's main path on a TPU: the quickest proof that the
system still starts on the chip. Not a benchmark.

    python chip_smoke.py [--seed N]              # one chip, phases (a)-(c)
    python chip_smoke.py --chips 4 [--seed N]    # the four-chip paths only

It drives the public API the README documents (``make_problem`` or
``SyntheticPopulation``, ``make_optimizer``, ``CommConfig``,
``run_rounds``) with x64 off and float32 data made from ``--seed``:

  (a) sync, dense: FLeNS on the SUSY twin at the paper's full size
      (n = 5,000,000, d = 18, m = 1000, k = 10) through a top-k
      sketched-Hessian uplink and qint8 elsewhere, so the SRHT, top-k
      and qint8 kernels all run. Run once on the Pallas kernels and once
      on the reference ops; the two must agree (``agree``).
  (b) population: the README's m = 100,000, dim = 16, Dirichlet-0.3
      population, a ``uniform:1e-3`` cohort (about 100 clients), FLeNS
      k = 8; Pallas against reference as in (a).
  (c) async: the phishing twin at full size (11,055 x 68, m = 40,
      k = 17) on the straggler edge channel with ``async_mode=True``;
      Pallas against reference as in (a).

``--chips 4`` runs only what exists across chips: the population run of
(b) with its cohort sharded over a four-device mesh against the same run
on one chip, and one ``DistributedFLeNS`` round on the mesh against the
simulator's round.

Each phase prints one line: gap at the first and last round, total wire
bytes, compile seconds, steady seconds per round (a smoke reading of the
host clock, rounds after the first, not a benchmark) and the device's
``peak_bytes_in_use``. No phase catches its own failure. The last line
is ``{"ok": true, "device": {...}}``; with no TPU the script exits
non-zero before any phase and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.paper_common import straggler_edge_channel  # noqa: E402
from repro.cache import enable_compile_cache  # noqa: E402
from repro.comm import ChannelModel, CommConfig, make_codec  # noqa: E402
from repro.core import (  # noqa: E402
    SyntheticPopulation,
    make_optimizer,
    make_problem,
    make_sketch,
    newton_solve,
    root_key,
    run_rounds,
)
from repro.core.losses import logistic  # noqa: E402
from repro.data.libsvm_like import (  # noqa: E402
    PAPER_DATASETS,
    load,
    make_classification,
)
from repro.kernels import ops as kops  # noqa: E402
from repro.obs import TelemetryConfig  # noqa: E402

LAM = 1e-3  # the paper's ridge weight on every dataset

# Two runs that differ only in which kernels serve the SRHT and codec
# ops agree when every round's gap is within GAP_TOL * gap[0] of the
# other's. Both are float32; the kernels sum in another order than the
# reference butterfly and gather (relative differences near 1e-7), but
# the top-k selection, the qint8 floor and FLeNS's accept/reject guard
# are discontinuous, so a near-tie can flip and the trajectories drift
# apart afterwards by about one quantization level of one client's
# payload. 1e-3 of the starting gap is far above that drift and far
# below what a wrong sketch or codec moves (a tenth of the gap or more
# within the first rounds).
GAP_TOL = 1e-3

# DistributedFLeNS against the simulator: the same float32 round with
# the aggregation as a psum across chips instead of a weighted sum over
# a vmapped axis; 1e-4 relative to max|w| leaves room for that
# reassociation (float32 rounding is near 1e-7).
W_RTOL = 1e-4


def _peak_bytes() -> "int | None":
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _report(phase: str, impl: str, hist) -> None:
    tel = hist.telemetry
    print(f"phase={phase} impl={impl} rounds={hist.rounds} "
          f"gap_first={float(hist.gap[0])!r} gap_last={float(hist.gap[-1])!r} "
          f"total_bytes={float(hist.cumulative_bytes[-1])!r} "
          f"compile_s={tel['compile_s']!r} "
          f"smoke_s_per_round={tel['exec_s_per_round']!r} "
          f"peak_bytes_in_use={_peak_bytes()}", flush=True)


def check_agree(phase: str, got, want, tol: float = GAP_TOL) -> None:
    """Equal wire bytes, and gap trajectories within ``tol * gap[0]``."""
    if not np.array_equal(got.cumulative_bytes, want.cumulative_bytes):
        raise AssertionError(
            f"{phase}: wire bytes differ: {got.cumulative_bytes[-1]!r} vs "
            f"{want.cumulative_bytes[-1]!r}")
    bound = tol * float(want.gap[0])
    dev = float(np.max(np.abs(got.gap - want.gap)))
    if not np.all(np.isfinite(got.gap)) or dev > bound:
        raise AssertionError(
            f"{phase}: gap trajectories differ by {dev!r} > {bound!r}: "
            f"{got.gap.tolist()} vs {want.gap.tolist()}")
    if not want.gap[-1] < want.gap[0]:
        raise AssertionError(f"{phase}: the gap did not shrink: "
                             f"{want.gap.tolist()}")
    print(f"phase={phase} agree=true max_gap_dev={dev!r} bound={bound!r} "
          f"bytes_equal=true", flush=True)


def pallas_vs_ref(phase: str, run) -> None:
    """``run()`` once on the platform's kernels, once on the reference
    ops, and hold the two to ``check_agree``."""
    fused = run()
    _report(phase, kops.resolve_impl(), fused)
    with kops.use_impl("ref"):
        ref = run()
    _report(phase, "ref", ref)
    check_agree(phase, fused, ref)


def _assert_kernel_in_hlo(fn, *specs) -> None:
    hlo = jax.jit(fn).lower(*specs).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("no Pallas kernel in the compiled HLO")


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_sync(seed: int, *, n: int = 5_000_000, rounds: int = 5) -> None:
    """(a) FLeNS on the full-size SUSY twin through lossy codecs."""
    spec = PAPER_DATASETS["susy"]
    X, y = make_classification(
        root_key(seed), n, spec.dim, spectrum_decay=spec.spectrum_decay,
        label_noise=spec.label_noise, dtype=jnp.float32)
    prob = make_problem(X, y, m=spec.m_clients, lam=LAM, objective=logistic,
                        key=root_key(seed, 1))
    del X, y
    w0 = jnp.zeros((spec.dim,), jnp.float32)
    w_star = newton_solve(prob, w0)
    comm = CommConfig(codecs={"h_sk": "topk0.25", "default": "qint8"},
                      seed=seed)

    # the kernels serve the phase's own shapes: the per-client sketch of
    # A_j and both codecs over the cohort's payloads
    f32 = jnp.float32
    k, (m, n_shard, dim) = spec.sketch_k, prob.X.shape
    s = make_sketch(root_key(seed, 2), "srht", k, dim, dtype=f32)
    keys = jax.ShapeDtypeStruct((m, 2), jnp.uint32)
    _assert_kernel_in_hlo(jax.vmap(s.apply),
                          jax.ShapeDtypeStruct((m, n_shard, dim), f32))
    for codec, shape in (("topk0.25", (m, k, k)), ("qint8", (m, k))):
        _assert_kernel_in_hlo(jax.vmap(make_codec(codec).roundtrip), keys,
                              jax.ShapeDtypeStruct(shape, f32))
    print("phase=sync tpu_custom_call=true ops=srht_apply,topk,qint8",
          flush=True)

    pallas_vs_ref("sync", lambda: run_rounds(
        make_optimizer("flens", k=k), prob, w0, w_star, rounds=rounds,
        seed=seed, comm=comm, obs=TelemetryConfig()))


def population_run(seed: int, *, m: int = 100_000, rounds: int = 8,
                   client_mesh=None):
    """The README's population setting; returns its ``History``."""
    pop = SyntheticPopulation(m=m, dim=16, seed=seed, dirichlet_alpha=0.3)
    comm = CommConfig(
        channel=ChannelModel(uplink_bytes_per_s="loguniform:3e4,3e6",
                             downlink_bytes_per_s="loguniform:3e5,3e7",
                             latency_s=0.08, dropout_prob=0.10),
        scheduler="uniform:1e-3", seed=seed)
    w0 = jnp.zeros((pop.dim,), jnp.float32)
    w_star = newton_solve(pop.eval_problem(), w0)
    return run_rounds(make_optimizer("flens", k=8), pop, w0, w_star,
                      rounds=rounds, seed=seed, comm=comm,
                      obs=TelemetryConfig(), client_mesh=client_mesh)


def phase_population(seed: int, **sizes) -> None:
    """(b) population FLeNS, Pallas against reference."""
    pallas_vs_ref("population", lambda: population_run(seed, **sizes))


def phase_async(seed: int, *, rounds: int = 12) -> None:
    """(c) async FLeNS on the full phishing twin over stragglers."""
    spec, X, y = load("phishing", dtype=jnp.float32, seed=seed)
    prob = make_problem(X, y, m=spec.m_clients, lam=LAM, objective=logistic,
                        key=root_key(seed, 1))
    w0 = jnp.zeros((spec.dim,), jnp.float32)
    w_star = newton_solve(prob, w0)
    comm = CommConfig(channel=straggler_edge_channel(spec.m_clients),
                      seed=seed, async_mode=True,
                      buffer_size=spec.m_clients // 4, staleness="inverse")
    pallas_vs_ref("async", lambda: run_rounds(
        make_optimizer("flens", k=spec.sketch_k), prob, w0, w_star,
        rounds=rounds, seed=seed, comm=comm, obs=TelemetryConfig()))


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------

def phase_cohort_mesh(seed: int, devices, *, m: int = 100_000,
                      **sizes) -> None:
    """Population FLeNS with the cohort's client axis over the mesh,
    against the same run on one device."""
    from repro.sharding.rules import shard_cohort

    # an Auto axis: GSPMD partitions the vmapped round along it
    mesh = jax.sharding.Mesh(np.asarray(devices), ("clients",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    pop = SyntheticPopulation(m=m, dim=16, seed=seed, dirichlet_alpha=0.3)
    cohort_size = CommConfig(scheduler="uniform:1e-3").scheduler.cohort_size(m)
    cohort = shard_cohort(mesh, pop.materialize(np.arange(cohort_size)))
    for leaf in jax.tree_util.tree_leaves(cohort):
        rows = {s.index[0] for s in leaf.addressable_shards}
        if len(rows) != len(devices):
            raise AssertionError(
                f"cohort leaf {leaf.shape} is split {len(rows)} ways, "
                f"not {len(devices)}")
    print(f"phase=cohort_mesh cohort_split={len(devices)}", flush=True)
    sharded = population_run(seed, m=m, client_mesh=mesh, **sizes)
    _report("cohort_mesh", f"mesh{len(devices)}", sharded)
    single = population_run(seed, m=m, **sizes)
    _report("cohort_mesh", "one_device", single)
    check_agree("cohort_mesh", sharded, single)


def phase_distributed(seed: int, devices, *, rounds: int = 3) -> None:
    """``DistributedFLeNS`` rounds on the mesh against the simulator's,
    both at full float32 matmul precision: the TPU's one-pass default
    would round the simulator's weighted sum over clients to bfloat16,
    which the psum does not."""
    with jax.default_matmul_precision("highest"):
        _distributed_rounds(seed, devices, rounds)


def _distributed_rounds(seed: int, devices, rounds: int) -> None:
    from repro.core.distributed import DistributedFLeNS
    from repro.core.flens import FLeNS

    m, dim, k = len(devices), 32, 16
    X, y = make_classification(root_key(seed), 400, dim, dtype=jnp.float32)
    prob = make_problem(X, y, m=m, lam=LAM, objective=logistic)
    w0 = jnp.zeros((dim,), jnp.float32)
    opt = FLeNS(k=k, beta=0.0, restart=False)
    state = opt.init(prob, w0)
    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",))
    dist = DistributedFLeNS(mesh=mesh, objective=logistic, dim=dim, k=k,
                            lam=LAM, beta=0.0, client_axes=("data",))
    step = dist.round_fn()
    Xd, yd = dist.shard_data(prob.X.reshape(-1, dim), prob.y.reshape(-1))
    w, w_prev = w0, w0
    for t in range(rounds):
        state = opt.round(prob, state, jax.random.PRNGKey(t))  # noqa: RA001 — the round seed is the wire contract DistributedFLeNS re-derives
        w, w_prev = step(Xd, yd, w, w_prev, t)
        err = float(jnp.max(jnp.abs(w - state["w"])))
        bound = W_RTOL * float(jnp.max(jnp.abs(state["w"])))
        if not err <= bound:
            raise AssertionError(
                f"distributed round {t}: |w - w_sim| = {err!r} > {bound!r}")
        print(f"phase=distributed round={t} max_abs_err={err!r} "
              f"bound={bound!r}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"compile_cache={enable_compile_cache()}", flush=True)

    if args.chips == 4:
        phase_cohort_mesh(args.seed, devices[:4])
        phase_distributed(args.seed, devices[:4])
    else:
        phase_sync(args.seed)
        phase_population(args.seed)
        phase_async(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
