"""Benchmark harness — one function per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows (one per measured quantity)
and writes detailed artifacts (trajectories, tables) to ``results/``.

  PYTHONPATH=src python -m benchmarks.run              # default (quick-ish)
  PYTHONPATH=src python -m benchmarks.run --full       # paper-scale rounds
  PYTHONPATH=src python -m benchmarks.run --only fig1
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

RESULTS = pathlib.Path("results")


def _csv(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------
# Fig. 1: optimality gap vs communication rounds, all methods
# ---------------------------------------------------------------------------

def bench_fig1_convergence(full: bool) -> None:
    from benchmarks.paper_common import build_problem, fig1_methods, run_method

    datasets = ["phishing"] + (["covtype", "susy"] if full else [])
    rounds = 30 if full else 20
    for ds in datasets:
        spec, prob, w0, w_star = build_problem(ds, n_cap=None if full else 20000)
        out = {"dataset": ds, "rounds": rounds, "methods": {}}
        for name, kw in fig1_methods(spec):
            hist = run_method(name, kw, prob, w0, w_star, rounds)
            out["methods"][hist.name] = {
                "gap": hist.gap.tolist(),
                "uplink_floats_per_round": hist.uplink_floats,
                "wall_s": hist.wall_time_s,
            }
            # derived: rounds to reach 1e-6 gap (paper's convergence metric)
            reach = np.argmax(hist.gap < 1e-6) if (hist.gap < 1e-6).any() else -1
            _csv(
                f"fig1/{ds}/{hist.name}",
                hist.wall_time_s / rounds * 1e6,
                f"gap_final={hist.gap[-1]:.3e};rounds_to_1e-6={reach}",
            )
        (RESULTS / "fig1").mkdir(parents=True, exist_ok=True)
        (RESULTS / "fig1" / f"{ds}.json").write_text(json.dumps(out, indent=1))


# ---------------------------------------------------------------------------
# Fig. 2: loss discrepancy vs sketch size
# ---------------------------------------------------------------------------

def bench_fig2_sketch_size(full: bool) -> None:
    from benchmarks.paper_common import build_problem, run_method

    spec, prob, w0, w_star = build_problem("phishing",
                                           n_cap=None if full else 20000)
    rounds = 25 if full else 15
    ks = [4, 8, 16, 32, 64, 128] if full else [8, 16, 32, 64]
    out = {"dataset": "phishing", "rounds": rounds, "gap_vs_k": {}}
    for k in ks:
        hist = run_method("flens", dict(k=k), prob, w0, w_star, rounds)
        out["gap_vs_k"][k] = float(hist.gap[-1])
        _csv(f"fig2/phishing/flens_k{k}", hist.wall_time_s / rounds * 1e6,
             f"gap_final={hist.gap[-1]:.3e}")
    # monotonicity check (paper: larger k -> closer to Newton)
    ks_sorted = sorted(out["gap_vs_k"])
    mono = all(out["gap_vs_k"][a] >= out["gap_vs_k"][b] * 0.5
               for a, b in zip(ks_sorted, ks_sorted[1:]))
    _csv("fig2/monotone_in_k", 0.0, f"monotone={mono}")
    (RESULTS / "fig2.json").write_text(json.dumps(out, indent=1))


# ---------------------------------------------------------------------------
# Fig. 3: computational time vs sketch size (FLeNS vs FedNS/FedNDES)
# ---------------------------------------------------------------------------

def bench_fig3_time_vs_sketch(full: bool) -> None:
    from benchmarks.paper_common import build_problem, run_method

    spec, prob, w0, w_star = build_problem("phishing",
                                           n_cap=None if full else 20000)
    rounds = 10 if full else 6
    ks = [8, 16, 32, 64] if not full else [8, 16, 32, 64, 128]
    out = {}
    for k in ks:
        row = {}
        for name in ("flens", "fedns"):
            hist = run_method(name, dict(k=k), prob, w0, w_star, rounds)
            per_round = hist.wall_time_s / rounds
            row[name] = per_round
            _csv(f"fig3/{name}_k{k}", per_round * 1e6,
                 f"gap_final={hist.gap[-1]:.3e}")
        out[k] = row
    (RESULTS / "fig3.json").write_text(json.dumps(out, indent=1))


# ---------------------------------------------------------------------------
# Table I: per-round communication (measured, floats per client)
# ---------------------------------------------------------------------------

def bench_table1_communication(full: bool) -> None:
    from benchmarks.paper_common import build_problem, fig1_methods
    from repro.core import make_optimizer

    spec, prob, w0, w_star = build_problem("phishing", n_cap=5000)
    m_dim, k = prob.dim, spec.sketch_k
    rows = []
    for name, kw in fig1_methods(spec):
        opt = make_optimizer(name, **kw)
        opt.init(prob, w0)  # fedndes resolves its adaptive k here
        up = opt.uplink_floats(prob)
        down = opt.downlink_floats(prob)
        rows.append((opt.name, up, down))
        _csv(f"table1/{opt.name}", 0.0, f"uplink_floats={up};downlink={down}")
    # the paper's headline claim: FLeNS uplink O(k^2) << FedNS O(kM)
    up = {r[0]: r[1] for r in rows}
    claim = up["flens"] < up["fedns"] and up["flens"] < up["fednewton"]
    _csv("table1/flens_cheapest_newton_type", 0.0, f"claim_holds={claim}")
    (RESULTS / "table1.json").write_text(json.dumps(
        {"M": m_dim, "k": k, "rows": rows}, indent=1))


# ---------------------------------------------------------------------------
# Comm: loss vs transmitted bytes / simulated time through repro.comm
# ---------------------------------------------------------------------------

def bench_comm(full: bool) -> None:
    """Loss-vs-bytes and loss-vs-simulated-time for FLeNS under the
    simulated transport: identity codec vs symmetric-pack + int8 on the
    sketched Hessian, vs a bf16-compressed model BROADCAST (the
    symmetric downlink direction — asserted to strictly lower both
    transport axes at a bounded loss gap), all under a 10%-dropout
    full-participation channel; plus error-feedback on/off curves for a
    top-k-crushed O(M) uplink (fedavg), whose ``ef_gap_shrink`` ratio
    records how much of the compression floor EF21 memory recovers at
    identical byte cost. Also asserts the backward-compat contract:
    identity codec + full participation reproduces the no-comm
    trajectory exactly.

    The sketch-policy axis (``SketchPolicy`` spec per variant) rides in
    every record; its headline is the ``flens_rot_ef`` pair: the same
    top-k-crushed sketch payloads under a fresh per-round basis (EF
    requested but ineligible — cross-round memory is meaningless there)
    vs a rotating ``srht:rotate=8`` basis (EF eligible by
    ``basis_persistent``), asserted strictly lower loss at exactly
    equal bytes — the cross-round sketch closing the sketch-payload
    compression floor the PR-2 ROADMAP item predicted."""
    from benchmarks.paper_common import (
        build_problem, ef_gap_shrink, ef_ratio_label, run_method)
    from repro.comm import ChannelModel, CommConfig, summarize
    from repro.core import make_optimizer, run_rounds

    spec, prob, w0, w_star = build_problem("phishing",
                                           n_cap=None if full else 20000)
    rounds = 25 if full else 12
    k = spec.sketch_k

    # contract check: identity/full-participation == legacy, bit for bit
    base = run_method("flens", dict(k=k), prob, w0, w_star, rounds)
    ident = run_rounds(make_optimizer("flens", k=k), prob, w0, w_star,
                       rounds=rounds, comm=CommConfig())
    exact = bool(np.array_equal(base.loss, ident.loss))
    _csv("comm/identity_reproduces_legacy", 0.0, f"exact={exact}")
    assert exact, "identity-codec comm path diverged from the legacy driver"

    # accounting cross-check: the formula-derived uplink byte curve
    # (History.cumulative_uplink — per-client floats × itemsize × m)
    # must equal the traced per-round uplink bytes on the identity/full
    # path, where every client delivers the raw wire format
    traced_up = sum(float(t.bytes_up.sum()) for t in ident.traces)
    formula_up = float(ident.cumulative_uplink[-1])
    _csv("comm/uplink_formula_matches_traced", 0.0,
         f"formula={formula_up:.0f};traced={traced_up:.0f};"
         f"match={bool(abs(formula_up - traced_up) < 0.5)}")
    assert abs(formula_up - traced_up) < 0.5, (
        f"cumulative_uplink formula ({formula_up}) disagrees with traced "
        f"bytes ({traced_up})")

    channel = ChannelModel(dropout_prob=0.10, straggler_prob=0.10)
    # the sketch-policy pair: identical top-k-crushed sketch payloads,
    # fresh vs rotating basis — only the rotating one can use EF
    sketch_topk = {"h_sk": "topk0.25", "sg": "topk0.5"}
    variants = [
        ("flens_identity", "flens", dict(k=k),
         CommConfig(channel=channel, seed=1)),
        ("flens_sympack_qint8", "flens", dict(k=k), CommConfig(
            codecs={"h_sk": "sympack+qint8", "sg": "qint8"},
            channel=channel, seed=1)),
        # the symmetric direction: compress the server's model broadcast
        # (identity uplink, so the saving is purely downlink)
        ("flens_down_bf16", "flens", dict(k=k), CommConfig(
            downlink_codecs="bf16", channel=channel, seed=1)),
        # the policy axis: EF is requested in BOTH runs; the fresh basis
        # is ineligible (basis_persistent -> False), the rotating basis
        # carries EF21 memory on h_sk/sg across its 8-round epochs
        ("flens_fresh_topk", "flens", dict(k=k, sketch="srht"), CommConfig(
            codecs=sketch_topk, error_feedback=True, channel=channel,
            seed=1)),
        ("flens_rot_ef", "flens", dict(k=k, sketch="srht:rotate=8"),
         CommConfig(codecs=sketch_topk, error_feedback=True,
                    channel=channel, seed=1)),
        # EF on/off under a biased codec that actually bites: fedavg's
        # O(M) model uplink at topk0.05 (5% of coordinates per round)
        ("fedavg_identity", "fedavg", dict(lr=2.0, local_steps=5),
         CommConfig(channel=channel, seed=1)),
        ("fedavg_topk_ef_off", "fedavg", dict(lr=2.0, local_steps=5),
         CommConfig(codecs="topk0.05", channel=channel, seed=1)),
        ("fedavg_topk_ef_on", "fedavg", dict(lr=2.0, local_steps=5),
         CommConfig(codecs="topk0.05", error_feedback=True,
                    channel=channel, seed=1)),
    ]
    out = {"dataset": spec.name, "rounds": rounds, "k": k, "variants": {}}
    finals = {}
    for name, opt_name, opt_kw, comm in variants:
        opt = make_optimizer(opt_name, **opt_kw)
        policy = getattr(opt, "policy", None)
        hist = run_rounds(opt, prob, w0, w_star, rounds=rounds, comm=comm)
        stats = summarize(hist.traces)
        finals[name] = float(hist.loss[-1])
        out["variants"][name] = {
            "policy": policy.spec() if policy is not None else None,
            "gap": hist.gap.tolist(),
            "loss_final": float(hist.loss[-1]),
            "cumulative_bytes": hist.cumulative_bytes.tolist(),
            "sim_time_s": hist.sim_time_s.tolist(),
            "stats": stats,
            "ef_residuals": hist.ef_residuals,
        }
        policy_label = f";policy={policy.spec()}" if policy is not None else ""
        _csv(
            f"comm/{name}",
            hist.wall_time_s / rounds * 1e6,
            f"gap_final={hist.gap[-1]:.3e};"
            f"total_MB={hist.cumulative_bytes[-1] / 1e6:.3f};"
            f"sim_s={hist.sim_time_s[-1]:.2f}" + policy_label,
        )
    ident_b = out["variants"]["flens_identity"]["cumulative_bytes"][-1]
    packed_b = out["variants"]["flens_sympack_qint8"]["cumulative_bytes"][-1]
    _csv("comm/bytes_saved_by_sympack_qint8", 0.0,
         f"ratio={ident_b / max(packed_b, 1):.2f}x")

    # downlink-compression acceptance: the bf16 broadcast must strictly
    # lower BOTH transport axes vs the identity broadcast at a bounded
    # final-loss gap (the guard absorbs the broadcast rounding noise)
    ident_v = out["variants"]["flens_identity"]
    down_v = out["variants"]["flens_down_bf16"]
    gap_id = float(ident_v["gap"][-1])
    gap_dn = float(down_v["gap"][-1])
    out["downlink"] = {
        "bytes_identity": ident_v["cumulative_bytes"][-1],
        "bytes_bf16": down_v["cumulative_bytes"][-1],
        "sim_identity": ident_v["sim_time_s"][-1],
        "sim_bf16": down_v["sim_time_s"][-1],
        "gap_identity": gap_id,
        "gap_bf16": gap_dn,
    }
    saves = (down_v["cumulative_bytes"][-1] < ident_v["cumulative_bytes"][-1]
             and down_v["sim_time_s"][-1] < ident_v["sim_time_s"][-1])
    _csv("comm/downlink_bf16_saves", 0.0,
         f"bytes_ratio={ident_v['cumulative_bytes'][-1] / max(down_v['cumulative_bytes'][-1], 1):.2f}x;"
         f"sim_ratio={ident_v['sim_time_s'][-1] / max(down_v['sim_time_s'][-1], 1e-9):.2f}x;"
         f"gap_identity={gap_id:.3e};gap_bf16={gap_dn:.3e};"
         f"strictly_lower={bool(saves)}")
    assert saves, (
        "bf16 downlink did not strictly lower both cumulative_bytes and "
        f"sim_time_s: {out['downlink']}")
    assert np.isfinite(gap_dn) and gap_dn < max(10.0 * gap_id, 1e-2), (
        f"bf16 broadcast loss gap unbounded: {gap_dn} vs identity {gap_id}")

    # sketch-policy acceptance: rotating-SRHT + EF21 must strictly beat
    # the fresh basis at EXACTLY equal bytes — EF never changes encoded
    # sizes, and both runs crush h_sk/sg with the same top-k codecs, so
    # the whole loss difference is the cross-round basis unlocking EF
    fresh_v = out["variants"]["flens_fresh_topk"]
    rot_v = out["variants"]["flens_rot_ef"]
    bytes_equal = fresh_v["cumulative_bytes"] == rot_v["cumulative_bytes"]
    gap_fresh, gap_rot = float(fresh_v["gap"][-1]), float(rot_v["gap"][-1])
    out["rot_ef"] = {
        "policy_fresh": fresh_v["policy"],
        "policy_rot": rot_v["policy"],
        "gap_fresh": gap_fresh,
        "gap_rot": gap_rot,
        "bytes": rot_v["cumulative_bytes"][-1],
        "bytes_equal": bool(bytes_equal),
        "ef_residuals_rot": rot_v["ef_residuals"],
    }
    _csv("comm/flens_rot_ef_closes_sketch_floor", 0.0,
         f"gap_fresh={gap_fresh:.3e};gap_rot={gap_rot:.3e};"
         f"ratio={gap_fresh / max(gap_rot, 1e-30):.2f}x;"
         f"equal_bytes={bool(bytes_equal)};"
         f"strictly_lower={bool(gap_rot < gap_fresh)}")
    assert bytes_equal, (
        "rotating-basis run must cost exactly the bytes of the fresh-basis "
        "run (EF and the schedule change values, never sizes)")
    assert finals["flens_rot_ef"] < finals["flens_fresh_topk"], (
        f"rotating-SRHT + EF did not beat the fresh basis at equal bytes: "
        f"{finals['flens_rot_ef']} vs {finals['flens_fresh_topk']}")
    # EF's headline number: how much of the loss gap to the
    # no-compression baseline the memory recovers (same encoded bytes)
    shrink = ef_gap_shrink(finals["fedavg_identity"],
                           finals["fedavg_topk_ef_off"],
                           finals["fedavg_topk_ef_on"])
    out["ef_gap_shrink"] = shrink
    off_b = out["variants"]["fedavg_topk_ef_off"]["cumulative_bytes"][-1]
    on_b = out["variants"]["fedavg_topk_ef_on"]["cumulative_bytes"][-1]
    _csv("comm/ef_gap_shrink", 0.0,
         f"ratio={ef_ratio_label(shrink)}x;ef_off_gap={shrink['ef_off']:.3e};"
         f"ef_on_gap={shrink['ef_on']:.3e};"
         f"same_bytes={bool(off_b == on_b)}")

    # population scale: the same seeded gate at m=100 000 with lazy
    # cohort materialization (uniform:1e-3 -> ~100 clients per round;
    # the dense (m, n_shard, M) tensor never exists). Byte accounting
    # stays exact under the gate: cohorts, channel draws, and codec
    # keys are pure functions of CommConfig.seed, and the trace stores
    # cohort-length arrays so the record stays small at this m.
    from repro.core import SyntheticPopulation, newton_solve

    pop_m, pop_q = 100_000, 1e-3
    pop = SyntheticPopulation(m=pop_m, dim=16, seed=1, dirichlet_alpha=0.3)
    w0_pop = np.zeros(pop.dim)
    w_star_pop = newton_solve(pop.eval_problem(), w0_pop)
    pop_comm = CommConfig(
        codecs={"h_sk": "sympack+qint8", "sg": "qint8"},
        channel=ChannelModel(
            uplink_bytes_per_s="loguniform:3e4,3e6",
            downlink_bytes_per_s="loguniform:3e5,3e7",
            latency_s=0.08, straggler_prob=0.20, straggler_slowdown=10.0,
            dropout_prob=0.10),
        scheduler=f"uniform:{pop_q}", seed=1)
    hist = run_rounds(make_optimizer("flens", k=8), pop, w0_pop,
                      w_star_pop, rounds=rounds, comm=pop_comm)
    cohort = max(len(t.ids) for t in hist.traces)
    assert cohort < 4 * pop_q * pop_m, (
        f"population cohorts should stay near q*m={pop_q * pop_m:.0f}, "
        f"got {cohort} — lazy materialization is not bounding the round")
    out["variants"]["flens_population_100k"] = {
        "policy": None,
        "gap": hist.gap.tolist(),
        "loss_final": float(hist.loss[-1]),
        "cumulative_bytes": hist.cumulative_bytes.tolist(),
        "sim_time_s": hist.sim_time_s.tolist(),
        "stats": summarize(hist.traces),
        "ef_residuals": hist.ef_residuals,
        "population": pop_m,
        "q": pop_q,
        "cohort": cohort,
    }
    _csv("comm/flens_population_100k", hist.wall_time_s / rounds * 1e6,
         f"gap_final={hist.gap[-1]:.3e};"
         f"total_MB={hist.cumulative_bytes[-1] / 1e6:.3f};"
         f"sim_s={hist.sim_time_s[-1]:.2f};"
         f"population={pop_m};cohort={cohort}")

    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "comm.json").write_text(json.dumps(out, indent=1))


# ---------------------------------------------------------------------------
# Robust: Byzantine sign-flip coalition, with/without trimmed-mean defense
# ---------------------------------------------------------------------------

def bench_robust(full: bool) -> None:
    """FLeNS vs FedAvg under a seeded 10% sign-flip coalition
    (``DynamicsConfig(threat="signflip:0.1")``), with and without the
    ``trimmed:0.1`` coordinate-wise trimmed mean, on the heterogeneous
    edge channel. Loss-vs-bytes is the axis that matters: the attack
    and the defense never change wire formats, so every variant of one
    optimizer transmits EXACTLY the same bytes and the entire loss
    difference is the coalition vs the aggregator.

    Gates (seeded, deterministic):

      * **fedavg** — the coalition flips the O(M) model uplink, a real
        attack; the trimmed mean must recover at least 2x of the
        final-loss gap it opens (``gap(attacked) >= 2 * gap(defended)``).
      * **flens** — sign-flipping BOTH sketch payloads (``h_sk`` and
        ``sg``) rescales the Hessian estimate and the gradient estimate
        by the same factor, and the Newton step ``H^-1 g``
        self-normalizes: the attack must open a SMALLER gap than it
        does on fedavg, and the defended run must stay within a small
        absolute band of clean (the trimmed mean's own bias bound).

    The records merge into ``results/comm.json`` so
    ``benchmarks/compare.py`` (and ``--update``) gates their bytes
    exactly and losses at rtol alongside the other comm variants.
    """
    from benchmarks.paper_common import build_problem, straggler_edge_channel
    from repro.comm import CommConfig, summarize
    from repro.core import make_optimizer, run_rounds
    from repro.dynamics import DynamicsConfig

    spec, prob, w0, w_star = build_problem("phishing",
                                           n_cap=None if full else 20000)
    rounds = 20 if full else 10
    k = spec.sketch_k
    channel = straggler_edge_channel(prob.m)
    threat, robust = "signflip:0.1", "trimmed:0.1"

    def comm(threat_spec=None, robust_spec=None):
        dyn = None
        if threat_spec or robust_spec:
            dyn = DynamicsConfig(threat=threat_spec, robust=robust_spec,
                                 seed=1)
        return CommConfig(channel=channel, seed=1, dynamics=dyn)

    lineup = [("flens", dict(k=k)),
              ("fedavg", dict(lr=2.0, local_steps=5))]
    arms = [("clean", None, None),
            ("attacked", threat, None),
            ("trimmed", threat, robust)]
    out = {"dataset": spec.name, "rounds": rounds, "m": prob.m, "k": k,
           "threat": threat, "robust": robust, "variants": {}}
    finals: dict = {}
    for opt_name, opt_kw in lineup:
        bytes_by_arm = {}
        for arm, t_spec, r_spec in arms:
            name = f"robust_{opt_name}_{arm}"
            hist = run_rounds(make_optimizer(opt_name, **opt_kw), prob, w0,
                              w_star, rounds=rounds,
                              comm=comm(t_spec, r_spec))
            finals[name] = float(hist.loss[-1])
            bytes_by_arm[arm] = hist.cumulative_bytes.tolist()
            out["variants"][name] = {
                "loss": hist.loss.tolist(),
                "loss_final": float(hist.loss[-1]),
                "cumulative_bytes": bytes_by_arm[arm],
                "stats": summarize(hist.traces),
            }
            _csv(f"robust/{name}", hist.wall_time_s / rounds * 1e6,
                 f"loss_final={hist.loss[-1]:.6f};"
                 f"total_MB={hist.cumulative_bytes[-1] / 1e6:.3f}")
        assert (bytes_by_arm["clean"] == bytes_by_arm["attacked"]
                == bytes_by_arm["trimmed"]), (
            f"{opt_name}: threat/robust changed the byte accounting — "
            "corruption and aggregation must never touch wire formats")
        gap_att = finals[f"robust_{opt_name}_attacked"] - finals[
            f"robust_{opt_name}_clean"]
        gap_def = finals[f"robust_{opt_name}_trimmed"] - finals[
            f"robust_{opt_name}_clean"]
        recovery = gap_att / max(gap_def, 1e-30)
        out.setdefault("robust_gate", {})[opt_name] = {
            "gap_attacked": gap_att,
            "gap_defended": gap_def,
            "recovery": recovery,
        }
        _csv(f"robust/{opt_name}_gate", 0.0,
             f"gap_attacked={gap_att:.3e};gap_defended={gap_def:.3e};"
             f"recovery={recovery:.1f}x")
        assert gap_att > 0, (
            f"{opt_name}: the sign-flip coalition did not hurt — the "
            "threat is not reaching the uplink")

    gates = out["robust_gate"]
    rec = gates["fedavg"]["recovery"]
    assert rec >= 2.0, (
        f"fedavg: trimmed mean recovered only {rec:.2f}x of the attack's "
        f"loss gap ({gates['fedavg']}); gate needs >= 2x")
    # the comparison headline: the Newton step self-normalizes, so the
    # same coalition hurts flens strictly less than fedavg — and the
    # trimmed mean's own bias stays within a small absolute band
    assert gates["flens"]["gap_attacked"] < gates["fedavg"]["gap_attacked"], (
        f"flens should be naturally MORE robust to proportional "
        f"sign-flips than fedavg: {gates}")
    assert abs(gates["flens"]["gap_defended"]) < 1e-2, (
        f"flens trimmed-mean bias left the clean band: {gates['flens']}")
    _csv("robust/gate", 0.0,
         f"fedavg_recovery={rec:.1f}x;"
         f"flens_self_normalizes="
         f"{bool(gates['flens']['gap_attacked'] < gates['fedavg']['gap_attacked'])}")

    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "robust.json").write_text(json.dumps(out, indent=1))
    # ride the comm regression gate: merge the seeded records into
    # results/comm.json so compare.py (and --update) pins their bytes
    # exactly and losses at rtol like every other comm variant
    comm_path = RESULTS / "comm.json"
    if comm_path.exists():
        doc = json.loads(comm_path.read_text())
        doc["variants"].update(out["variants"])
        doc["robust_gate"] = out["robust_gate"]
        comm_path.write_text(json.dumps(doc, indent=1))
        _csv("robust/merged_into_comm_record", 0.0,
             f"variants={len(out['variants'])}")


# ---------------------------------------------------------------------------
# Async: loss vs simulated time, synchronous vs event-driven driver
# ---------------------------------------------------------------------------

def bench_async(full: bool) -> None:
    """Sync vs async round driver under stragglers on heterogeneous edge
    links: the synchronous server waits for the slowest delivering
    client every round, the async server commits once a FedBuff-style
    buffer of K uploads has arrived, weighting stale contributions by
    1/(1+tau). Records loss at the latest common simulated-time point
    (``async_beats_sync``: the headline loss-vs-sim-time comparison) and
    asserts the lock-step anchor: async with a full quorum reproduces
    the synchronous trajectory bit-identically."""
    from benchmarks.paper_common import (
        build_problem,
        check_async_lockstep_anchor,
        hist_record,
        loss_at,
        straggler_edge_channel,
        sync_async_race,
    )
    from repro.core import make_optimizer

    spec, prob, w0, w_star = build_problem("phishing",
                                           n_cap=None if full else 20000)
    rounds = 20 if full else 10
    m = prob.m
    channel = straggler_edge_channel(m)

    def fedavg():
        return make_optimizer("fedavg", lr=2.0, local_steps=5)

    # lock-step anchor: full-quorum async == sync, bit for bit
    exact, _, _ = check_async_lockstep_anchor(fedavg, prob, w0, w_star,
                                              channel, rounds=4)
    _csv("async/full_quorum_reproduces_sync", 0.0, f"exact={exact}")
    assert exact, "full-quorum async diverged from the synchronous driver"

    out = {"dataset": spec.name, "rounds": rounds, "m": m,
           "straggler_prob": channel.straggler_prob, "variants": {}}
    hists = sync_async_race(fedavg, prob, w0, w_star, channel, rounds=rounds)
    for name, hist in hists.items():
        out["variants"][name] = hist_record(hist)
        r = hist.rounds
        _csv(f"async/{name}", hist.wall_time_s / r * 1e6,
             f"gap_final={hist.gap[-1]:.3e};"
             f"sim_s={hist.sim_time_s[-1]:.2f};rounds={r}")

    sync_h = hists["sync"]
    failures = []
    for name in ("async_buf", "async_q50"):
        av = out["variants"][name]
        t_common = min(sync_h.sim_time_s[-1], hists[name].sim_time_s[-1])
        loss_sync = loss_at(sync_h, t_common)
        loss_async = loss_at(hists[name], t_common)
        beats = bool(loss_async < loss_sync)
        av["loss_at_common_sim_time"] = {
            "t": t_common, "sync": loss_sync, "async": loss_async}
        _csv(f"async/{name}_beats_sync_at_t", 0.0,
             f"t={t_common:.1f}s;sync={loss_sync:.6f};"
             f"async={loss_async:.6f};beats={beats}")
        if not beats:
            failures.append(
                f"{name}: async ({loss_async}) did not beat sync "
                f"({loss_sync}) on loss-vs-sim-time at t={t_common}")
    # persist the curves BEFORE asserting: a failed dominance check is
    # exactly when the per-variant diagnostics are needed
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "async.json").write_text(json.dumps(out, indent=1))
    assert not failures, "; ".join(failures)


# ---------------------------------------------------------------------------
# Perf trajectory: per-optimizer compile/exec wall-clock + bytes + loss
# ---------------------------------------------------------------------------

# the representative per-family lineup the perf trajectory tracks (one
# first-order, one exact-Newton, and the three sketched-Newton variants
# the paper headlines); kwargs as in fig1_methods
_ROUND_TIME_OPTS = [
    ("fedavg", lambda k: dict(lr=2.0, local_steps=5)),
    ("fednewton", lambda k: {}),
    ("fedns", lambda k: dict(k=k)),
    ("flens", lambda k: dict(k=k)),
    ("flens_plus", lambda k: dict(k=k)),
]

# committed at the repo root: the tracked perf-trajectory artifact
# (schema-checked by `python -m repro.obs.report --check-schema`, gated
# by `python benchmarks/compare.py --bench`)
BENCH_PATH = pathlib.Path(__file__).resolve().parent.parent / (
    "BENCH_round_time.json")


def bench_round_time(full: bool) -> None:
    """The tracked wall-clock axis: run the representative optimizer
    lineup through the instrumented round driver and emit
    ``BENCH_round_time.json`` (repo root) with, per optimizer, the
    compile-vs-execute wall-clock split (from ``repro.obs`` telemetry —
    first jitted-round call billed as compile), the exact transported
    bytes, and the loss reached at a common byte budget (the smallest
    total any optimizer transmitted, so every method is compared at
    bytes it actually reached). Bytes and losses are pure functions of
    ``CommConfig.seed`` — deterministic, gated against the committed
    baseline by ``benchmarks/compare.py --bench``; wall-clock fields are
    machine-dependent and gated only by a generous slowdown factor.

    The full per-round telemetry stream (phase timings, per-round
    records) lands in ``results/telemetry_round_time.jsonl`` — one
    artifact, one run per optimizer label, rendered by
    ``python -m repro.obs.report``. When roofline dry-run artifacts are
    present (``results/dryrun*``), their per-arch dominant-term summary
    is attached under ``"roofline"`` so the accelerator-model axis rides
    the same tracked file.
    """
    from benchmarks.paper_common import build_problem
    from benchmarks.roofline import aggregate
    from repro.comm import CommConfig
    from repro.core import make_optimizer, run_rounds
    from repro.obs import TelemetryConfig
    from repro.obs.report import BENCH_SCHEMA

    spec, prob, w0, w_star = build_problem("phishing",
                                           n_cap=None if full else 20000)
    rounds = 20 if full else 12
    k = spec.sketch_k
    telemetry_path = RESULTS / "telemetry_round_time.jsonl"
    telemetry_path.unlink(missing_ok=True)  # the jsonl sink appends

    opts: dict = {}
    hists: dict = {}
    for name, kw_fn in _ROUND_TIME_OPTS:
        hist = run_rounds(
            make_optimizer(name, **kw_fn(k)), prob, w0, w_star,
            rounds=rounds, comm=CommConfig(seed=1),
            obs=TelemetryConfig(sink=f"jsonl:{telemetry_path}", label=name))
        tel = hist.telemetry
        hists[name] = hist
        opts[name] = {
            # wall-clock (machine-dependent; gated by ratio only)
            "compile_s": tel["compile_s"],
            "exec_s": tel["exec_s"],
            "exec_s_per_round": tel["exec_s_per_round"],
            "wall_time_s": hist.wall_time_s,
            # deterministic (gated exactly / at loss rtol)
            "bytes_total": float(hist.cumulative_bytes[-1]),
            "uplink_floats": int(hist.uplink_floats),
            "loss_final": float(hist.loss[-1]),
        }
        _csv(f"round_time/{name}", tel["exec_s_per_round"] * 1e6,
             f"compile_s={tel['compile_s']:.3f};"
             f"bytes_total={hist.cumulative_bytes[-1]:.0f};"
             f"loss_final={hist.loss[-1]:.6f}")

    # loss at the common byte budget: the smallest total transmitted —
    # every optimizer's curve is interpolated at bytes it reached
    budget = min(row["bytes_total"] for row in opts.values())
    for name, hist in hists.items():
        opts[name]["loss_at_budget"] = float(
            np.interp(budget, hist.cumulative_bytes, hist.loss))

    doc = {
        "schema": BENCH_SCHEMA,
        "dataset": spec.name,
        "rounds": rounds,
        "clients": prob.m,
        "budget_bytes": budget,
        "optimizers": opts,
    }
    dryrun = RESULTS / ("dryrun_opt" if (RESULTS / "dryrun_opt").exists()
                        else "dryrun")
    if dryrun.exists():
        doc["roofline"] = [
            {"arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
             "status": r["status"],
             **({"dominant": r["roofline"]["dominant"],
                 "compute_s": r["roofline"]["compute_s"],
                 "memory_s": r["roofline"]["memory_s"],
                 "collective_s": r["roofline"]["collective_s"]}
                if r["status"] == "ok" else {})}
            for r in aggregate(dryrun)
        ]
    BENCH_PATH.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
    _csv("round_time/artifact", 0.0,
         f"budget_MB={budget / 1e6:.3f};wrote={BENCH_PATH.name}")


# ---------------------------------------------------------------------------
# Kernel micro-benchmarks (CPU timings of the portable paths)
# ---------------------------------------------------------------------------

def bench_kernels(full: bool) -> None:
    from repro.kernels import ops as kops
    from repro.kernels import ref

    # Fused SRHT dispatch (sign-flip -> FWHT -> row-subsample) on the
    # active backend: `kernels/srht_*` rows track the sketch hot loop
    # end-to-end through repro.kernels.ops — the exact code path
    # Sketch.apply runs inside every sketched optimizer. On CPU the
    # resolver picks the reference path; on TPU the same rows time the
    # fused Pallas kernel, so speedups land in this CSV unchanged.
    impl = kops.resolve_impl()
    for n in (1024, 4096):
        k = n // 16
        key = jax.random.PRNGKey(0)
        signs = jax.random.rademacher(key, (n,), jnp.float32)
        rows_idx = jax.random.choice(jax.random.PRNGKey(1), n, (k,),
                                     replace=False)
        x = jax.random.normal(jax.random.PRNGKey(2), (64, n), jnp.float32)
        y = jax.random.normal(jax.random.PRNGKey(3), (64, k), jnp.float32)
        fwd = jax.jit(lambda x: kops.srht_apply(x, signs, rows_idx))
        bwd = jax.jit(lambda y: kops.srht_apply_t(y, signs, rows_idx, n))
        for tag, fn, arg in (("fwd", fwd, x), ("t", bwd, y)):
            fn(arg).block_until_ready()
            t0 = time.perf_counter()
            iters = 20
            for _ in range(iters):
                fn(arg).block_until_ready()
            dt = (time.perf_counter() - t0) / iters
            _csv(f"kernels/srht_{tag}_{impl}_n{n}", dt * 1e6,
                 f"k={k};rows=64")

    # Fused codec inner loops (the transport hot path) through the same
    # dispatch: top-k select+pack and qint8 quantize->dequantize
    size = 4096 * 16
    x = jax.random.normal(jax.random.PRNGKey(4), (size,), jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(5), (size,), jnp.float32)
    kept = size // 20
    codec_fns = (
        (f"topk_{impl}", jax.jit(lambda x: kops.topk_mask(x, kept)), (x,)),
        (f"qint8_{impl}", jax.jit(kops.qint8_roundtrip), (x, u)),
    )
    for tag, fn, args_ in codec_fns:
        fn(*args_).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            fn(*args_).block_until_ready()
        dt = (time.perf_counter() - t0) / 20
        _csv(f"kernels/{tag}_n{size}", dt * 1e6, f"kept={kept}")

    # FWHT: the SRHT hot loop
    for n in (1024, 4096):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, n), jnp.float32)
        f = jax.jit(lambda x: ref.fwht(x))
        f(x).block_until_ready()
        t0 = time.perf_counter()
        iters = 20
        for _ in range(iters):
            f(x).block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        gbps = x.size * 4 * np.log2(n) / dt / 1e9
        _csv(f"kernels/fwht_ref_n{n}", dt * 1e6, f"effective_GB/s={gbps:.2f}")

    # blocked attention vs naive (the flash structure's win is memory; on
    # CPU we report time parity + the memory ratio it avoids)
    b, t, h, d = 1, 1024, 4, 64
    q = jax.random.normal(jax.random.PRNGKey(1), (b, t, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (b, t, h, d), jnp.float32)
    for name, fn in (
        ("naive", jax.jit(lambda q, k, v: ref.mha(q, k, v))),
        ("blocked", jax.jit(lambda q, k, v: ref.mha_blocked(q, k, v))),
    ):
        fn(q, k, v).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            fn(q, k, v).block_until_ready()
        dt = (time.perf_counter() - t0) / 5
        _csv(f"kernels/attention_{name}_t{t}", dt * 1e6,
             f"logits_bytes_naive={b*h*t*t*4}")


# ---------------------------------------------------------------------------
# Roofline aggregation (from the dry-run artifacts)
# ---------------------------------------------------------------------------

def bench_sketch_types(full: bool) -> None:
    """Paper §VI: SRHT vs sub-Gaussian vs SJLT sketches inside FLeNS."""
    from benchmarks.paper_common import build_problem, run_method

    spec, prob, w0, w_star = build_problem("phishing", n_cap=20000)
    rounds = 12
    for kind in ("srht", "gaussian", "sjlt"):
        hist = run_method("flens", dict(k=spec.sketch_k, sketch=kind),
                          prob, w0, w_star, rounds)
        _csv(f"sketch_types/flens_{kind}", hist.wall_time_s / rounds * 1e6,
             f"gap_final={hist.gap[-1]:.3e}")


def bench_flens_ablation(full: bool) -> None:
    """Ablate the FLeNS design choices (momentum rule, guard, step point)."""
    from benchmarks.paper_common import build_problem, run_method

    spec, prob, w0, w_star = build_problem("phishing", n_cap=20000)
    rounds = 15
    k = spec.sketch_k
    variants = [
        ("beta0", dict(k=k, beta=0.0)),
        ("betaA7_guarded", dict(k=k, beta="paper", restart=True)),
        ("betaA7_unguarded", dict(k=k, beta="paper", restart=False)),
        ("beta_sqrt", dict(k=k, beta="sqrt")),
        ("step_from_w", dict(k=k, beta="paper", step_from="w")),
        ("gauss_sketch", dict(k=k, beta=0.0, sketch="gaussian")),
    ]
    for name, kw in variants:
        hist = run_method("flens", kw, prob, w0, w_star, rounds)
        gap = hist.gap[-1]
        import numpy as _np

        stable = bool(_np.isfinite(hist.gap).all() and gap < hist.gap[0])
        _csv(f"ablation/flens_{name}", hist.wall_time_s / rounds * 1e6,
             f"gap_final={gap:.3e};stable={stable}")


def bench_roofline(full: bool) -> None:
    from benchmarks.roofline import aggregate

    # prefer the post-§Perf artifacts when present (baseline kept alongside)
    src = RESULTS / ("dryrun_opt" if (RESULTS / "dryrun_opt").exists()
                     else "dryrun")
    table = aggregate(src)
    for row in table:
        if row["status"] != "ok":
            _csv(f"roofline/{row['arch']}/{row['shape']}/{row['mesh']}", 0.0,
                 f"status={row['status']}")
            continue
        r = row["roofline"]
        _csv(
            f"roofline/{row['arch']}/{row['shape']}/{row['mesh']}",
            max(r["compute_s"], r["memory_s"], r["collective_s"]) * 1e6,
            f"dominant={r['dominant']};compute_s={r['compute_s']:.3e};"
            f"memory_s={r['memory_s']:.3e};collective_s={r['collective_s']:.3e}",
        )


BENCHES = {
    "fig1": bench_fig1_convergence,
    "fig2": bench_fig2_sketch_size,
    "fig3": bench_fig3_time_vs_sketch,
    "table1": bench_table1_communication,
    "comm": bench_comm,
    "robust": bench_robust,
    "async": bench_async,
    "round_time": bench_round_time,
    "sketch_types": bench_sketch_types,
    "ablation": bench_flens_ablation,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args()
    from repro.cache import enable_compile_cache

    enable_compile_cache()
    RESULTS.mkdir(exist_ok=True)
    names = args.only.split(",") if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        BENCHES[name](args.full)


if __name__ == "__main__":
    main()
