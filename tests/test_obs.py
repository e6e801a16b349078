"""Observability layer tests (`repro.obs`).

Covers the PR's contract:
  * zero interference — running any driver (no-comm, sync, async) with
    telemetry enabled (null sink AND jsonl sink) reproduces the
    uninstrumented trajectory bit-identically: the instrumentation
    wraps jit boundaries from the host and can never perturb the
    optimization;
  * the run summary — compile-vs-exec wall-clock split, phase
    attribution, session metrics (bytes, deliveries, staleness
    distribution, async queue depths), flight-recorder stats;
  * primitives — metrics registry kind safety, flight-recorder ring
    truncation semantics, sink specs, `mean_staleness` edge cases;
  * artifacts — `History.to_jsonl`/`from_jsonl` round-trip (traces,
    staleness, non-finite values), `repro.obs.report` rendering and
    schema checking, the `benchmarks/compare.py` drift table and
    `--bench` gate;
  * diagnostics — driver warnings stay API-visible through the
    structured logger.
"""

import json
import logging
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import ChannelModel, CommConfig
from repro.comm.metrics import RoundTrace
from repro.core import make_optimizer, make_problem, newton_solve, run_rounds
from repro.core.base import History
from repro.core.losses import logistic
from repro.data import make_classification
from repro.obs import (
    FlightRecorder,
    MetricsRegistry,
    TelemetryConfig,
    make_sink,
)
from repro.obs import log as obs_log

pytestmark = pytest.mark.telemetry


@pytest.fixture(scope="module")
def small_problem():
    X, y = make_classification(jax.random.PRNGKey(3), 400, 16)
    prob = make_problem(X, y, m=6, lam=1e-3, objective=logistic)
    w0 = jnp.zeros(prob.dim, jnp.float64)
    w_star = newton_solve(prob, w0, iters=30)
    return prob, w0, w_star


def _flens():
    return make_optimizer("flens", k=6)


# ---------------------------------------------------------------------------
# zero interference: instrumented == uninstrumented, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "comm_fn",
    [
        pytest.param(lambda: None, id="no-comm"),
        pytest.param(lambda: CommConfig(seed=1), id="sync"),
        pytest.param(
            lambda: CommConfig(
                seed=1,
                async_mode=True,
                buffer_size=3,
                channel=ChannelModel(straggler_prob=0.3,
                                     straggler_slowdown=4.0),
            ),
            id="async",
        ),
    ],
)
def test_null_sink_bit_identical(small_problem, comm_fn, tmp_path):
    """Telemetry (null sink and jsonl sink alike, and with its spans
    annotating a running profiler) must not perturb the trajectory on
    any driver: same losses, same grads, same bytes."""
    prob, w0, w_star = small_problem
    bare = run_rounds(_flens(), prob, w0, w_star, rounds=4, comm=comm_fn())
    null = run_rounds(_flens(), prob, w0, w_star, rounds=4, comm=comm_fn(),
                      obs=TelemetryConfig())
    jsonl = run_rounds(
        _flens(), prob, w0, w_star, rounds=4, comm=comm_fn(),
        obs=TelemetryConfig(sink=f"jsonl:{tmp_path / 'tel.jsonl'}"))
    with jax.profiler.trace(str(tmp_path / "trace")):
        profiled = run_rounds(_flens(), prob, w0, w_star, rounds=4,
                              comm=comm_fn(), obs=TelemetryConfig())
    for instrumented in (null, jsonl, profiled):
        assert np.array_equal(bare.loss, instrumented.loss)
        assert np.array_equal(bare.grad_norm, instrumented.grad_norm)
        assert np.array_equal(bare.cumulative_bytes,
                              instrumented.cumulative_bytes)
        assert np.array_equal(bare.sim_time_s, instrumented.sim_time_s)
    # default is uninstrumented: no summary on the history
    assert bare.telemetry is None
    assert null.telemetry is not None


def test_profiler_hook_raises_when_trace_cannot_start(small_problem,
                                                      tmp_path):
    """A trace that was asked for and cannot start is an error, never a
    run that quietly has no device trace."""
    prob, w0, w_star = small_problem
    jax.profiler.start_trace(str(tmp_path / "held"))
    try:
        with pytest.raises(RuntimeError, match="already been started"):
            run_rounds(_flens(), prob, w0, w_star, rounds=2,
                       obs=TelemetryConfig(profile_rounds=1,
                                           profile_dir=str(tmp_path / "p")))
    finally:
        jax.profiler.stop_trace()


def test_profile_window_skips_the_compile_round(small_problem, tmp_path):
    """``profile_rounds`` traces N rounds from the first that does not
    compile: the trace's ``round`` annotations are rounds 1 and 2."""
    prob, w0, w_star = small_problem
    run_rounds(_flens(), prob, w0, w_star, rounds=4, comm=CommConfig(seed=1),
               obs=TelemetryConfig(profile_rounds=2,
                                   profile_dir=str(tmp_path / "p")))
    rounds = {s["round"] for s in _host_spans(tmp_path / "p")
              if s["name"] == "round"}
    assert rounds == {1, 2}


# ---------------------------------------------------------------------------
# session spans, round calls, and the spans on the profiler's clock
# ---------------------------------------------------------------------------

SESSION_PHASES = {
    "sync": {"session.schedule", "launch", "session.stats",
             "session.account"},
    "async": {"session.pump", "session.schedule", "launch", "session.stats",
              "session.aggregate", "session.account", "session.gc",
              "session.dispatch"},
}


def _async_comm():
    return CommConfig(seed=1, async_mode=True, buffer_size=3,
                      channel=ChannelModel(straggler_prob=0.5,
                                           straggler_slowdown=6.0),
                      staleness="inverse")


def _round_records(path):
    with open(path) as f:
        return [r for r in map(json.loads, f) if r["type"] == "round"]


@pytest.mark.parametrize("driver", ["sync", "async"])
def test_round_records_carry_session_phases_and_round_calls(
        small_problem, driver, tmp_path):
    """Every round record splits its ``step`` into the session's phases,
    ``launch`` and ``wait``, and counts its jitted round launches: one a
    sync round, one per base model version in an async commit."""
    prob, w0, w_star = small_problem
    comm = CommConfig(seed=1) if driver == "sync" else _async_comm()
    path = tmp_path / "tel.jsonl"
    hist = run_rounds(_flens(), prob, w0, w_star, rounds=8, comm=comm,
                      obs=TelemetryConfig(sink=f"jsonl:{path}"))
    records = _round_records(path)
    assert len(records) == len(hist.traces) == 8
    for rec, tr in zip(records, hist.traces):
        assert SESSION_PHASES[driver] | {"step", "wait", "eval"} <= set(
            rec["phases"])
        if driver == "sync":
            assert rec["round_calls"] == 1
        else:
            bases = {tr.version - 1 - s for s in tr.staleness[tr.delivered]}
            assert rec["round_calls"] == len(bases)
    calls = [r["round_calls"] for r in records]
    assert hist.telemetry["metrics"]["counters"]["round_calls"] == sum(calls)
    if driver == "async":
        assert max(calls) > 1  # stale groups re-run the round


def _host_spans(trace_dir):
    """The ``repro.obs`` spans on the host plane of the newest trace in
    ``trace_dir``: name, thread, start, end (ns), round."""
    from jax.profiler import ProfileData

    path = max(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    names = {"round", "prepare", "begin_variant", "probe_plan", "step",
             "launch", "wait", "eval", "finalize"}
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names or e.name.startswith("session."):
                    spans.append({"name": e.name, "line": line.name,
                                  "start": e.start_ns,
                                  "end": e.start_ns + e.duration_ns,
                                  "round": dict(e.stats).get("round")})
    return spans


@pytest.mark.parametrize("driver", ["sync", "async"])
def test_spans_reach_the_profiler_and_nest(small_problem, driver, tmp_path):
    """Under a running profiler every span lands on its host plane with
    its round: spans nest without overlap, the session's phases,
    ``launch`` and ``wait`` sit directly under ``step``, and ``step``
    and ``eval`` under their ``round``."""
    prob, w0, w_star = small_problem
    comm = CommConfig(seed=1) if driver == "sync" else _async_comm()
    with jax.profiler.trace(str(tmp_path)):
        run_rounds(_flens(), prob, w0, w_star, rounds=4, comm=comm,
                   obs=TelemetryConfig())
    spans = sorted(_host_spans(tmp_path),
                   key=lambda s: (s["start"], -s["end"]))
    assert {s["name"] for s in spans} >= SESSION_PHASES[driver] | {
        "round", "step", "wait", "eval", "prepare", "finalize"}
    assert len({s["line"] for s in spans}) == 1
    stack, parent = [], {}
    for s in spans:
        while stack and stack[-1]["end"] <= s["start"]:
            stack.pop()
        if stack:
            assert s["end"] <= stack[-1]["end"], (stack[-1], s)  # nested
        parent[id(s)] = stack[-1]["name"] if stack else None
        stack.append(s)
    for s in spans:
        if s["name"].startswith("session.") or s["name"] in ("launch",
                                                             "wait"):
            assert parent[id(s)] == "step", s
        if s["name"] in ("step", "eval"):
            assert parent[id(s)] == "round", s
        if parent[id(s)] is not None:
            assert s["round"] is not None
    assert sorted(s["round"] for s in spans if s["name"] == "round") == [
        0, 1, 2, 3]


def test_summary_compile_exec_split(small_problem):
    """Exactly one compile round per jit variant; wall-clock splits into
    compile_s (first call, trace+compile) and exec_s (steady state)."""
    prob, w0, w_star = small_problem
    hist = run_rounds(_flens(), prob, w0, w_star, rounds=5,
                      comm=CommConfig(seed=1), obs=TelemetryConfig())
    tel = hist.telemetry
    assert tel["rounds"] == 5
    assert tel["compile_rounds"] == 1
    assert tel["compile_s"] > 0
    assert tel["exec_s"] > 0
    assert tel["exec_s_per_round"] == pytest.approx(tel["exec_s"] / 4)
    # phase spans partition the loop: step + eval at minimum
    assert {"step", "eval"} <= set(tel["phase_s"])
    counters = tel["metrics"]["counters"]
    assert counters["bytes_up"] == float(
        sum(t.bytes_up.sum() for t in hist.traces))
    assert counters["bytes_down"] == float(
        sum(t.bytes_down.sum() for t in hist.traces))
    assert counters["variant_retraces"] == 0


def test_async_summary_metrics(small_problem):
    """Async runs populate the flight recorder and the staleness /
    queue-depth histograms."""
    prob, w0, w_star = small_problem
    comm = CommConfig(
        seed=1, async_mode=True, buffer_size=2,
        channel=ChannelModel(straggler_prob=0.3, straggler_slowdown=4.0),
        staleness="inverse")
    hist = run_rounds(_flens(), prob, w0, w_star, rounds=5, comm=comm,
                      obs=TelemetryConfig(flight_capacity=8))
    tel = hist.telemetry
    hists = tel["metrics"]["histograms"]
    assert hists["staleness"]["count"] == sum(
        int((~np.isnan(t.staleness)).sum()) for t in hist.traces)
    assert hists["commit_buffer_depth"]["count"] == len(hist.traces)
    assert hists["buffered_upload_age_s"]["min"] >= 0.0
    assert "inflight_depth" in hists
    fl = tel["flight"]
    assert fl["capacity"] == 8
    assert fl["total"] > 8  # dispatches + arrivals + commits overflow 8
    assert fl["kept"] == 8
    assert fl["truncated"] == fl["total"] - 8


def test_variant_retraces_counted(small_problem):
    """Every NEW jitted round variant after the first counts as one
    retrace; its first execution is billed as a compile round."""
    prob, w0, w_star = small_problem
    opt = make_optimizer("fedavg", lr=1.0, local_steps=2)
    # two static variants over four rounds (an adaptive-k policy would
    # announce its k changes exactly like this)
    opt.round_signature = lambda t, state: t // 2
    hist = run_rounds(opt, prob, w0, w_star, rounds=4,
                      comm=CommConfig(seed=1), obs=TelemetryConfig())
    tel = hist.telemetry
    assert tel["metrics"]["counters"]["variant_retraces"] == 1
    assert tel["compile_rounds"] == 2


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def test_metrics_registry_kinds():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.inc()
    c.inc(2.5)
    assert reg.counter("n") is c  # get-or-create
    reg.gauge("g").set(7)
    reg.histogram("h").observe_many([1.0, 2.0, 3.0])
    with pytest.raises(TypeError):
        reg.gauge("n")  # kind clash must not silently shadow
    snap = reg.snapshot()
    assert snap["counters"]["n"] == 3.5
    assert snap["gauges"]["g"] == 7.0
    assert snap["histograms"]["h"]["count"] == 3
    assert snap["histograms"]["h"]["p50"] == 2.0


def test_flight_recorder_ring_truncation():
    """The ring keeps the MOST RECENT capacity events; total/truncated
    count everything ever recorded."""
    rec = FlightRecorder(capacity=3)
    for i in range(7):
        rec.record("dispatch", float(i), client=i)
    assert rec.total == 7
    assert rec.truncated == 4
    assert [e["client"] for e in rec.events()] == [4, 5, 6]  # oldest first
    assert rec.stats() == {"capacity": 3, "total": 7, "kept": 3,
                           "truncated": 4}
    with pytest.raises(ValueError):
        rec.record("teleport", 0.0)
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_sink_specs(tmp_path, capsys):
    path = tmp_path / "sub" / "records.jsonl"
    sink = make_sink(f"jsonl:{path}")
    sink.emit({"type": "round", "x": float("nan"), "y": float("inf")})
    sink.close()
    rec = json.loads(path.read_text())
    assert rec["x"] is None and rec["y"] is None  # strict JSON, no NaN token
    make_sink("stdout").emit({"type": "round", "n": 1})
    assert json.loads(capsys.readouterr().out)["n"] == 1
    make_sink("null").emit({"whatever": 1})
    with pytest.raises(ValueError):
        make_sink("csv:nope")


def test_mean_staleness_all_nan():
    """A commit that delivered nobody has no lag to report: 0.0, not
    NaN (and not a RuntimeWarning from an empty mean)."""
    m = 4
    tr = RoundTrace(
        round=0,
        scheduled=np.zeros(m, dtype=bool),
        delivered=np.zeros(m, dtype=bool),
        straggler=np.zeros(m, dtype=bool),
        bytes_up=np.zeros(m),
        bytes_down=np.zeros(m),
        sim_time_s=0.0,
        staleness=np.full(m, np.nan),
    )
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert tr.mean_staleness == 0.0
    # sync traces (no staleness array) are 0.0 too
    assert RoundTrace(
        round=0, scheduled=np.ones(m, bool), delivered=np.ones(m, bool),
        straggler=np.zeros(m, bool), bytes_up=np.zeros(m),
        bytes_down=np.zeros(m), sim_time_s=1.0).mean_staleness == 0.0


# ---------------------------------------------------------------------------
# artifacts: History JSONL round-trip + report CLI
# ---------------------------------------------------------------------------


def test_history_jsonl_roundtrip(small_problem, tmp_path):
    """to_jsonl/from_jsonl must preserve every curve, per-round trace
    (incl. per-client NaN staleness), and the telemetry summary."""
    prob, w0, w_star = small_problem
    comm = CommConfig(
        seed=1, async_mode=True, buffer_size=2,
        channel=ChannelModel(straggler_prob=0.3, straggler_slowdown=4.0))
    hist = run_rounds(_flens(), prob, w0, w_star, rounds=4, comm=comm,
                      obs=TelemetryConfig(label="rt"))
    path = hist.to_jsonl(tmp_path / "hist.jsonl")
    back = History.from_jsonl(path)
    assert back.name == hist.name
    assert np.array_equal(hist.loss, back.loss)
    assert np.array_equal(hist.gap, back.gap)
    assert np.array_equal(hist.cumulative_bytes, back.cumulative_bytes)
    assert np.allclose(hist.staleness, back.staleness, equal_nan=True)
    assert back.telemetry["label"] == "rt"
    assert len(back.traces) == len(hist.traces)
    for a, b in zip(hist.traces, back.traces):
        assert np.array_equal(a.delivered, b.delivered)
        assert np.array_equal(a.bytes_up, b.bytes_up)
        assert np.allclose(a.staleness, b.staleness, equal_nan=True)
        assert a.version == b.version
        assert a.mean_staleness == b.mean_staleness


def test_history_jsonl_nonfinite(tmp_path):
    """Diverged runs (inf gap) must survive the strict-JSON round trip
    as NaN-free null tokens."""
    hist = History(
        name="diverged",
        loss=np.array([1.0, np.inf, np.nan]),
        gap=np.array([1.0, np.inf, np.nan]),
        grad_norm=np.array([1.0, 2.0, 3.0]),
        uplink_floats=4, downlink_floats=4, wall_time_s=0.1, rounds=2)
    back = History.from_jsonl(hist.to_jsonl(tmp_path / "d.jsonl"))
    assert back.loss[0] == 1.0
    # inf and NaN both travel as null -> come back as NaN
    assert np.isnan(back.loss[1]) and np.isnan(back.loss[2])
    with pytest.raises(ValueError):
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"type": "history", "schema": "repro.history/v999"}\n')
        History.from_jsonl(bad)


def test_report_cli_jsonl(small_problem, tmp_path, capsys):
    """`python -m repro.obs.report` renders the summary (phases,
    compile/exec split, bytes, staleness) and --check-schema passes on a
    healthy stream / fails on a drifted one."""
    from repro.obs import report

    prob, w0, w_star = small_problem
    path = tmp_path / "tel.jsonl"
    comm = CommConfig(
        seed=1, async_mode=True, buffer_size=2,
        channel=ChannelModel(straggler_prob=0.3, straggler_slowdown=4.0))
    run_rounds(_flens(), prob, w0, w_star, rounds=4, comm=comm,
               obs=TelemetryConfig(sink=f"jsonl:{path}", label="probe"))

    assert report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "== run probe ==" in out
    assert "compile" in out and "staleness" in out and "bytes" in out

    assert report.main([str(path), "--check-schema"]) == 0
    capsys.readouterr()

    # schema drift: summary missing a required key must fail loudly
    records = [json.loads(line) for line in path.read_text().splitlines()]
    summary = next(r for r in records if r["type"] == "summary")
    del summary["compile_s"]
    drifted = tmp_path / "drifted.jsonl"
    drifted.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    assert report.main([str(drifted), "--check-schema"]) == 1
    assert "SCHEMA DRIFT" in capsys.readouterr().out
    # a stream with no summary (truncated run) also fails
    truncated = tmp_path / "trunc.jsonl"
    truncated.write_text(json.dumps(
        {"type": "round", "round": 0, "wall_s": 0.1, "compile": True,
         "phases": {}}) + "\n")
    assert report.main([str(truncated), "--check-schema"]) == 1


def test_report_cli_bench(tmp_path, capsys):
    from repro.obs import report

    doc = {
        "schema": report.BENCH_SCHEMA,
        "dataset": "phishing", "rounds": 12, "budget_bytes": 1000.0,
        "optimizers": {"flens": {
            "compile_s": 1.0, "exec_s_per_round": 0.01,
            "bytes_total": 1000.0, "loss_final": 0.5,
            "loss_at_budget": 0.5}},
    }
    path = tmp_path / "BENCH_round_time.json"
    path.write_text(json.dumps(doc))
    assert report.main([str(path), "--check-schema"]) == 0
    assert report.main([str(path)]) == 0
    assert "flens" in capsys.readouterr().out
    del doc["optimizers"]["flens"]["loss_at_budget"]
    path.write_text(json.dumps(doc))
    assert report.main([str(path), "--check-schema"]) == 1


# ---------------------------------------------------------------------------
# compare.py: drift table + bench gate
# ---------------------------------------------------------------------------


def _bench_doc(exec_s=0.01, loss=0.5, bytes_total=1000):
    return {
        "schema": "bench_round_time/v1", "dataset": "phishing",
        "rounds": 12, "clients": 8, "budget_bytes": float(bytes_total),
        "optimizers": {"flens": {
            "compile_s": 1.0, "exec_s": exec_s * 11,
            "exec_s_per_round": exec_s, "wall_time_s": 1.0 + exec_s * 11,
            "bytes_total": float(bytes_total), "uplink_floats": 100,
            "loss_final": loss, "loss_at_budget": loss}},
    }


def test_compare_drift_table():
    """Every (record, field) comparison appears in the table — not just
    the first failure — with old/new values and pass/fail status."""
    from benchmarks.compare import compare, drift_table, violations_of

    base = {"variants": {"a": {
        "cumulative_bytes": [0, 100], "loss_final": 0.5,
        "stats": {"total_bytes_up": 60, "total_bytes_down": 40}}}}
    cur = {"variants": {"a": {
        "cumulative_bytes": [0, 120], "loss_final": 0.5 * (1 + 1e-5),
        "stats": {"total_bytes_up": 80, "total_bytes_down": 40}}}}
    rows = compare(cur, base, loss_rtol=5e-3)
    # all four fields compared, two fail
    assert [r["field"] for r in rows] == [
        "bytes_total", "stats.total_bytes_up", "stats.total_bytes_down",
        "loss_final"]
    assert [r["ok"] for r in rows] == [False, False, True, True]
    table = drift_table(rows)
    assert table.count("\n") >= 5  # header + rule + 4 rows
    assert "FAIL" in table and "PASS" in table
    assert "100" in table and "120" in table  # old AND new values shown
    viol = violations_of(rows)
    assert len(viol) == 2 and all("drifted" in v for v in viol)


def test_compare_bench_gate():
    """Deterministic fields gate exactly / at rtol; wall-clock only
    fails past the slowdown factor (speedups always pass)."""
    from benchmarks.compare import compare_bench, violations_of

    base = _bench_doc()
    # identical -> clean pass
    assert violations_of(compare_bench(_bench_doc(), base, 5e-3, 5.0)) == []
    # 3x slower passes at factor 5, 10x slower fails
    assert violations_of(
        compare_bench(_bench_doc(exec_s=0.03), base, 5e-3, 5.0)) == []
    viol = violations_of(
        compare_bench(_bench_doc(exec_s=0.1), base, 5e-3, 5.0))
    assert len(viol) == 1 and "exec_s_per_round" in viol[0]
    # 10x FASTER passes (slowdown-only gate)
    assert violations_of(
        compare_bench(_bench_doc(exec_s=0.001), base, 5e-3, 5.0)) == []
    # byte drift is exact-gated
    assert any("bytes_total" in v for v in violations_of(
        compare_bench(_bench_doc(bytes_total=1001), base, 5e-3, 5.0)))
    # loss drift past rtol fails
    assert any("loss_final" in v for v in violations_of(
        compare_bench(_bench_doc(loss=0.51), base, 5e-3, 5.0)))


def test_compare_bench_record_then_gate(tmp_path):
    """A missing bench baseline is installed from the current record
    (exit 0); the next run gates against it."""
    from benchmarks.compare import main as compare_main

    cur = tmp_path / "BENCH_round_time.json"
    baseline = tmp_path / "bench_baseline.json"
    cur.write_text(json.dumps(_bench_doc()))
    assert compare_main(["--bench", str(cur), str(baseline)]) == 0
    assert json.loads(baseline.read_text()) == _bench_doc()
    # second run: gate passes against the recorded baseline
    assert compare_main(["--bench", str(cur), str(baseline)]) == 0
    # a byte drift now fails the gate
    cur.write_text(json.dumps(_bench_doc(bytes_total=2000)))
    assert compare_main(["--bench", str(cur), str(baseline)]) == 1


# ---------------------------------------------------------------------------
# diagnostics: structured logging keeps warnings API-visible
# ---------------------------------------------------------------------------


def test_warn_with_context_dual_emission(caplog):
    """Driver diagnostics emit BOTH a structured log record (with
    machine-readable context) and a real warnings.warn."""
    with caplog.at_level(logging.WARNING, logger="repro.obs"):
        with pytest.warns(UserWarning, match="probe failed"):
            obs_log.warn_with_context("probe failed", round=3,
                                      optimizer="flens", policy=None)
    assert len(caplog.records) == 1
    rec = caplog.records[0]
    assert rec.context == {"round": 3, "optimizer": "flens", "policy": None}
    # None-valued context is dropped from the rendered suffix
    assert "round=3" in rec.getMessage() and "policy" not in rec.getMessage()


def test_quorum_cap_warning_api_visible(small_problem):
    """The async quorum-cap diagnostic must still surface through the
    warnings machinery after the logger conversion."""
    prob, w0, w_star = small_problem
    comm = CommConfig(
        seed=1, async_mode=True, buffer_size=prob.m,  # demands full quorum
        scheduler="uniform:0.4",  # but idles most clients
        channel=ChannelModel())
    with pytest.warns(UserWarning, match="quorum capped"):
        run_rounds(_flens(), prob, w0, w_star, rounds=2, comm=comm)
