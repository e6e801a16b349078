"""The join of the program's host spans with the device's idle time, on a
synthetic trace whose numbers are known and on a copy of a chip trace;
and the readers of the sessions' per-layer metrics."""
import pathlib
import types

import pytest

from benchmarks.chip import harness, hostspans, xplane

from _bench_common import synthetic_trace, synthetic_trace_text, write_trace

DATA = pathlib.Path(__file__).parent / "data"
US = 1_000_000  # picoseconds in a microsecond

# (name, start us, end us, round): two rounds of the synthetic device
# trace's steady slice (10-25 us), whose idle stretches are 13-14,
# 15-20 and 22-22.5 us
HOST_SPANS = [
    ("round", 9, 17, 1), ("step", 9, 14.5, 1), ("launch", 9.5, 10, 1),
    ("session.account", 10, 10.5, 1), ("wait", 10.5, 14.5, 1),
    ("eval", 14.5, 16.5, 1),
    ("round", 17.5, 25, 2), ("step", 17.5, 24, 2),
    ("session.schedule", 17.5, 19, 2), ("launch", 19, 19.5, 2),
    ("wait", 19.5, 24, 2), ("eval", 24, 25, 2),
]
# a host event of the runtime, not of the program: never counted
OTHER = ("ExecuteHelper", 17.1, 17.4)
IDLE_US = {"wait": 2.0, "eval": 1.5, "session.schedule": 1.5,
           "launch": 0.5, "round": 0.5, hostspans.NO_SPAN: 0.5}


# launches of the synthetic device trace (start, end us), all programs
MODULES_US = [(0, 5), (6, 9), (10, 15), (20, 25)]


def _host_plane(spans=HOST_SPANS, other=OTHER, runtime=()):
    names = sorted({s[0] for s in spans} | {other[0]}
                   | {hostspans.ENQUEUE, hostspans.DONE})
    meta = {n: i + 1 for i, n in enumerate(names)}

    def ev(name, start, end, rnd=None):
        stat = ("" if rnd is None else
                f" stats {{ metadata_id: 98 int64_value: {rnd} }}")
        return (f"events {{ metadata_id: {meta[name]} "
                f"offset_ps: {int(start * US)} "
                f"duration_ps: {int((end - start) * US)}{stat} }}")

    program = " ".join(ev(*s) for s in spans)
    metadata = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in meta.items())
    return f'''planes {{
  id: 99 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {program} }}
  lines {{ id: 2 name: "runtime" timestamp_ns: 0 {ev(*other)}
    {" ".join(ev(*r) for r in runtime)} }}
  {metadata}
  stat_metadata {{ key: 98 value {{ id: 98 name: "round" }} }}
}}'''


def _event_line(name):
    """The start of a host event of ``name`` in ``traced_text``."""
    names = sorted({s[0] for s in HOST_SPANS} | {OTHER[0]}
                   | {hostspans.ENQUEUE, hostspans.DONE})
    return f"events {{ metadata_id: {names.index(name) + 1} "


def traced_text(chips=1, early_us=0.0):
    """The synthetic TPU trace with the program's spans on its host
    plane. With ``early_us`` the device plane reads that much early:
    the host's spans move later instead, and the runtime brackets each
    launch by 0.2 us (enqueued before it, done after it)."""
    spans = [(n, a + early_us, b + early_us, r) for n, a, b, r in HOST_SPANS]
    runtime = []
    if early_us:
        for a, b in MODULES_US:
            runtime += [(hostspans.ENQUEUE, a + early_us - 0.2,
                         a + early_us - 0.1),
                        (hostspans.DONE, b + early_us + 0.2,
                         b + early_us + 0.3)]
    return synthetic_trace_text(chips).replace(
        'planes { id: 99 name: "/host:CPU" }',
        _host_plane(spans, runtime=runtime))


def _traced():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(traced_text())


def test_spans_are_the_programs_with_their_rounds():
    spans = hostspans.read_spans(_traced())
    assert [(s.name, s.round) for s in spans] == [
        (name, rnd) for name, _, _, rnd in HOST_SPANS]
    assert spans[0].start == 9_000 and spans[0].end == 17_000


def test_idle_goes_to_the_innermost_span():
    split = hostspans.idle_by_span(_traced())
    assert split.window_s == pytest.approx(15e-6)
    assert split.idle_s == pytest.approx(6.5e-6)
    assert split.by_span == pytest.approx(
        {k: v * 1e-6 for k, v in IDLE_US.items()})
    assert split.share(lambda k: k.startswith("session.")) == pytest.approx(
        100 * 1.5 / 15)
    assert split.share(lambda k: k == "eval") == pytest.approx(100 * 1.5 / 15)


def test_the_device_plane_is_moved_onto_the_host_clock():
    """A device plane that reads 1 us early: the runtime's launch events
    bound the offset to 1 +- 0.2 us, and the split is the aligned one."""
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto(traced_text(early_us=1.0))
    assert hostspans.clock_offset(data) == hostspans.Offset(-1000, 200)
    split = hostspans.idle_by_span(data)
    assert split.by_span == pytest.approx(
        {k: v * 1e-6 for k, v in IDLE_US.items()})
    assert hostspans.clock_offset(_traced()) is None


@pytest.mark.parametrize("cut", ["first enqueue", "last done", "both"])
def test_the_offset_survives_launches_cut_at_the_trace_ends(cut):
    """A launch at either end of a trace may lack its runtime event: the
    pairing drops it and reads the same offset."""
    from jax.profiler import ProfileData

    enqueue = _event_line(hostspans.ENQUEUE)
    done = _event_line(hostspans.DONE)
    text = traced_text(early_us=1.0)
    host = text.index(f'name: "{hostspans.HOST_PLANE}"')
    first_enqueue = text.index(enqueue, host)
    last_done = text.rindex(done)
    if cut in ("last done", "both"):
        text = text[:last_done] + text[text.index("}", last_done) + 1:]
    if cut in ("first enqueue", "both"):
        text = (text[:first_enqueue]
                + text[text.index("}", first_enqueue) + 1:])
    data = ProfileData.from_text_proto(text)
    assert hostspans.clock_offset(data) == hostspans.Offset(-1000, 200)


def test_the_split_covers_the_idle_time_the_reduction_counts():
    split = hostspans.idle_by_span(_traced())
    red = xplane.reduce(_traced(), "jit__round", ())
    assert sum(split.by_span.values()) == pytest.approx(
        red.window_s - red.busy_s)


def test_a_trace_without_program_spans_has_no_split():
    assert hostspans.read_spans(synthetic_trace()) == []
    assert hostspans.idle_by_span(synthetic_trace()) is None


def test_innermost_pieces_follow_the_nesting():
    spans = [hostspans.Span("outer", 0, 10, None),
             hostspans.Span("a", 2, 4, None),
             hostspans.Span("b", 4, 6, None),
             hostspans.Span("c", 5, 6, None)]
    assert hostspans.innermost(spans, -1, 12) == [
        (-1, 0, hostspans.NO_SPAN), (0, 2, "outer"), (2, 4, "a"),
        (4, 5, "b"), (5, 6, "c"), (6, 10, "outer"),
        (10, 12, hostspans.NO_SPAN)]


def test_idle_intervals_complement_the_busy_union():
    assert hostspans.idle_intervals([(2, 4), (6, 7)], 0, 10) == [
        (0, 2), (4, 6), (7, 10)]
    assert hostspans.idle_intervals([(0, 10)], 0, 10) == []


def test_a_recorded_copy_keeps_the_spans_and_the_split(tmp_path):
    src = write_trace(tmp_path / "full.xplane.pb.gz", traced_text(chips=2))
    dst = str(tmp_path / "copy.xplane.pb.gz")
    hostspans.recorded_copy(src, dst, launches=3)
    assert len(xplane.read_devices(dst)) == 1
    assert hostspans.read_spans(dst) == hostspans.read_spans(_traced())
    assert hostspans.idle_by_span(dst) == hostspans.idle_by_span(_traced())


def test_a_recorded_copy_keeps_the_launch_events_it_needs(tmp_path):
    """The copy keeps the runtime's events of the launches it keeps
    (all four: the round's first three launches span the synthetic
    trace), so the offset reads as on the source, and the split too."""
    src = write_trace(tmp_path / "full.xplane.pb.gz",
                      traced_text(early_us=1.0))
    dst = str(tmp_path / "copy.xplane.pb.gz")
    hostspans.recorded_copy(src, dst, launches=3)
    events = hostspans.runtime_events(xplane.load(dst))
    assert len(events[hostspans.ENQUEUE]) == len(events[hostspans.DONE]) == 4
    assert hostspans.clock_offset(xplane.load(dst)) == hostspans.Offset(
        -1000, 200)
    assert hostspans.idle_by_span(dst) == hostspans.idle_by_span(src)


def test_the_command_prints_the_split(tmp_path, capsys):
    import json

    src = write_trace(tmp_path / "t.xplane.pb.gz", traced_text())
    assert hostspans.main([src]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["idle_by_span"]["wait"] == pytest.approx(2e-6)
    assert out["idle_no_span_share"] == pytest.approx(100 * 0.5 / 15)
    empty = write_trace(tmp_path / "e.xplane.pb.gz", synthetic_trace_text())
    assert hostspans.main([empty]) == 1


# -- the readers of the sessions' metrics -------------------------------------

def _run(rounds):
    return types.SimpleNamespace(rounds=rounds, reduction=None, shapes={},
                                 peaks={})


SYNC = [{"round": t, "wall_s": 0.01, "round_calls": 1,
         "phases": {"step": 0.008, "session.schedule": 0.002,
                    "launch": 0.001, "session.account": 0.0005,
                    "wait": 0.004, "eval": 0.0015}} for t in (1, 2)]
ASYNC = [{"round": t, "wall_s": 0.02, "round_calls": calls,
          "phases": {"step": 0.018, "session.pump": 0.001,
                     "launch": 0.002 * calls, "session.aggregate": 0.003,
                     "session.dispatch": 0.002}}
         for t, calls in ((1, 1), (2, 3), (3, 2))]
# a program that opens no session span and counts no launch
BARE = [{"round": 1, "wall_s": 0.01, "phases": {"step": 0.008,
                                                "eval": 0.002}}]


def test_session_share_reads_the_session_spans():
    read = harness.load_reader("session_share")
    assert read(_run(SYNC)) == pytest.approx(100 * 0.0025 / 0.01)
    assert read(_run(ASYNC)) == pytest.approx(100 * 0.006 / 0.02)
    assert read(_run(BARE)) is None
    assert read(_run([])) is None


def test_round_calls_per_commit_averages_the_launches():
    read = harness.load_reader("round_calls_per_commit")
    assert read(_run(ASYNC)) == pytest.approx(2.0)
    assert read(_run(SYNC)) == 1.0
    assert read(_run(BARE)) is None


# -- a chip trace ---------------------------------------------------------------

# a copy of a traced ``phishing.sync-qint8`` run on a TPU v5e: launches
# 1-12 of the round's program, rounds 0-11
RECORDED = str(DATA / "phishing.sync-qint8.xplane.pb.gz")


def _on_host_clock(e, offset):
    return e.start - offset.ns, e.end - offset.ns


def test_a_recorded_chip_trace_puts_each_launch_inside_its_round():
    """Once on the host clock, every steady launch of the round's program
    starts after the host's ``launch`` span of its round began and ends
    before that round's ``wait`` span ended, within the offset's error
    (73 us here; the least margins read 1.85 ms and 0.38 ms)."""
    data = xplane.load(RECORDED)
    offset = hostspans.clock_offset(data)
    runs = [e for e in xplane.read_devices(data)[0].modules
            if e.name.startswith("jit__round")]
    spans = hostspans.read_spans(data)
    launches = [s for s in spans if s.name == "launch"]
    waits = {s.round: s for s in spans if s.name == "wait"}
    tol = offset.error_ns
    matched = []
    for run in runs[1:]:
        start, end = _on_host_clock(run, offset)
        launch = [s for s in launches if s.start <= start + tol][-1]
        assert end <= waits[launch.round].end + tol
        matched.append(launch.round)
    assert matched == list(range(1, 12))


def test_the_device_plane_of_a_recorded_chip_trace_reads_early():
    """The runtime's launch events put the device plane 1.51 ms early
    against the host, to within 73 us. Read raw, the driver's loss
    program would start on the device before the host's ``eval`` span
    asked for it; on the host clock it starts inside that span."""
    data = xplane.load(RECORDED)
    offset = hostspans.clock_offset(data)
    assert -2_000_000 < offset.ns < -1_000_000
    assert offset.error_ns < 100_000
    evals = [s for s in hostspans.read_spans(data) if s.name == "eval"]
    losses = [e for e in xplane.read_devices(data)[0].modules
              if e.name.startswith("jit_global_value")]
    assert len(losses) == 11
    for e in losses:
        start, end = _on_host_clock(e, offset)
        inside = [s for s in evals if s.start <= start and end <= s.end]
        assert len(inside) == 1
        assert not any(s.start <= e.start <= s.end for s in evals)


def test_a_recorded_chip_trace_names_its_idle_time():
    """On the same copy the device idles most of the steady slice, and
    nearly all of that idle time lies under a named host span: most
    under the session's scheduling, then the driver's evaluation."""
    split = hostspans.idle_by_span(RECORDED)
    assert split.idle_s / split.window_s > 0.9
    assert split.by_span[hostspans.NO_SPAN] < 0.01 * split.idle_s
    assert max(split.by_span, key=split.by_span.get) == "session.schedule"
    assert split.by_span["eval"] > 0.1 * split.idle_s
