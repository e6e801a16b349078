"""The program's host spans in a profiler trace, and the device's idle
time named by them.

``repro.obs`` opens a ``jax.profiler`` annotation for every span of an
enabled tracer, and one named ``round`` around each round, each with the
round's index as its ``round`` statistic. A traced call's ``.xplane.pb``
therefore holds them on its host plane (``/host:CPU``) beside the device
planes. The two clocks do not quite agree: on a TPU v5e the device
plane read 0.5 to 1.6 ms early against the host, by an amount fixed
within a trace. The runtime's own host events around each launch bound
that offset (``clock_offset``), and the join moves the device's times
onto the host clock first. Here:

* ``read_spans``: the program's spans (a program that annotates nothing
  has none; the host plane's other events are left out);
* ``clock_offset``: the device plane's offset and its error;
* ``idle_by_span``: every idle interval of the first chip's steady slice
  (``xplane.steady_slice``) goes to the innermost span covering it, and
  idle time under no span to ``NO_SPAN``;
* ``recorded_copy``: a small copy of a trace for the tests, the first
  chip's plane, the program's spans and the runtime's launch events.

    python3 -m benchmarks.chip.hostspans <.xplane.pb or directory>
    python3 -m benchmarks.chip.hostspans <src> --copy <dst.xplane.pb.gz> \\
        --launches <n>

The first prints the idle split of the newest trace as JSON; the second
writes a copy that ends with launch ``n`` of the round's program.
"""
from __future__ import annotations

import argparse
import dataclasses
import gzip
import itertools
import json
import os
import sys

from benchmarks.chip import xplane

HOST_PLANE = "/host:CPU"
ROUND_MODULE = "jit__round"
NO_SPAN = "no span"
# the span names of ``run_rounds`` and of the sessions (``session.*``)
DRIVER_SPANS = frozenset({"round", "prepare", "begin_variant", "probe_plan",
                          "step", "launch", "wait", "eval", "finalize"})
ROUND_STAT = "round"
# the runtime's host events around each launch of a program on a chip
ENQUEUE = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"
EDGE = 2  # launches at a trace's ends that may lack a runtime event


def is_program_span(name: str) -> bool:
    return name in DRIVER_SPANS or name.startswith("session.")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int  # ns, on the trace's clock
    end: int
    round: "int | None"  # the round the span belongs to


def read_spans(trace) -> "list[Span]":
    """The program's spans on the host plane of a trace (a path, see
    ``xplane.load``, or a ``jax.profiler.ProfileData``), by start."""
    data = xplane.load(trace) if isinstance(trace, str) else trace
    spans = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if not is_program_span(e.name):
                    continue
                rnd = dict(e.stats).get(ROUND_STAT)
                start = int(e.start_ns)
                spans.append(Span(e.name, start, start + int(e.duration_ns),
                                  None if rnd is None else int(rnd)))
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans


@dataclasses.dataclass(frozen=True)
class Offset:
    """Where the device plane's clock lies against the host's: a device
    time minus ``ns`` is a host time, to within ``error_ns``."""

    ns: int
    error_ns: int


def runtime_events(data) -> "dict[str, list[tuple[int, int]]]":
    """The runtime's host events that bracket each launch of a program
    (``ENQUEUE``: the host starts to enqueue it; ``DONE``: the host learns
    it finished), as sorted (start, end) pairs by name."""
    out: "dict[str, list[tuple[int, int]]]" = {ENQUEUE: [], DONE: []}
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    start = int(e.start_ns)
                    out[e.name].append((start, start + int(e.duration_ns)))
    for v in out.values():
        v.sort()
    return out


def clock_offset(data) -> "Offset | None":
    """The device plane's offset from the host clock, from causality: a
    launch starts on the device after the host began to enqueue it, and
    ends before the host hears it is done. The k-th launch of the first
    chip pairs with the k-th ``ENQUEUE`` and ``DONE`` event; the offset
    lies between the largest (device end - done) and the least (device
    start - enqueue). A launch cut by the trace's start or stop may lack
    an event, so up to ``EDGE`` unpaired ones are dropped at either end,
    and the narrowest consistent pairing is kept. None where the trace
    holds no such events, or no pairing is consistent."""
    events = runtime_events(data)
    devices = xplane.read_devices(data)
    if not devices:
        return None
    mods = sorted(devices[0].modules, key=lambda e: e.start)

    def alignments(evs):
        n = min(len(mods), len(evs))
        if n == 0 or max(len(mods), len(evs)) - n > EDGE:
            return []
        return list(itertools.product((mods[:n], mods[len(mods) - n:]),
                                      (evs[:n], evs[len(evs) - n:])))

    los = [max(m.end - d[0] for m, d in zip(ms, dn))
           for ms, dn in alignments(events[DONE])]
    his = [min(m.start - i[0] for m, i in zip(ms, enq))
           for ms, enq in alignments(events[ENQUEUE])]
    fits = [(lo, hi) for lo in los for hi in his if lo <= hi]
    if not fits:
        return None
    lo, hi = min(fits, key=lambda b: b[1] - b[0])
    return Offset((lo + hi) // 2, (hi - lo + 1) // 2)


def innermost(spans: "list[Span]", start: int,
              end: int) -> "list[tuple[int, int, str]]":
    """``[start, end]`` cut into sorted disjoint pieces, each named by the
    innermost span covering it (the covering span that started last:
    spans of one thread nest), or ``NO_SPAN``."""
    cuts = sorted({start, end} | {t for s in spans for t in (s.start, s.end)
                                  if start < t < end})
    order = sorted(spans, key=lambda s: (s.start, -s.end))
    pieces, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i].start <= a:
            active.append(order[i])
            i += 1
        active = [s for s in active if s.end > a]
        pieces.append((a, b, active[-1].name if active else NO_SPAN))
    return pieces


def idle_intervals(busy: "list[tuple[int, int]]", start: int,
                   end: int) -> "list[tuple[int, int]]":
    """The complement of the sorted disjoint ``busy`` pairs in
    ``[start, end]``."""
    out, cursor = [], start
    for s, t in busy:
        if s > cursor:
            out.append((cursor, min(s, end)))
        cursor = max(cursor, t)
    if end > cursor:
        out.append((cursor, end))
    return [(s, t) for s, t in out if t > s]


@dataclasses.dataclass
class IdleSplit:
    """The first chip's steady slice and its idle time by span."""

    window_s: float
    idle_s: float
    by_span: "dict[str, float]"  # idle seconds by innermost span name
    offset: "Offset | None"  # the device clock's, None: taken as aligned

    def share(self, match) -> float:
        """Percent of the slice idle under the spans ``match`` accepts."""
        return 100.0 * sum(v for k, v in self.by_span.items()
                           if match(k)) / self.window_s


def idle_by_span(trace, module: str = ROUND_MODULE) -> "IdleSplit | None":
    """The device's idle time in the steady slice, by the innermost host
    span covering it once the device's times are put on the host clock
    (``clock_offset``; a trace without the runtime's events is taken as
    aligned); None where the trace holds no program span."""
    data = xplane.load(trace) if isinstance(trace, str) else trace
    spans = read_spans(data)
    if not spans:
        return None
    devices = xplane.read_devices(data)
    if not devices:
        raise ValueError(f"no {xplane.DEVICE_PREFIX}* plane in the trace")
    start, end, _ = xplane.steady_slice(devices[0], module)
    busy = xplane.busy_intervals(xplane.clip(devices[0].ops, start, end))
    offset = clock_offset(data)
    shift = offset.ns if offset is not None else 0
    idle = [(s - shift, t - shift) for s, t in idle_intervals(busy, start,
                                                              end)]
    pieces = innermost(spans, start - shift, end - shift)
    totals: "dict[str, int]" = {}
    j = 0
    for s, t in idle:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < t:
            a, b, name = pieces[k]
            overlap = min(b, t) - max(a, s)
            if overlap > 0:
                totals[name] = totals.get(name, 0) + overlap
            k += 1
    by_span = {name: ns / 1e9 for name, ns in
               sorted(totals.items(), key=lambda kv: -kv[1])}
    return IdleSplit((end - start) / 1e9,
                     sum(t - s for s, t in idle) / 1e9, by_span, offset)


def _text_span(meta: int, span: Span, round_meta: int, t0: int) -> str:
    stat = ("" if span.round is None else
            f" stats {{ metadata_id: {round_meta} int64_value: {span.round} }}")
    return (f"events {{ metadata_id: {meta} "
            f"offset_ps: {(span.start - t0) * 1000} "
            f"duration_ps: {(span.end - span.start) * 1000}{stat} }}")


def _metadata(ids: "dict[str, int]") -> str:
    return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: {xplane._quoted(name)} }} }}'
                    for name, i in ids.items())


def recorded_copy(src: str, dst: str, launches: int,
                  module: str = ROUND_MODULE) -> None:
    """A small copy of a traced call, gzipped to ``dst``: the first
    chip's plane from its first launch of ``module`` to the end of launch
    ``launches``, with each operation's name and HLO name; on the host
    plane, the program's spans of every round that overlaps that stretch
    and the runtime's ``ENQUEUE`` and ``DONE`` events of the launches
    kept."""
    from jax.profiler import ProfileData

    data = xplane.load(src)
    dev = xplane.read_devices(data)[0]
    runs = [e for e in dev.modules if e.name.startswith(module)]
    start, end = runs[0].start, runs[min(launches, len(runs)) - 1].end
    spans = read_spans(data)
    rounds = {s.round for s in spans if s.name == "round"
              and s.start < end and s.end > start}
    kept = [s for s in spans if s.round in rounds]
    mods = sorted(dev.modules, key=lambda e: e.start)
    events = runtime_events(data)
    runtime = []
    if len(mods) == len(events[ENQUEUE]) == len(events[DONE]):
        for m, i, d in zip(mods, events[ENQUEUE], events[DONE]):
            if m.start >= start and m.end <= end:
                runtime += [Span(ENQUEUE, *i, None), Span(DONE, *d, None)]
    t0 = min([start] + [s.start for s in kept + runtime])
    ids: "dict[str, int]" = {}
    lines = []
    for line_id, (line, evs) in enumerate(
            ((xplane.MODULES, dev.modules), (xplane.OPS, dev.ops)), start=1):
        body = " ".join(
            xplane._text_event(ids.setdefault(e.name, len(ids) + 1), e,
                               999999, t0)
            for e in evs if e.start >= start and e.end <= end)
        lines.append(f'lines {{ id: {line_id} name: "{line}" '
                     f'timestamp_ns: 0 {body} }}')
    host_ids: "dict[str, int]" = {}
    host_lines = []
    for line_id, (line, evs) in enumerate(
            (("python", kept), ("runtime", runtime)), start=1):
        body = " ".join(
            _text_span(host_ids.setdefault(s.name, len(host_ids) + 1), s,
                       999998, t0) for s in evs)
        host_lines.append(f'lines {{ id: {line_id} name: "{line}" '
                          f'timestamp_ns: 0 {body} }}')
    text = (f'planes {{ id: 1 name: "{dev.name}" {" ".join(lines)} '
            f'{_metadata(ids)} stat_metadata {{ key: 999999 value {{ '
            f'id: 999999 name: "long_name" }} }} }} '
            f'planes {{ id: 2 name: "{HOST_PLANE}" {" ".join(host_lines)} '
            f'{_metadata(host_ids)} stat_metadata {{ key: 999998 value {{ '
            f'id: 999998 name: "{ROUND_STAT}" }} }} }}')
    with gzip.open(dst, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help=".xplane.pb(.gz), or a directory")
    ap.add_argument("--copy", help="write a recorded copy here")
    ap.add_argument("--launches", type=int, default=8)
    args = ap.parse_args(argv)
    path = (xplane.newest_xplane(args.trace) if os.path.isdir(args.trace)
            else args.trace)
    if args.copy:
        recorded_copy(path, args.copy, args.launches)
        return 0
    split = idle_by_span(path)
    if split is None:
        print(f"hostspans: no program span in {path}", file=sys.stderr)
        return 1
    offset = split.offset
    print(json.dumps({"trace": path, "window_s": split.window_s,
                      "idle_s": split.idle_s, "idle_by_span": split.by_span,
                      "clock_offset_s": offset and offset.ns / 1e9,
                      "clock_error_s": offset and offset.error_ns / 1e9,
                      "idle_session_share": split.share(
                          lambda k: k.startswith("session.")),
                      "idle_eval_share": split.share(lambda k: k == "eval"),
                      "idle_no_span_share": split.share(
                          lambda k: k == NO_SPAN)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
