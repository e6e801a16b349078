"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the numbers
the per-layer metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<i>``); its ``XLA
Modules`` line has one event per launch of a compiled program, its
``XLA Ops`` line one event per operation. The steady slice runs from the
start of the second launch of the round's program (the first one of a
call follows its compile) to the end of its last launch; everything
below is clipped to it:

* busy time: the union of the operations' intervals;
* a kernel's time: the summed durations of the operations whose name or
  whose HLO name (the ``long_name``/``hlo_op`` statistics) contains the
  kernel's name;
* the operations that took most time, and the longest idle gaps, each
  gap named by the operations on either side of it (the program's host
  spans are not in the profiler's trace).
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os

DEVICE_PREFIX = "/device:TPU:"
MODULES = "XLA Modules"
OPS = "XLA Ops"
NAME_STATS = ("long_name", "hlo_op", "tf_op", "name")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    label: str  # the name and the event's name-like statistics
    start: int  # ns
    end: int


@dataclasses.dataclass
class Device:
    name: str
    modules: "list[Event]"
    ops: "list[Event]"


def newest_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def _events(line) -> "list[Event]":
    out = []
    for e in line.events:
        parts = [e.name]
        for key, value in e.stats:
            if key in NAME_STATS and isinstance(value, str):
                parts.append(value)
        start = int(e.start_ns)
        out.append(Event(e.name, " ".join(parts), start,
                         start + int(e.duration_ns)))
    out.sort(key=lambda ev: ev.start)
    return out


def load(path: str):
    """A ``.xplane.pb`` file, or a gzipped one (``.xplane.pb.gz``), as a
    ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def read_devices(trace) -> "list[Device]":
    """The TPU planes of a trace: a path (see ``load``), or a
    ``jax.profiler.ProfileData``."""
    data = load(trace) if isinstance(trace, str) else trace
    devices = []
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        devices.append(Device(
            plane.name,
            _events(lines[MODULES]) if MODULES in lines else [],
            _events(lines[OPS]) if OPS in lines else []))
    return devices


def steady_slice(dev: Device, module: str) -> "tuple[int, int, int]":
    """(start, end, launches) of the slice from the second launch of the
    program whose name starts with ``module`` to the end of its last
    launch; ``launches`` counts the launches that start inside it."""
    runs = [e for e in dev.modules if e.name.startswith(module)]
    if len(runs) < 2:
        raise ValueError(f"{dev.name}: {len(runs)} launch(es) of "
                         f"{module!r}, need two or more")
    return runs[1].start, runs[-1].end, len(runs) - 1


def clip(events: "list[Event]", start: int, end: int) -> "list[Event]":
    out = []
    for e in events:
        s, t = max(e.start, start), min(e.end, end)
        if t > s:
            out.append(dataclasses.replace(e, start=s, end=t))
    return out


def busy_intervals(events: "list[Event]") -> "list[tuple[int, int]]":
    """The union of the events' intervals, as sorted disjoint pairs."""
    merged: "list[list[int]]" = []
    for e in sorted(events, key=lambda ev: ev.start):
        if merged and e.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end)
        else:
            merged.append([e.start, e.end])
    return [(s, t) for s, t in merged]


def busy_ns(events: "list[Event]") -> int:
    return sum(t - s for s, t in busy_intervals(events))


def kernel_ns(events: "list[Event]", kernel: str) -> int:
    return sum(e.end - e.start for e in events if kernel in e.label)


def top_ops(events: "list[Event]", n: int = 10) -> "list[list]":
    totals: "dict[str, int]" = {}
    for e in events:
        totals[e.name] = totals.get(e.name, 0) + (e.end - e.start)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(events: "list[Event]", start: int, end: int,
              n: int = 10) -> "list[list]":
    """The longest stretches of the slice with no operation running,
    named by the operations before and after them."""
    ordered = sorted(events, key=lambda ev: ev.start)
    gaps = []
    cursor, before = start, "slice start"
    for e in ordered:
        if e.start > cursor:
            gaps.append((e.start - cursor, f"{before} -> {e.name}"))
        if e.end > cursor:
            cursor, before = e.end, e.name
    if end > cursor:
        gaps.append((end - cursor, f"{before} -> slice end"))
    gaps.sort(key=lambda g: -g[0])
    return [[name, ns / 1e9] for ns, name in gaps[:n]]


@dataclasses.dataclass
class Reduction:
    """What the per-layer metrics read from one traced call."""

    window_s: float  # length of the steady slice
    busy_s: float  # device-busy time in it, averaged over the chips
    rounds: int  # round launches that start in the slice
    kernel_s: "dict[str, float]"  # device time per kernel name, summed
    device_ops: "list[list]"
    idle_gaps: "list[list]"

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(trace, module: str, kernels=()) -> Reduction:
    """The steady slice's numbers from a trace (path or ProfileData);
    ``kernels`` names the kernels whose device time is summed."""
    devices = read_devices(trace)
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane in the trace")
    windows, busies, rounds = [], [], []
    kernel_s = {name: 0.0 for name in kernels}
    first = None
    for dev in devices:
        start, end, launches = steady_slice(dev, module)
        ops = clip(dev.ops, start, end)
        windows.append((end - start) / 1e9)
        busies.append(busy_ns(ops) / 1e9)
        rounds.append(launches)
        for name in kernels:
            kernel_s[name] += kernel_ns(ops, name) / 1e9
        if first is None:
            first = (ops, start, end)
    ops, start, end = first
    chips = len(devices)
    return Reduction(
        window_s=max(windows),
        busy_s=sum(busies) / chips,
        rounds=min(rounds),
        kernel_s={k: v / chips for k, v in kernel_s.items()},
        device_ops=top_ops(ops),
        idle_gaps=idle_gaps(ops, start, end))


def _quoted(text: str) -> str:
    """``text`` as a string of the text proto format (HLO op names hold
    quotes)."""
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _text_event(meta: int, ev: Event, long_meta: int, t0: int) -> str:
    stat = ""
    extra = ev.label[len(ev.name):].strip()
    if extra:
        stat = (f" stats {{ metadata_id: {long_meta} "
                f"str_value: {_quoted(extra)} }}")
    return (f"events {{ metadata_id: {meta} offset_ps: {(ev.start - t0) * 1000}"
            f" duration_ps: {(ev.end - ev.start) * 1000}{stat} }}")


def recorded_copy(src: str, dst: str, module: str, launches: int) -> None:
    """A small copy of a chip trace, for the tests of this reduction:
    the first chip's plane alone, from its first launch of ``module`` to
    the end of launch ``launches``, with each operation's name and HLO
    name, gzipped to ``dst`` (``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    dev = read_devices(src)[0]
    runs = [e for e in dev.modules if e.name.startswith(module)]
    start, end = runs[0].start, runs[min(launches, len(runs)) - 1].end
    ids: "dict[str, int]" = {}
    lines = []
    for line_id, (line, events) in enumerate(
            ((MODULES, dev.modules), (OPS, dev.ops)), start=1):
        kept = [e for e in events if e.start >= start and e.end <= end]
        body = " ".join(_text_event(ids.setdefault(e.name, len(ids) + 1), e,
                                    999999, start) for e in kept)
        lines.append(f'lines {{ id: {line_id} name: "{line}" '
                     f'timestamp_ns: 0 {body} }}')
    meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: {_quoted(name)} }} }}' for name, i in ids.items())
    text = (f'planes {{ id: 1 name: "{dev.name}" {" ".join(lines)} {meta} '
            f'stat_metadata {{ key: 999999 value {{ id: 999999 '
            f'name: "long_name" }} }} }}')
    data = ProfileData.text_proto_to_serialized_xspace(text)
    with gzip.open(dst, "wb") as f:
        f.write(data)
