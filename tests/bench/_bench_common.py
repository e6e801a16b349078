"""Shared set-up of the benchmark's CPU tests: the manifest, the rule
every configuration file keeps, a CPU size for every cell, a harness
that takes the CPU for the chip, and synthetic TPU traces."""
import gzip
import json
import math
import pathlib

import jax
import pytest

from jax.profiler import ProfileData

from benchmarks.chip import counts, harness, xplane
from repro.data.libsvm_like import PAPER_DATASETS

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
END_TO_END = {m["name"] for m in MANIFEST["end_to_end"]}
CHECKS = {"loss_gap", "grad_gap", "bytes_gap", "schedule_gap"}


# ---------------------------------------------------------------------------
# the rule every configuration file keeps
# ---------------------------------------------------------------------------

PAPER = "https://arxiv.org/abs/2409.15216"
# Table II's rows (``PAPER_DATASETS`` holds smaller twins for the CPU):
# SUSY, phishing and UCI Covertype as LIBSVM publishes them
PAPER_ROWS = {"susy": 5_000_000, "phishing": 11_055, "covtype": 581_012}
# every number the harness reads from a configuration file
SIZE_KEYS = ("n", "dim", "m_clients", "sketch_k", "spectrum_decay",
             "label_noise", "lam", "data_seed")
# the shapes of a deployment: never cut, as a model's widths are not
WIDTHS = ("dim", "sketch_k")


def configuration_faults(entry: dict, data: dict) -> "list[str]":
    """The clauses of the configuration rule that a manifest entry
    ``entry`` and its file ``data`` break, each as ``"<clause>: why"``;
    empty where it keeps them all.

    A configuration that names the FLeNS paper as its source is Table II
    at full size: its width, clients and sketch size are
    ``PAPER_DATASETS``'s, its rows the paper's, and nothing is reduced.
    Any other states what it was taken from: ``published`` names the
    source down to the part that defines the deployment,
    ``published_sizes`` holds the source's numbers (``n`` and ``dim`` at
    least), and every number the harness reads is either published or
    named under ``assumed`` with its reason. A number that differs from
    the published one is listed under ``reduced`` and is smaller; a
    width is never reduced."""
    faults = []
    reduced = data.get("reduced", [])
    if reduced != entry["reduced"]:
        faults.append(f"reduced: file {reduced} against manifest "
                      f"{entry['reduced']}")
    if data.get("dtype") != "float32":
        faults.append(f"dtype: {data.get('dtype')!r}, not 'float32'")
    if data.get("source") != entry["source"]:
        faults.append(f"source: file {data.get('source')!r} against "
                      f"manifest {entry['source']!r}")
    if PAPER in (entry["source"], data.get("source")):
        if entry["name"] not in PAPER_DATASETS:
            return faults + [f"paper: {entry['name']!r} is not in Table II"]
        spec = PAPER_DATASETS[entry["name"]]
        got = tuple(data.get(k) for k in ("n", "dim", "m_clients",
                                          "sketch_k"))
        want = (PAPER_ROWS[entry["name"]], spec.dim, spec.m_clients,
                spec.sketch_k)
        if got != want:
            faults.append(f"paper: (n, dim, m_clients, sketch_k) {got}, "
                          f"Table II {want}")
        if reduced != []:
            faults.append(f"paper: reduced {reduced} at full size")
        return faults
    if not (isinstance(data.get("published"), str)
            and data["published"].strip()):
        faults.append("published: no statement of the source")
    published = data.get("published_sizes")
    if not (isinstance(published, dict) and {"n", "dim"} <= set(published)):
        faults.append(f"published_sizes: {published!r} lacks n or dim")
        published = published if isinstance(published, dict) else {}
    assumed = data.get("assumed") or {}
    for key in SIZE_KEYS:
        if key not in published and not (
                isinstance(assumed.get(key), str) and assumed[key].strip()):
            faults.append(f"unsourced: {key} is neither published nor "
                          f"assumed with a reason")
    for key, value in published.items():
        if key in reduced:
            continue
        if data.get(key) != value:
            faults.append(f"unlisted_change: {key} {data.get(key)!r}, "
                          f"published {value!r}, not in reduced")
    for key in reduced:
        if key in WIDTHS:
            faults.append(f"width: {key} is a width and is never reduced")
        elif key not in published:
            faults.append(f"not_smaller: {key} has no published size")
        elif not data.get(key, published[key]) < published[key]:
            faults.append(f"not_smaller: {key} {data.get(key)!r} is not "
                          f"below the published {published[key]!r}")
    return faults


# ---------------------------------------------------------------------------
# a CPU size for every cell
# ---------------------------------------------------------------------------

# rows and clients of every cell on the CPU
CPU_ROWS = {"n": 4000, "m_clients": 10}
# the width and the sketch size, cut only where a configuration is wider
CPU_WIDTH_CAPS = {"dim": 256, "sketch_k": 64}


def cpu_sizes(config: dict) -> dict:
    """The CPU size of a configuration (``harness.load_cell``'s
    ``sizes``): a few thousand rows over ten clients, and a width and a
    sketch size no larger than the caps. The chip runs at full width
    decide ``correct``; on the CPU the kernels run on the reference ops,
    and the tests check the harness's logic, which a width of 2,000
    would only slow. A cap of 256 keeps the SRHT's transform wider than
    128 lanes, so a wide configuration still traces its wide path."""
    return {**CPU_ROWS, **{k: cap for k, cap in CPU_WIDTH_CAPS.items()
                           if config[k] > cap}}


SIZES = {name: cpu_sizes(harness.load_cell(name).config) for name in CELLS}


@pytest.fixture
def cpu_harness(monkeypatch):
    """The harness on this process's CPU: x64 off as on the chip, the
    CPU devices in place of the chips with the v5e's peaks, and no
    persistent compilation cache."""
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    monkeypatch.setattr(harness, "find_chips", lambda chips: (
        jax.devices(), harness.peaks_for("TPU v5 lite")))
    monkeypatch.setattr(harness, "_compile_cache", lambda: None)
    yield
    jax.config.update("jax_enable_x64", before)


# a seed whose first FLeNS step the guard accepts in every cell at its
# CPU size: the check compares the first round, and a rejected step
# leaves nothing of the round's arithmetic to compare
SEED = 2**31 + 18


def run(name, tmp_path, seed=SEED, trace=False, sizes=None):
    """One harness run of cell ``name`` at ``sizes``, by default its CPU
    size."""
    sizes = SIZES[name] if sizes is None else sizes
    return harness.run_cell(name, seed, 0.3, trace, sizes=sizes,
                            out=tmp_path)


US = 1_000_000  # picoseconds in a microsecond


def _ev(meta, start_us, dur_us, long_name=None, unit_ps=US):
    stat = (f' stats {{ metadata_id: 90 str_value: "{long_name}" }}'
            if long_name else "")
    return (f"events {{ metadata_id: {meta} "
            f"offset_ps: {int(start_us * unit_ps)}"
            f" duration_ps: {int(dur_us * unit_ps)}{stat} }}")


# What the synthetic trace's steady slice holds, in its microseconds:
# launches 2 and 3 of ``jit__round`` from 10 to 25, SRHT ops of 2 + 2,
# and ops of 2 + 1 + 1 + 2 + 2.5 that never overlap.
SLICE_ROUNDS, SLICE_US, SRHT_US, BUSY_US = 2, 15, 4, 8.5


def synthetic_trace_text(chips=1, unit_ps=US):
    """A TPU trace whose numbers are known, as a text proto: four
    launches (three of ``jit__round``), kernel ops found by their HLO
    names, idle gaps. ``unit_ps`` stretches it: the picoseconds that
    each of its microseconds lasts (see ``cell_trace``)."""
    modules = [(1, 0, 5), (2, 6, 3), (1, 10, 5), (1, 20, 5)]
    ops = [(10, 0, 2, "srht_fwd"),      # first launch: outside the slice
           (10, 10, 2, "srht_fwd"), (11, 12, 1, "topk"),
           (12, 14, 1, None),           # gap 13-14, then idle 15-20
           (10, 20, 2, "srht_t"), (12, 22.5, 2.5, None)]
    planes = []
    for chip in range(chips):
        mod_lines = " ".join(_ev(m, s, d, unit_ps=unit_ps)
                             for m, s, d in modules)
        op_lines = " ".join(_ev(m, s, d, n, unit_ps=unit_ps)
                            for m, s, d, n in ops)
        planes.append(f'''planes {{
  id: {chip + 1} name: "/device:TPU:{chip}"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {mod_lines} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {op_lines} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit__round(7)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_global_value(3)" }} }}
  event_metadata {{ key: 10 value {{ id: 10 name: "custom-call.2" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "custom-call.5" }} }}
  event_metadata {{ key: 12 value {{ id: 12 name: "fusion.1" }} }}
  stat_metadata {{ key: 90 value {{ id: 90 name: "long_name" }} }}
}}''')
    planes.append('planes { id: 99 name: "/host:CPU" }')
    return "\n".join(planes)


def synthetic_trace(chips=1):
    return ProfileData.from_text_proto(synthetic_trace_text(chips))


def cell_trace(shapes, peaks):
    """The synthetic trace stretched to one cell's round: its SRHT ops
    in the steady slice last about twice the least time ``counts`` gives
    the slice's rounds of SRHT calls at the round shapes ``shapes``
    (rows, clients, dim, k, eval_rows), and the slice is stretched with
    them. A microsecond of the layout becomes a whole,
    even number of nanoseconds, so every event starts and lasts whole
    nanoseconds, as the reduction reads them.

    Returns the text and the per-layer readings that follow from the
    trace and ``counts`` alone."""
    need = sum(counts.min_time(ops, nbytes, peaks)[0]
               for ops, nbytes in counts.flens_srht_calls(
                   shapes["rows"], shapes["clients"], shapes["dim"],
                   shapes["k"]))
    unit_ns = 2 * math.ceil(need * SLICE_ROUNDS * 2 / SRHT_US * 1e9 / 2)
    unit_s = unit_ns * 1e-9
    flops = (counts.flens_round_flops(shapes["rows"], shapes["clients"],
                                      shapes["dim"], shapes["k"])
             + counts.eval_flops(shapes["eval_rows"], shapes["dim"]))
    readings = {
        "srht_roofline": 100.0 * need * SLICE_ROUNDS / (SRHT_US * unit_s),
        "round_mfu": 100.0 * flops * SLICE_ROUNDS / (SLICE_US * unit_s)
        / float(peaks["bf16_flops_per_s"]),
        "device_idle_share": 100.0 * (1.0 - BUSY_US / SLICE_US),
    }
    return synthetic_trace_text(unit_ps=unit_ns * 1000), readings


def write_trace(path, text):
    """``text`` as a gzipped ``.xplane.pb.gz`` file at ``path``."""
    with gzip.open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def check_traced_run(name, tmp_path, monkeypatch, sizes, listed):
    """A CPU trace has no TPU plane: the reduction reads a TPU trace
    whose kernels carry the names the chip's do, stretched to the cell's
    round at ``sizes`` (``cell_trace``). Every per-layer metric in
    ``listed`` comes out, with the breakdown; those that follow from the
    trace and ``counts`` alone read what they must, the others a share
    between 0 and 100. Returns the run's result line."""
    cfg = harness.load_cell(name, sizes=sizes).config
    # a round: every row, every client, and the driver's eval over the rows
    shapes = {"rows": cfg["n"], "clients": cfg["m_clients"],
              "dim": cfg["dim"], "k": cfg["sketch_k"], "eval_rows": cfg["n"]}
    text, expected = cell_trace(shapes, harness.peaks_for("TPU v5 lite"))
    trace = write_trace(tmp_path / "t.xplane.pb.gz", text)
    monkeypatch.setattr(xplane, "newest_xplane", lambda d: trace)
    result = run(name, tmp_path, trace=True, sizes=sizes)
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == listed
    for metric, v in result["metrics"].items():
        if metric in expected:
            assert v["value"] == pytest.approx(expected[metric], rel=1e-9), (
                metric, v["value"], expected[metric])
        assert 0 < v["value"] < 100, metric
    assert 0 < result["device"]["busy_s"] < result["device"]["window_s"]
    assert result["breakdown"]["device_ops"]
    return result
