"""Shared set-up of the benchmark's CPU tests: the manifest, a CPU size
for every cell, and a harness that takes the CPU for the chip."""
import gzip
import json
import pathlib

import jax
import pytest

from jax.profiler import ProfileData

from benchmarks.chip import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# rows and clients: a CPU size for every cell
SIZES = {"n": 4000, "m_clients": 10}
END_TO_END = {m["name"] for m in MANIFEST["end_to_end"]}
CHECKS = {"loss_gap", "grad_gap", "bytes_gap", "schedule_gap"}


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


# a seed whose first FLeNS step the guard accepts in every cell at
# SIZES: the check compares the first round, and a rejected step
# leaves nothing of the round's arithmetic to compare
SEED = 2**31 + 18


def run(name, tmp_path, seed=SEED, trace=False):
    return harness.run_cell(name, seed, 0.3, trace, sizes=SIZES,
                            out=tmp_path)


US = 1_000_000  # picoseconds in a microsecond


def _ev(meta, start_us, dur_us, long_name=None):
    stat = (f' stats {{ metadata_id: 90 str_value: "{long_name}" }}'
            if long_name else "")
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_us * US)}"
            f" duration_ps: {int(dur_us * US)}{stat} }}")


def synthetic_trace_text(chips=1):
    """A TPU trace whose numbers are known, as a text proto: four
    launches (three of ``jit__round``), kernel ops found by their HLO
    names, idle gaps."""
    modules = [(1, 0, 5), (2, 6, 3), (1, 10, 5), (1, 20, 5)]
    ops = [(10, 0, 2, "srht_fwd"),      # first launch: outside the slice
           (10, 10, 2, "srht_fwd"), (11, 12, 1, "topk"),
           (12, 14, 1, None),           # gap 13-14, then idle 15-20
           (10, 20, 2, "srht_t"), (12, 22.5, 2.5, None)]
    planes = []
    for chip in range(chips):
        mod_lines = " ".join(_ev(m, s, d) for m, s, d in modules)
        op_lines = " ".join(_ev(m, s, d, n) for m, s, d, n in ops)
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


def write_trace(path, text):
    """``text`` as a gzipped ``.xplane.pb.gz`` file at ``path``."""
    with gzip.open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)
