"""A configuration from outside the paper's Table II enters the benchmark
as data files and manifest entries alone: a checkout that holds a
manifest, a configuration, a traffic mix and limits for ``probe-wide``,
and the benchmark's own per-layer readers, runs through the harness
unchanged. ``probe-wide`` is 300 features wide, so its SRHT transform
is 512 long, wider than the 128 lanes every Table II cell fits in."""
import json
import shutil

import pytest

from benchmarks.chip import harness
from repro.core.flens import FLeNS
from repro.data.libsvm_like import PAPER_DATASETS

from _bench_common import (CPU_ROWS, END_TO_END, MANIFEST, PAPER,
                           PAPER_ROWS, SIZES, check_traced_run,
                           configuration_faults, cpu_sizes, run)
from _bench_common import cpu_harness  # noqa: F401 — a fixture
from test_bench_faults import _unchanged

CONFIG = "probe-wide"
CELL = "probe-wide.sync-qint8-wide"
ENTRY = {"name": CONFIG, "source": "https://example.org/probe-wide",
         "file": f"benchmarks/chip/configs/{CONFIG}.json", "reduced": ["n"],
         "why": "a wide dense deployment outside Table II, cut in rows"}
DATA = {
    "name": CONFIG,
    "source": ENTRY["source"],
    "published": "a stated deployment: 40,000 rows of 300 dense features "
                 "over 10 silos",
    "published_sizes": {"n": 40_000, "dim": 300, "m_clients": 10},
    "n": 4000, "dim": 300, "m_clients": 10, "sketch_k": 40,
    "spectrum_decay": 1.5, "label_noise": 0.05, "lam": 0.001,
    "dtype": "float32", "data_seed": 0,
    "reduced": ["n"],
    "assumed": {
        "sketch_k": "k = 40 of 300, as the paper's k is a few times below d",
        "spectrum_decay": "the seeded twin's power-law feature spectrum",
        "label_noise": "the twin's label flips",
        "lam": "the paper's ridge weight 1e-3",
        "data_seed": "one fixed twin per configuration",
    },
}
TRAFFIC = {"driver": "sync", "channel": None, "codecs": {"default": "qint8"},
           "warmup_rounds": 0, "rounds_per_s": 4.5, "trace_rounds": 4,
           "compare_steps": 1}
# placeholders, as susy.sync-qint8-slow's: the probe is never run on a chip
LIMITS = {"loss_gap": 4e-4, "grad_gap": 7e-4, "bytes_gap": 0,
          "schedule_gap": 0}
METRICS = ("eval_share", "device_idle_share", "round_mfu", "srht_roofline",
           "session_share")


@pytest.fixture
def probe(tmp_path, monkeypatch):
    """A checkout that holds the probe's manifest and files beside the
    benchmark's readers and peaks, with the harness pointed at it."""
    root = tmp_path / "checkout"
    here = root / "benchmarks" / "chip"
    shutil.copytree(harness.HERE / "metrics", here / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.HERE / "peaks.json", here / "peaks.json")
    manifest = dict(
        MANIFEST, configs=[ENTRY],
        workloads=[{"name": CELL, "config": CONFIG,
                    "traffic": "sync-qint8-wide", "chips": 1,
                    "why": "sync FLeNS k=40 over 10 silos of 400 rows, "
                           "qint8 uplinks: the SRHT at n = 512"}],
        per_layer=[dict(m, workloads=[CELL]) for m in MANIFEST["per_layer"]
                   if m["name"] in METRICS])
    for path, body in (
            (root / "BENCHMARK.json", manifest),
            (here / "configs" / f"{CONFIG}.json", DATA),
            (here / "workloads" / "sync-qint8-wide.json", TRAFFIC),
            (here / "limits" / f"{CELL}.json", LIMITS)):
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body, indent=2))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "HERE", here)
    out = tmp_path / "out"
    out.mkdir()
    return out


def test_the_probe_keeps_the_configuration_rule():
    assert configuration_faults(ENTRY, DATA) == []


def _broken(clause):
    """The probe's entry and file with one clause of the rule broken."""
    entry, data = dict(ENTRY), json.loads(json.dumps(DATA))
    if clause == "unlisted_change":
        data["m_clients"] = 20
    elif clause == "unsourced":
        del data["assumed"]["lam"]
    elif clause == "paper":
        data["source"] = PAPER
    elif clause == "source":
        entry["source"] = "https://example.org/elsewhere"
    elif clause == "published":
        del data["published"]
    elif clause == "published_sizes":
        del data["published_sizes"]["dim"]
    elif clause == "not_smaller":
        data["n"] = 80_000
    elif clause == "width":
        data["dim"] = 200
        data["reduced"] = entry["reduced"] = ["n", "dim"]
    elif clause == "reduced":
        entry["reduced"] = []
    elif clause == "dtype":
        data["dtype"] = "bfloat16"
    return entry, data


@pytest.mark.parametrize("clause", [
    "unlisted_change", "unsourced", "paper", "source", "published",
    "published_sizes", "not_smaller", "width", "reduced", "dtype"])
def test_breaking_a_clause_fails_the_configuration_rule(clause):
    faults = configuration_faults(*_broken(clause))
    assert any(f.startswith(f"{clause}:") for f in faults), faults


@pytest.mark.parametrize("name", sorted(PAPER_DATASETS))
def test_a_table_ii_configuration_keeps_the_paper_pins(name):
    """Each of Table II's datasets enters under the paper's own numbers,
    and under no others: half the paper's rows is not Table II."""
    spec = PAPER_DATASETS[name]
    entry = {"name": name, "source": PAPER, "reduced": []}
    data = {"name": name, "source": PAPER, "dtype": "float32",
            "reduced": [], "n": PAPER_ROWS[name], "dim": spec.dim,
            "m_clients": spec.m_clients, "sketch_k": spec.sketch_k}
    assert configuration_faults(entry, data) == []
    faults = configuration_faults(entry, dict(data, n=PAPER_ROWS[name] // 2))
    assert any(f.startswith("paper:") for f in faults), faults


def test_the_cpu_size_cuts_width_only_where_a_configuration_is_wider():
    """Table II's cells run at today's CPU size; only a configuration
    wider than the caps has its width and sketch size cut."""
    paper = {c["name"] for c in MANIFEST["configs"] if c["source"] == PAPER}
    for cell in MANIFEST["workloads"]:
        if cell["config"] in paper:
            assert SIZES[cell["name"]] == CPU_ROWS, (cell["name"], SIZES)
    assert cpu_sizes(DATA) == {**CPU_ROWS, "dim": 256}
    assert cpu_sizes({"dim": 2000, "sketch_k": 500}) == {
        **CPU_ROWS, "dim": 256, "sketch_k": 64}


def test_the_probe_traced_run_reads_every_listed_metric(probe, cpu_harness,
                                                        monkeypatch):
    """The probe's one run that reads ``correct`` true: traced, so it
    also reads every per-layer metric listed for the cell."""
    result = check_traced_run(CELL, probe, monkeypatch, {}, set(METRICS))
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["compiled_in_window"] == 0


def test_the_probe_with_its_state_unchanged_is_not_correct(
        probe, cpu_harness, monkeypatch):
    """An untraced run: it reports the end-to-end metrics, and a round
    that leaves the state unchanged reads ``correct`` false."""
    monkeypatch.setattr(FLeNS, "round", _unchanged(FLeNS.round))
    result = run(CELL, probe, sizes={})
    assert set(result["metrics"]) == END_TO_END
    assert result["attempted"] >= 2
    assert result["correct"] is False, result["checks"]
