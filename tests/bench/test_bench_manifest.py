"""``BENCHMARK.json`` and the files it names: every cell, configuration,
traffic mix, limit and per-layer reader is where the harness looks for
it, and says what the manifest says."""
import json
import re

import pytest

from benchmarks.chip import harness

from _bench_common import CHECKS, MANIFEST, ROOT, configuration_faults

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
HERE = ROOT / "benchmarks" / "chip"


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"][1] == "benchmarks/chip/bench.py"
    assert all((ROOT / p).is_dir() for p in MANIFEST["paths"])
    assert 1 <= MANIFEST["run_seconds"] <= 51


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("cfg", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_configuration_is_the_paper_table(cfg):
    """A configuration from the paper is Table II at full size; any
    other states its source, its published sizes and what it assumed
    (``configuration_faults``)."""
    data = json.loads((ROOT / cfg["file"]).read_text())
    assert configuration_faults(cfg, data) == []


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_every_cell_finds_its_files_and_reports_enough(cell):
    loaded = harness.load_cell(cell["name"])
    assert cell["chips"] == 1
    assert set(loaded.limits) == CHECKS
    assert loaded.limits["bytes_gap"] == loaded.limits["schedule_gap"] == 0
    e2e = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert loaded.per_layer


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_reader_declares_what_the_manifest_says(metric):
    src = (HERE / "metrics" / f"{metric['name']}.py").read_text()
    for key, field in (("LAYER", "layer"), ("UNIT", "unit"),
                       ("MOVES", "moves"), ("SOURCE", "source")):
        assert f'{key} = "{metric[field]}"' in src
    assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    assert callable(harness.load_reader(metric["name"]))


def test_bounds():
    bounds = {m["name"]: m["bound"] for m in MANIFEST["end_to_end"]}
    assert bounds["setup_s"] <= 0.25
    assert all(0.01 <= b <= 0.25 for b in bounds.values())
