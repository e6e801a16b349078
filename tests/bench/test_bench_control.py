"""The check's control and planted faults in the reference fail the
cell's limits while the program passes them; the chip guards: no TPU
or an unknown device kind is an error, never a CPU fallback."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip import control, harness

from _bench_common import CELLS, CHECKS, MANIFEST, ROOT, SEED, SIZES
from _bench_common import cpu_harness  # noqa: F401 — a fixture


@pytest.mark.parametrize("name", CELLS)
def test_control_and_faults_fail_the_limits(name, tmp_path, cpu_harness):
    limits = harness.load_cell(name).limits
    got = {}
    for rec in control.readings(name, [SEED], sizes=SIZES[name],
                                out=tmp_path):
        got[rec["variant"]] = rec
    for key in CHECKS:
        assert got["program"][key] <= limits[key]
    for variant in ("bf16", "unchanged", "half"):
        assert any(got[variant][key] > limits[key]
                   for key in ("loss_gap", "grad_gap")), got[variant]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.NoChip, match="not in peaks.json"):
        harness.peaks_for("TPU v0 imaginary")
    peaks = harness.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    source = json.loads((ROOT / "benchmarks/chip/peaks.json").read_text())
    assert "TPU v5e" in source["source"]


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_ENABLE_X64", None)
    proc = subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
