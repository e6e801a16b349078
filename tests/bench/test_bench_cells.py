"""Every cell's harness path, end to end at a CPU size: the same code a
chip run executes, with the CPU taken for the chip (``cpu_harness``) and
``sizes`` cutting the data to a few thousand rows; the kernels run on
the reference ops, as on any CPU."""
import pytest

from benchmarks.chip import harness

from _bench_common import (CELLS, CHECKS, END_TO_END, MANIFEST, SIZES,
                           check_traced_run, run)
from _bench_common import cpu_harness  # noqa: F401 — a fixture


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end_and_is_correct(name, tmp_path, cpu_harness):
    result = run(name, tmp_path)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert result["compiled_in_window"] == 0
    assert set(result["metrics"]) == END_TO_END
    assert all(v["value"] > 0 for k, v in result["metrics"].items()
               if k != "peak_mem_gb")
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == CHECKS
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_every_listed_metric(name, tmp_path, cpu_harness,
                                              monkeypatch):
    """Every per-layer metric the manifest lists for the cell comes out
    of a traced run at the cell's CPU size (``check_traced_run``)."""
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if name in m["workloads"]}
    check_traced_run(name, tmp_path, monkeypatch, SIZES[name], listed)


def test_window_rounds_follow_the_stated_rate():
    mix = {"rounds_per_s": 4.5, "compare_steps": 3}
    assert harness.window_rounds(mix, 30) == 1 + 135
    assert harness.window_rounds(mix, 0.1) == 1 + 4


def test_compile_clock_counts_compiles_while_open():
    import jax
    import jax.numpy as jnp

    x = jnp.arange(7.0)
    with harness.CompileClock() as clock:
        jax.jit(lambda v: v * 3 - 1)(x).block_until_ready()
    assert len(clock.ends) == 1
    jax.jit(lambda v: v * 5 - 2)(x).block_until_ready()  # clock closed
    assert len(clock.ends) == 1
    assert clock.between(clock.ends[0] - 1, clock.ends[0]) == 1
    assert clock.between(clock.ends[0], clock.ends[0] + 1) == 0
