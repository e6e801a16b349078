"""The reduction from a profiler trace to the per-layer numbers: the
steady slice, the busy union, the idle share, kernel time found by name,
and the breakdown, on a synthetic trace whose numbers are known and on
a copy of a chip trace."""
import pathlib

import pytest

from benchmarks.chip import xplane

from _bench_common import synthetic_trace as _trace
from _bench_common import synthetic_trace_text, write_trace

def test_steady_slice_starts_at_the_second_launch():
    dev = xplane.read_devices(_trace())[0]
    start, end, launches = xplane.steady_slice(dev, "jit__round")
    assert (start, end, launches) == (10_000, 25_000, 2)


def test_busy_union_idle_share_and_kernels():
    red = xplane.reduce(_trace(), "jit__round", ("srht", "topk"))
    assert red.window_s == pytest.approx(15e-6)
    # ops in the slice: 2 + 1 + 1 + 2 + 2.5 us, none overlapping
    assert red.busy_s == pytest.approx(8.5e-6)
    assert red.idle_share == pytest.approx(1 - 8.5 / 15)
    assert red.rounds == 2
    # found by the HLO name statistic, not the op's own name
    assert red.kernel_s == {"srht": pytest.approx(4e-6),
                            "topk": pytest.approx(1e-6)}


def test_breakdown_ops_and_gaps():
    red = xplane.reduce(_trace(), "jit__round", ())
    assert red.device_ops[0] == ["custom-call.2", pytest.approx(4e-6)]
    assert [name for name, _ in red.device_ops] == [
        "custom-call.2", "fusion.1", "custom-call.5"]
    assert red.idle_gaps[0] == ["fusion.1 -> custom-call.2",
                                pytest.approx(5e-6)]
    assert [g[1] for g in red.idle_gaps] == pytest.approx(
        [5e-6, 1e-6, 0.5e-6])


def test_overlapping_ops_count_once():
    evs = [xplane.Event("a", "a", 0, 10), xplane.Event("b", "b", 5, 12),
           xplane.Event("c", "c", 20, 25)]
    assert xplane.busy_intervals(evs) == [(0, 12), (20, 25)]
    assert xplane.busy_ns(evs) == 17


def test_every_chip_is_averaged():
    red = xplane.reduce(_trace(chips=4), "jit__round", ("srht",))
    assert red.busy_s == pytest.approx(8.5e-6)
    assert red.kernel_s["srht"] == pytest.approx(4e-6)


def test_a_trace_without_a_second_launch_is_refused():
    dev = xplane.read_devices(_trace())[0]
    with pytest.raises(ValueError, match="need two or more"):
        xplane.steady_slice(dev, "jit_global_value")


def test_a_recorded_copy_reduces_as_its_source(tmp_path):
    """``recorded_copy`` keeps the first chip's launches of the round
    and every op among them, with the names the reduction looks for;
    a gzipped trace reads as a plain one."""
    src = write_trace(tmp_path / "full.xplane.pb.gz",
                      synthetic_trace_text(chips=2))
    dst = str(tmp_path / "copy.xplane.pb.gz")
    xplane.recorded_copy(src, dst, "jit__round", 3)
    assert len(xplane.read_devices(dst)) == 1
    kernels = ("srht", "topk")
    assert (xplane.reduce(dst, "jit__round", kernels)
            == xplane.reduce(_trace(), "jit__round", kernels))


def test_a_recorded_copy_keeps_quoted_op_names(tmp_path):
    """A chip's HLO op names hold quotes (``custom_call_target=
    "tpu_custom_call"``); the copy keeps them, and every op after them."""
    name = 'custom-call.2 = f32[8] custom_call_target=\\"tpu_custom_call\\"'
    text = synthetic_trace_text().replace('name: "custom-call.2"',
                                          f'name: "{name}"')
    src = write_trace(tmp_path / "full.xplane.pb.gz", text)
    dst = str(tmp_path / "copy.xplane.pb.gz")
    xplane.recorded_copy(src, dst, "jit__round", 3)
    kernels = ("srht", "topk")
    assert xplane.reduce(dst, "jit__round", kernels) == xplane.reduce(
        src, "jit__round", kernels)
    assert any('"tpu_custom_call"' in e.name
               for e in xplane.read_devices(dst)[0].ops)


def test_a_recorded_chip_trace():
    """A copy of a traced ``susy.sync-qint8-slow`` run on a TPU v5e (first
    chip, launches 1-4 of the round): the slice holds three rounds, the
    SRHT kernel is found by its HLO name and takes most of the busy
    time, and the longest gap follows the driver's evaluation."""
    path = str(pathlib.Path(__file__).parent / "data"
               / "susy.sync-qint8-slow.xplane.pb.gz")
    red = xplane.reduce(path, "jit__round", ("srht",))
    assert red.rounds == 3
    assert red.window_s == pytest.approx(0.642312922)
    assert red.busy_s == pytest.approx(0.619137481)
    assert red.idle_share == pytest.approx(0.0360812, abs=1e-6)
    assert red.kernel_s["srht"] == pytest.approx(0.546592588)
    name, seconds = red.device_ops[0]
    assert "srht_apply_pallas" in name and "tpu_custom_call" in name
    assert seconds == pytest.approx(0.525661933)
    assert red.idle_gaps[0][1] == pytest.approx(0.00248003)
