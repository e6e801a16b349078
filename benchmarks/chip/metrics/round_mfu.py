"""round_mfu: the floating-point operations that one FLeNS round and the
driver's evaluation need by the algorithm (``counts.flens_round_flops``
and ``counts.eval_flops``), times the rounds per second of the traced
steady slice, over the chip's bf16 peak."""
from benchmarks.chip import counts

LAYER = "whole round on the device"
UNIT = "%"
MOVES = "client_updates_per_s"
SOURCE = "device_trace"


def read(run):
    red = run.reduction
    if red is None or red.window_s <= 0 or red.rounds <= 0:
        return None
    s = run.shapes
    flops = (counts.flens_round_flops(s["rows"], s["clients"], s["dim"],
                                      s["k"])
             + counts.eval_flops(s["eval_rows"], s["dim"]))
    rate = flops * red.rounds / red.window_s
    return 100.0 * rate / float(run.peaks["bf16_flops_per_s"])
