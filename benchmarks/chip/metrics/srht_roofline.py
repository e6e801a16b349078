"""srht_roofline: the least time the chip needs for the round's SRHT
applications (``counts.flens_srht_calls``: A_j S^T, S g_j, S S^T and
S^T delta; bytes and operations of the algorithm, the larger bound of
each) over the device time of the SRHT kernels, in the steady slice."""
from benchmarks.chip import counts

LAYER = "kernels"
UNIT = "%"
MOVES = "client_updates_per_s"
SOURCE = "device_trace"
KERNEL = "srht"


def read(run):
    red = run.reduction
    spent = red.kernel_s.get(KERNEL, 0.0) if red is not None else 0.0
    if spent <= 0 or red.rounds <= 0:
        return None
    s = run.shapes
    need = sum(counts.min_time(ops, nbytes, run.peaks)[0]
               for ops, nbytes in counts.flens_srht_calls(
                   s["rows"], s["clients"], s["dim"], s["k"]))
    return 100.0 * need * red.rounds / spent
