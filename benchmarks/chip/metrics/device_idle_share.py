"""device_idle_share: the share of the steady slice of the trace in
which no operation ran on the chip (1 minus the union of the
operations' intervals over the slice)."""
LAYER = "device"
UNIT = "%"
MOVES = "client_updates_per_s"
SOURCE = "device_trace"


def read(run):
    red = run.reduction
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * red.idle_share
