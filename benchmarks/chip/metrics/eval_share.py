"""eval_share: the driver's per-round evaluation (the host pull of the
loss and the gradient norm, ``run_rounds``'s ``eval`` span) as a share
of the round's wall time, over the traced call's steady rounds."""
LAYER = "driver loop"
UNIT = "%"
MOVES = "client_updates_per_s"
SOURCE = "program_span"


def read(run):
    wall = sum(r["wall_s"] for r in run.rounds)
    if not run.rounds or wall <= 0:
        return None
    spent = sum(r["phases"].get("eval", 0.0) for r in run.rounds)
    return 100.0 * spent / wall
