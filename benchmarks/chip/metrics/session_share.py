"""session_share: the sessions' own host work between the driver's jit
boundaries (the ``session.*`` spans of ``repro.obs``: scheduling and
channel draws, shard materialization, stats, byte accounting, the async
heap, aggregation, snapshot collection and dispatch) as a share of the
round's wall time, over the traced call's steady rounds. None where the
program opens no such span."""
LAYER = "sessions"
UNIT = "%"
MOVES = "client_updates_per_s"
SOURCE = "program_span"
PREFIX = "session."


def read(run):
    wall = sum(r["wall_s"] for r in run.rounds)
    phases = [(k, v) for r in run.rounds for k, v in r["phases"].items()
              if k.startswith(PREFIX)]
    if not phases or wall <= 0:
        return None
    return 100.0 * sum(v for _, v in phases) / wall
