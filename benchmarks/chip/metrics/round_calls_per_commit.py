"""round_calls_per_commit: the jitted round launches per round record
(the ``round_calls`` field ``repro.obs`` sessions write: 1 for a sync
round, the number of base model versions for an async commit), averaged
over the traced call's steady commits. None where the records carry no
such field."""
LAYER = "sessions"
UNIT = "calls"
MOVES = "client_updates_per_s"
SOURCE = "program_counter"


def read(run):
    calls = [r["round_calls"] for r in run.rounds if "round_calls" in r]
    if not calls:
        return None
    return sum(calls) / len(calls)
