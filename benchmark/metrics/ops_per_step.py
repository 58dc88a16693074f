"""Acknowledged operations per device step dispatched (all three
coordinators' steps, full-width and active-set)."""

UNIT = "ops/step"
LAYER = "wave loop"
MOVES = "ops_s"


def read(run):
    if run.deltas is None:
        return None
    steps = run.deltas.scalar("steps")
    return run.acked / steps if steps > 0 else None
