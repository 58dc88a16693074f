"""Snapshots the machines' release cursors made the logs cut (the
coordinators' counter ``release_cursor_snapshots``, all three replicas'
added) per 1,000 acknowledged operations."""

UNIT = "1/kop"
LAYER = "durability"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    if "release_cursor_snapshots" not in run.deltas.after["coordinator"]:
        return None  # a program without the counter
    return 1000.0 * run.deltas.counter(
        "coordinator", "release_cursor_snapshots") / run.acked
