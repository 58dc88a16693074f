"""Share of the WALs' append requests (a contiguous run or a single
write) that continued their writer's sequence above the snapshot floor
and so extended the file's index in place, with no ``Seq`` built: the
WAL counters ``runs_in_place`` / ``runs``, all three nodes' added. The
rest overlapped a floor, overwrote, or left a gap, and took the exact
per-entry rules."""

UNIT = "%"
LAYER = "durability"
MOVES = "ops_s"

COUNTER = "runs_in_place"


def read(run):
    if run.deltas is None or COUNTER not in run.deltas.after["wal"]:
        return None  # a program without the account
    runs = run.deltas.counter("wal", "runs")
    if runs <= 0:
        return None
    return 100.0 * run.deltas.counter("wal", COUNTER) / runs
