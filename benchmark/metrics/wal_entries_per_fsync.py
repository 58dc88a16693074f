"""Log entries the WALs framed per fsync (all three nodes' counters
``entries`` / ``fsyncs``): what group commit amortises over."""

UNIT = "1/fsync"
LAYER = "durability"
MOVES = "ops_s"


def read(run):
    if run.deltas is None:
        return None
    fsyncs = run.deltas.counter("wal", "fsyncs")
    if fsyncs <= 0:
        return None
    return run.deltas.counter("wal", "entries") / fsyncs
