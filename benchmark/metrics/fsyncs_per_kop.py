"""WAL fsyncs (all three nodes') per 1,000 acknowledged writes."""

UNIT = "1/kop"
LAYER = "durability"
MOVES = "ops_s"


def read(run):
    writes = run.acked_of("write")
    if run.deltas is None or writes <= 0:
        return None
    return 1000.0 * run.deltas.counter("wal", "fsyncs") / writes
