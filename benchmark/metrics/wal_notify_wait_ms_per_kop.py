"""What the WAL writers wait for the coordinators' state lock to hand
over a batch's written events, per 1,000 acknowledged operations: the
counter ``wal_notify_wait_ns`` (``wal_notify_many``: lock asked -> lock
held, one round per fsync batch), the three coordinators added. That
wait lies inside every ``append_durable``."""

UNIT = "ms/kop"
LAYER = "durability"
MOVES = "commit_p95_ms"

COUNTER = "wal_notify_wait_ns"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    if COUNTER not in run.deltas.after["coordinator"]:
        return None  # a program without the account
    return run.deltas.counter("coordinator", COUNTER) / 1e6 / (run.acked / 1000.0)
