"""Commit stage ``durable_commit`` (the leader's durable watermark covers
the entry -> the quorum's commit is seen: the replication round through
the followers' waves and WALs), median over the window's samples."""

UNIT = "ms"
LAYER = "wave loop"
MOVES = "commit_p95_ms"


def read(run):
    h = run.deltas.hist("commit", "durable_commit") if run.deltas else None
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
