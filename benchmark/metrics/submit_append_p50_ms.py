"""Commit stage ``submit_append`` (the client's submit -> the leader's log
append: ingress ring, classification, the wait for the step thread),
median over the window's samples (groups with ``gid & 63 == 0``)."""

UNIT = "ms"
LAYER = "ingress"
MOVES = "commit_p95_ms"


def read(run):
    h = run.deltas.hist("commit", "submit_append") if run.deltas else None
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
