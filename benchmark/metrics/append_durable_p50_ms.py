"""Commit stage ``append_durable`` (log append -> the WAL's durable
watermark covers it), median over the window's samples (groups with
``gid & 63 == 0``)."""

UNIT = "ms"
LAYER = "durability"
MOVES = "commit_p95_ms"


def read(run):
    h = run.deltas.hist("commit", "append_durable") if run.deltas else None
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
