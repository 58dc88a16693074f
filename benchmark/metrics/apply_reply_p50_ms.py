"""Commit stages ``commit_apply`` + ``apply_reply`` (commit observed ->
machine applied -> reply issued), their medians added."""

UNIT = "ms"
LAYER = "apply + reply"
MOVES = "commit_p95_ms"


def read(run):
    if run.deltas is None:
        return None
    total = 0.0
    for stage in ("commit_apply", "apply_reply"):
        h = run.deltas.hist("commit", stage)
        p = h.percentile_ns(50) if h else None
        if p is None:
            return None
        total += p
    return total / 1e6
