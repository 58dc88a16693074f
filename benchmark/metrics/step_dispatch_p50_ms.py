"""Wave sub-phase ``step_dispatch`` (of ``host_pack``: the call of the
jitted step, its argument transfer and the dispatch), median."""

UNIT = "ms"
LAYER = "device programs"
MOVES = "ops_s"


def read(run):
    h = run.deltas.hist("wave", "step_dispatch") if run.deltas else None
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
