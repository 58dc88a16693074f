"""Wave phase ``device_step``, median: the host's clock around the step
dispatch and the egress sync, not the device's time."""

UNIT = "ms"
LAYER = "device programs"
MOVES = "ops_s"


def read(run):
    h = run.deltas.hist("wave", "device_step") if run.deltas else None
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
