"""Wave sub-phase ``egress_sync`` (of ``device_step``: ``np.asarray`` of
the step's egress, the host's one true wait for the device), median."""

UNIT = "ms"
LAYER = "device programs"
MOVES = "ops_s"


def read(run):
    h = run.deltas.hist("wave", "egress_sync") if run.deltas else None
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
