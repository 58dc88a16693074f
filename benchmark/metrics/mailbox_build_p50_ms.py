"""Wave sub-phase ``mailbox_build`` (of ``host_pack``: packing the step's
mailbox on the host), median over the window's steps."""

UNIT = "ms"
LAYER = "wave loop"
MOVES = "ops_s"


def read(run):
    h = run.deltas.hist("wave", "mailbox_build") if run.deltas else None
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
