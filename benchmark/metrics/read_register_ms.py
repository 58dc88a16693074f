"""A consistent read's first leg, mean: the caller's future is born -> the
leader has queued the query's heartbeats (ingress ring, the step's wave,
the rare path on the egress thread); counter ``read_register_ns`` over
``read_registers``. A mean and not a median: the three legs' means add up
to the read's mean."""

UNIT = "ms"
LAYER = "client entry / read path"
MOVES = "read_p95_ms"


def read(run):
    if run.deltas is None:
        return None
    n = run.deltas.counter("coordinator", "read_registers")
    if n <= 0:
        return None
    return run.deltas.counter("coordinator", "read_register_ns") / 1e6 / n
