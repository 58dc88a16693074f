"""Wave sub-phase ``ticket_queue`` (of ``device_step``: step dispatched ->
the egress thread takes the ticket up; holds the dispatch-time AER
fan-out and the wait in the pipe queue), median."""

UNIT = "ms"
LAYER = "wave loop"
MOVES = "commit_p95_ms"


def read(run):
    h = run.deltas.hist("wave", "ticket_queue") if run.deltas else None
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
