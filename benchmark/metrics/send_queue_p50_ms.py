"""A batch's wait for the sender thread, median: from its publish into
the sender's ring (``_send_batch``, on the step, egress or WAL thread) to
the sender thread's drain of it (wave sub-phase ``send_queue``, one
sample a batch). Every hop of a commit's round that leaves a node stands
in this queue once; an inline send waits for nobody and records
none."""

UNIT = "ms"
LAYER = "wave loop"
MOVES = "commit_p95_ms"


def read(run):
    h = run.deltas.hist("wave", "send_queue") if run.deltas else None
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
