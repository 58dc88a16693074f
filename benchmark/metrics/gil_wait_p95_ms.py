"""The wait for the interpreter lock, 95th percentile (wave sub-phase
``gil_wait``, the program's own probe: see ``gil_wait_p50_ms``): the
long turns, which are what a commit's tail is made of when each of its
hops ends in a thread coming back for the lock."""

UNIT = "ms"
LAYER = "wave loop"
MOVES = "commit_p95_ms"


def read(run):
    h = run.deltas.hist("wave", "gil_wait") if run.deltas else None
    p = h.percentile_ns(95) if h else None
    return None if p is None else p / 1e6
