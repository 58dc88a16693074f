"""The wait for the interpreter lock, median: how much later than asked
the program's own probe thread wakes from a 20 ms sleep (wave sub-phase
``gil_wait``; ``ra_tpu/runtime/gil_probe.py``), which is what any thread
of the process pays when it comes back from a call that let go of the
lock (a jitted call, an fsync, a socket, a numpy call). Sampled 50 times
a second; the process's, no one thread's; kernel timer slack included
(0.06-0.25 ms when nothing contends). Stands beside
``step_dispatch_p50_ms``: a dispatch that takes no longer than this is a
turn at the lock, not time inside the call."""

UNIT = "ms"
LAYER = "wave loop"
MOVES = "ops_s"


def read(run):
    h = run.deltas.hist("wave", "gil_wait") if run.deltas else None
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
