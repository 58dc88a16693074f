"""Device time of the step programs (every ``consensus_step_packed*``
variant) per step run, from the profiler's trace of the traced part of
the window."""

UNIT = "us"
LAYER = "device programs"
MOVES = "ops_s"


def read(run):
    t = run.trace
    if not t or t["step_count"] <= 0:
        return None
    return 1e6 * t["step_seconds"] / t["step_count"]
