"""A consistent read's quorum round, mean: heartbeats queued -> the reply
issued on a quorum of ``HeartbeatReply`` (a wave on two followers, one
more on the leader); counter ``read_quorum_ns`` over
``read_quorum_rounds``."""

UNIT = "ms"
LAYER = "client entry / read path"
MOVES = "read_p95_ms"


def read(run):
    if run.deltas is None:
        return None
    n = run.deltas.counter("coordinator", "read_quorum_rounds")
    if n <= 0:
        return None
    return run.deltas.counter("coordinator", "read_quorum_ns") / 1e6 / n
