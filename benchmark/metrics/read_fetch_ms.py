"""``kv_get``'s log fetch, mean: the ``state_query``'s future is born -> its
reply issued (one more wave on the leader); counter ``state_query_ns``
over ``state_queries``."""

UNIT = "ms"
LAYER = "client entry / read path"
MOVES = "read_p95_ms"


def read(run):
    if run.deltas is None:
        return None
    n = run.deltas.counter("coordinator", "state_queries")
    if n <= 0:
        return None
    return run.deltas.counter("coordinator", "state_query_ns") / 1e6 / n
