"""Host time the step threads spend putting client commands into the
logs and the WAL queue, per 1,000 acknowledged operations: the total of
the wave sub-phase ``ingest_append`` (a subset of ``ingress_drain``,
one record per pass over all its groups), the three coordinators
added."""

UNIT = "ms/kop"
LAYER = "wave loop"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    h = run.deltas.hist("wave", "ingest_append")
    if h is None:
        return None  # a program without the account
    return h.total_ns / 1e6 / (run.acked / 1000.0)
