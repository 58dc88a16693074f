"""Host time the step threads spend popping the ingress lanes and
classifying the burst, outside the state lock, per 1,000 acknowledged
operations: the total of the wave sub-phase ``ingress_classify`` (a leaf
of ``ingress_drain``, recorded where it is), the three coordinators
added."""

UNIT = "ms/kop"
LAYER = "ingress"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    h = run.deltas.hist("wave", "ingress_classify")
    if h is None:
        return None  # a program without the account
    return h.total_ns / 1e6 / (run.acked / 1000.0)
