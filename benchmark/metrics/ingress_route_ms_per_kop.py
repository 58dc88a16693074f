"""Host time the step threads spend handing every drained protocol
message to its handler (``_route_one``) and the replies that produced to
the sender, per 1,000 acknowledged operations: the total of the wave
sub-phase ``ingress_route`` (a leaf of ``ingress_drain``, one record per
pass that had messages, from the state lock's own stamp), the three
coordinators added. The counter ``routed_msgs`` turns it into a time a
message."""

UNIT = "ms/kop"
LAYER = "ingress"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    h = run.deltas.hist("wave", "ingress_route")
    if h is None:
        return None  # a program without the account
    return h.total_ns / 1e6 / (run.acked / 1000.0)
