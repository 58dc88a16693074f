"""Host time the realising threads spend on rare messages
(``_handle_rares``: where a consistent query is registered and a
heartbeat answered, so a kv cell's read legs are turns of this; also
election timeouts, snapshots, membership), per 1,000 acknowledged
operations: the total of the wave sub-phase ``egress_rare`` (a leaf of
``host_egress``), the three coordinators added. The counter
``rares_handled`` turns it into a time a message."""

UNIT = "ms/kop"
LAYER = "wave loop"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    h = run.deltas.hist("wave", "egress_rare")
    if h is None:
        return None  # a program without the account
    return h.total_ns / 1e6 / (run.acked / 1000.0)
