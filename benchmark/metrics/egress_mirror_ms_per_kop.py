"""Host time the realising threads spend in the sweep over the groups a
step touched (roles, terms, the meta store, became-leader, term hints)
and handing the step's replies to the sender, less the applies inside
the sweep (those are ``egress_apply_ms_per_kop``), per 1,000
acknowledged operations: the total of the wave sub-phase
``egress_mirror`` (a leaf of ``host_egress``), the three coordinators
added."""

UNIT = "ms/kop"
LAYER = "wave loop"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    h = run.deltas.hist("wave", "egress_mirror")
    if h is None:
        return None  # a program without the account
    return h.total_ns / 1e6 / (run.acked / 1000.0)
