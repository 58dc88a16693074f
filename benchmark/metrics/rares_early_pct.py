"""The share of rare messages that the ingest pass which routed them
handled, before it packed and dispatched: 100 x ``rares_early`` /
(``rares_early`` + ``rares_handled``), the coordinators added. A read's
consistent query, the followers' heartbeats and the leader's heartbeat
replies are handled there while no step of the coordinator is
unrealised; every other rare message (elections, snapshots, membership,
probes) is handled as the pass's step is realised (``egress_rare``)."""

UNIT = "%"
LAYER = "client entry / read path"
MOVES = "ops_s"


def read(run):
    if run.deltas is None:
        return None
    if "rares_early" not in run.deltas.after["coordinator"]:
        return None  # a program that handles every rare at realisation
    early = run.deltas.counter("coordinator", "rares_early")
    total = early + run.deltas.counter("coordinator", "rares_handled")
    if total <= 0:
        return None
    return 100.0 * early / total
