"""Host time the realising threads spend on machine effects (deliveries,
monitors, release cursors), per 1,000 acknowledged operations: the total
of the wave sub-phase ``effects_realise`` (a subset of ``host_egress``,
one record per step that realised any), the three coordinators added."""

UNIT = "ms/kop"
LAYER = "apply + reply"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    h = run.deltas.hist("wave", "effects_realise")
    if h is None:
        return None  # a program without the account
    return h.total_ns / 1e6 / (run.acked / 1000.0)
