"""Host time of the wave loop per 1,000 acknowledged operations: the
totals of the wave phases ``ingress_drain`` + ``host_pack`` +
``host_egress`` + ``aer_fanout`` over the window, the three
coordinators added."""

UNIT = "ms/kop"
LAYER = "wave loop"
MOVES = "ops_s"

PHASES = ("ingress_drain", "host_pack", "host_egress", "aer_fanout")


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    hists = [run.deltas.hist("wave", p) for p in PHASES]
    if any(h is None for h in hists):
        return None
    return sum(h.total_ns for h in hists) / 1e6 / (run.acked / 1000.0)
