"""The share of consistent reads that paid a quorum round: 100 x (1 -
``read_lease_served`` / consistent reads the generator ended in the
window). (``read_quorum_fallback`` counts only when a lease is
configured, so it cannot be the source.)"""

UNIT = "%"
LAYER = "client entry / read path"
MOVES = "read_p95_ms"


def read(run):
    reads = run.issued.get("consistent_reads", 0)
    if run.deltas is None or reads <= 0:
        return None
    served = run.deltas.counter("coordinator", "read_lease_served")
    return 100.0 * (1.0 - served / reads)
