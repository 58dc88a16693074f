"""Commands the ingress refused (admission window, no space, a full
ingress ring) per 1,000 operations attempted in the window."""

UNIT = "1/kop"
LAYER = "ingress"
MOVES = "commit_p95_ms"


def read(run):
    attempted = run.acked + run.failed
    if run.deltas is None or attempted <= 0:
        return None
    refused = sum(run.deltas.counter("coordinator", c) for c in (
        "commands_rejected", "commands_rejected_nospace",
        "commands_dropped_overload", "ingress_ring_full"))
    return 1000.0 * refused / attempted
