"""Median of histogram ``client_hop``: a call's stamp on the client node
before it is encoded -> handed to the leader's node's ingress (encode,
MAC, socket, the reader thread's decode). Both ends read one process's
monotonic clock, valid because the deployment's nodes share it."""

UNIT = "ms"
LAYER = "transport"
MOVES = "commit_p95_ms"


def read(run):
    if run.deltas is None or "client_hops" not in run.deltas.after:
        return None
    h = run.deltas.hist("client_hops", "client_hop")
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
