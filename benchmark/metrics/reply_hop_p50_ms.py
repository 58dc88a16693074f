"""Median of histogram ``reply_hop``: a reply's stamp on the leader's
node as its wave hands the replies to one caller node to the send path
-> taken in on the client node, before the caller's future is set (the
sender thread's queue, encode, MAC, socket, decode). Both ends read one
process's monotonic clock, valid because the deployment's nodes share
it."""

UNIT = "ms"
LAYER = "apply + reply"
MOVES = "commit_p95_ms"


def read(run):
    if run.deltas is None or "client_hops" not in run.deltas.after:
        return None
    h = run.deltas.hist("client_hops", "reply_hop")
    p = h.percentile_ns(50) if h else None
    return None if p is None else p / 1e6
