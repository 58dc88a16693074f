"""Messages the wire lost (the coordinators' counter ``wire_dropped``: a
full outbox, a blocked pair, a message no frame holds, a failed connect
or write, or shed by the receiving ingress) per 1,000 acknowledged
operations. 0 in a healthy window: every loss is a resend later, which
is latency."""

UNIT = "1/kop"
LAYER = "transport"
MOVES = "commit_p95_ms"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    if "wire_dropped" not in run.deltas.after["coordinator"]:
        return None  # a program without the counter
    if run.deltas.counter("coordinator", "wire_frames_out") <= 0:
        return None  # nothing left a process: not a wired deployment
    return 1000.0 * run.deltas.counter("coordinator", "wire_dropped") \
        / run.acked
