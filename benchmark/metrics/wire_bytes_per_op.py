"""Bytes the nodes wrote to their sockets in batch frames (the
coordinators' counter ``wire_bytes_out``, length prefix and MAC
included) per acknowledged operation: an enqueue's 1 KB body goes to two
followers, a settle is small, so about 2 x 1 KB x the share of enqueues
plus the messages' framing."""

UNIT = "bytes/op"
LAYER = "transport"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    if "wire_bytes_out" not in run.deltas.after["coordinator"]:
        return None  # a program without the counter
    if run.deltas.counter("coordinator", "wire_frames_out") <= 0:
        return None  # nothing left a process: not a wired deployment
    return run.deltas.counter("coordinator", "wire_bytes_out") / run.acked
