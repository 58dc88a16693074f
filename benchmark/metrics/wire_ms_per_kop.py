"""Host milliseconds on the wire per 1,000 acknowledged operations: the
coordinators' counters ``wire_encode_ns`` (building a batch's list,
encoding and sealing it: one clock pair a ``send_batch``) and
``wire_decode_ns`` (a batch frame's MAC check, its restricted decode and
``ingest_batch``: one clock pair a frame), the three nodes added. Wall
time of the sender and reader threads, waits for the interpreter lock
included."""

UNIT = "ms/kop"
LAYER = "transport"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    if "wire_encode_ns" not in run.deltas.after["coordinator"]:
        return None  # a program without the counter
    if run.deltas.counter("coordinator", "wire_frames_out") <= 0:
        return None  # nothing left a process: not a wired deployment
    ns = run.deltas.counter("coordinator", "wire_encode_ns") \
        + run.deltas.counter("coordinator", "wire_decode_ns")
    return ns / 1e6 * 1000.0 / run.acked
