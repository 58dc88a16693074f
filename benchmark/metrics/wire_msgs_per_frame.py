"""Protocol messages per batch frame on the wire (the coordinators'
counters ``wire_msgs_out`` / ``wire_frames_out``, booked by the
transport once a frame): what one encode, one MAC and one socket write
carry. 1 would be the per-message wire; a wave's AERs, acks and a tick's
probes to one destination make it tens."""

UNIT = "msgs/frame"
LAYER = "transport"
MOVES = "ops_s"


def read(run):
    if run.deltas is None:
        return None
    if "wire_frames_out" not in run.deltas.after["coordinator"]:
        return None  # a program without the counter
    frames = run.deltas.counter("coordinator", "wire_frames_out")
    if frames <= 0:
        return None  # nothing left a process: not a wired deployment
    return run.deltas.counter("coordinator", "wire_msgs_out") / frames
