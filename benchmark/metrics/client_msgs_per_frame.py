"""Client calls and replies per frame that carries them (the nodes'
client-path counters ``client_calls_out`` + ``client_replies_out`` over
``client_frames_out``, booked by each node's transport once a frame):
what one encode, one MAC and one socket write carry on the client path.
A call leaves its caller's thread as a frame of its own; a leader's
wave sends its replies to one caller node as one frame."""

UNIT = "msgs/frame"
LAYER = "transport"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or "client" not in run.deltas.after:
        return None  # no client node, or a program without the counters
    frames = run.deltas.counter("client", "client_frames_out")
    if frames <= 0:
        return None
    return (run.deltas.counter("client", "client_calls_out")
            + run.deltas.counter("client", "client_replies_out")) / frames
