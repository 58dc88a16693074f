"""Host time the realising threads spend in the loop over a step's
consumed messages, per 1,000 acknowledged operations: vote and
AppendEntries replies and, on a follower, the log write and the ack of
every AppendEntries (``_host_write_entries`` + ``_ack_aer``). The total
of the wave sub-phase ``egress_follow`` (a leaf of ``host_egress``, from
the phase's start to the end of the loop), the three coordinators added.
The counters ``follower_aers`` and ``follower_entries`` turn it into a
time a message."""

UNIT = "ms/kop"
LAYER = "wave loop"
MOVES = "ops_s"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    h = run.deltas.hist("wave", "egress_follow")
    if h is None:
        return None  # a program without the account
    return h.total_ns / 1e6 / (run.acked / 1000.0)
