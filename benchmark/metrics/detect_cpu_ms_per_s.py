"""Thread CPU of the detector threads per second of window, the three
nodes' added: the counter ``detector_cpu_ns`` (``coordinator.
_detect_loop``: one ``time.thread_time_ns()`` pair around each pass,
the tick's work, the node liveness reads and the suspicion sweep
included) over the seconds between the two snapshots. The detector is
paid by the second, not by the operation, and shares the process's
interpreter lock with the wave threads: what it burns, a wave waits
for. On the chip's host that clock ticks in 10 ms, so the value is a sum
over a window's passes, never one pass's."""

UNIT = "ms/s"
LAYER = "failure detection"
MOVES = "ops_s"

COUNTER = "detector_cpu_ns"


def read(run):
    if run.deltas is None or run.deltas.seconds <= 0:
        return None
    if COUNTER not in run.deltas.after["coordinator"]:
        return None  # a program without the account
    return run.deltas.counter("coordinator", COUNTER) / 1e6 / run.deltas.seconds
