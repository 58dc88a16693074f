"""What the cyclic garbage collector stops the process for, per 1,000
acknowledged operations: the counter ``gc_pause_ns``
(``ra_tpu/runtime/heap.py``: one ``gc.callbacks`` hook while a
coordinator of the process is started, collection start -> end, every
generation, booked on one coordinator only, because a pause is the
process's and the snapshot adds the coordinators up). Every Python
thread stands still inside a pause: it lies inside every lock wait,
phase and commit stage of the window."""

UNIT = "ms/kop"
LAYER = "wave loop"
MOVES = "ops_s"

COUNTER = "gc_pause_ns"


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    if COUNTER not in run.deltas.after["coordinator"]:
        return None  # a program without the account
    return run.deltas.counter("coordinator", COUNTER) / 1e6 / (run.acked / 1000.0)
