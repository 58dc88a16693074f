"""Thread CPU the WAL writers spend per log entry they frame: the WAL
counters ``writer_cpu_ns`` / ``entries``, all three nodes' added
(``Wal._timed_batch``: one ``time.thread_time_ns()`` pair around each
batch, bookkeeping, frame + write + fsync and the written hand-off
included). The writers share the process's interpreter lock with the
step and egress threads: what they burn, a wave waits for. On the chip's
host that clock ticks in 10 ms, so the value is a sum over a window's
batches, never one batch's."""

UNIT = "us"
LAYER = "durability"
MOVES = "ops_s"

COUNTER = "writer_cpu_ns"


def read(run):
    if run.deltas is None or COUNTER not in run.deltas.after["wal"]:
        return None  # a program without the account
    entries = run.deltas.counter("wal", "entries")
    if entries <= 0:
        return None
    return run.deltas.counter("wal", COUNTER) / 1e3 / entries
