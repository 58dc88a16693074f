"""Thread CPU time of the wave loop per 1,000 acknowledged operations:
the counters ``cpu_ns_ingress_drain`` + ``cpu_ns_host_pack`` +
``cpu_ns_host_egress`` + ``cpu_ns_aer_fanout`` (``time.thread_time_ns()``
at the boundaries of the wall phases that ``host_ms_per_kop`` adds; the
program reads the clock on one turn in 16 and books it 16 times, so the
counters are estimates of the totals), the three coordinators added.
``host_ms_per_kop`` less this is what the wave threads spent off a core:
interpreter lock, state lock, system calls. On the v5e's host the thread
clock ticks in 10 ms, so over the 5 s traced window two runs differ by a
fifth and more: compare medians of several runs."""

UNIT = "ms/kop"
LAYER = "wave loop"
MOVES = "ops_s"

COUNTERS = ("cpu_ns_ingress_drain", "cpu_ns_host_pack",
            "cpu_ns_host_egress", "cpu_ns_aer_fanout")


def read(run):
    if run.deltas is None or run.acked <= 0:
        return None
    if any(c not in run.deltas.after["coordinator"] for c in COUNTERS):
        return None  # a program without the accounts
    total = sum(run.deltas.counter("coordinator", c) for c in COUNTERS)
    return total / 1e6 / (run.acked / 1000.0)
