#!/usr/bin/env python3
"""Who is on the core: one cell of the benchmark through
``benchmark/run.py``'s own ``main()``, with every thread's CPU over the
window beside its result line.

    python3 scripts/thread_cpu.py --workload <cell> --seed <n> --seconds 40 --trace 0

Read-only: the cell runs as ``benchmark/run.py`` runs it. The one
addition is a reading of every Python thread's CPU-time clock
(``time.pthread_getcpuclockid``: user + system, in ns) and of the
process's (``time.process_time``) at each of the deployment's two
snapshots, which are the window's start and end (``run_cell``). What
the process burned beyond its Python threads is the row ``not
Python's``: XLA's and PJRT's threads, and Python threads that ended
inside the window. (The same numbers are in ``/proc/self/task/*/stat``,
but a reading of 130 of those files took 12 s on the chip tool's
machine, with the generators running on: PERF.md section 6, PR 31.)
Threads are grouped by role, which is the name less its node or shard.
The table is a JSON line ``{"line": "thread_cpu", ...}`` printed just
before the result line: ``cores`` per role (CPU seconds per second of
window), their share of the process, and the process's total. PERF.md
section 5 rests on it; it runs beside the benchmark and is no code a
cell runs. Linux only."""

import json
import os
import re
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OTHER = "not Python's"

# a thread's role: its name less the node ("ra-batch-det-bench1"), the
# shard or the caller ("fifo-gen-3", "ycsb-17") it was started for
ROLES = (
    (re.compile(r"^ra-batch-det-"), "detector"),
    (re.compile(r"^ra-batch-eg-"), "egress"),
    (re.compile(r"^ra-batch-snd-"), "sender"),
    (re.compile(r"^ra-batch-"), "step"),
    (re.compile(r"^ra-tcp-out-"), "wire writer"),
    (re.compile(r"^ra-tcp-in-"), "wire reader"),
    (re.compile(r"^ra-tcp-(ping|accept)-"), "wire liveness"),
    (re.compile(r"^ra-wal"), "wal writer"),
    (re.compile(r"^ra-segment-writer"), "segment writer"),
    (re.compile(r"^(fifo-gen|bench-gen|ycsb)-"), "generator"),
    (re.compile(r"^MainThread$"), "main"),
)


def role_of(name: str) -> str:
    for pattern, role in ROLES:
        if pattern.search(name):
            return role
    return re.sub(r"[-_/:]*\d+$", "", name) or name


def read_threads() -> dict:
    """``{(ident, name): cpu seconds so far}`` of every Python thread
    alive now, and under ``OTHER`` what the process burned beyond them."""
    out = {}
    for t in threading.enumerate():
        try:
            clock = time.pthread_getcpuclockid(t.ident)
            out[(t.ident, t.name)] = time.clock_gettime(clock)
        except (OSError, OverflowError, TypeError):
            continue  # a thread that ended under the reading
    out[OTHER] = time.process_time() - sum(out.values())
    return out


def table(before: dict, after: dict, seconds: float) -> dict:
    """Cores per role between two readings: a thread that was not there
    at the first counts from zero; one that ended in between, with
    whatever else ran off Python's threads, is under ``OTHER``."""
    roles, threads = {}, {}
    for key, cpu in after.items():
        used = cpu - before.get(key, 0.0)
        role = key if key == OTHER else role_of(key[1])
        roles[role] = roles.get(role, 0.0) + used
        threads[role] = threads.get(role, 0) + (key != OTHER)
    ended = sum(cpu for key, cpu in before.items() if key not in after)
    roles[OTHER] = roles.get(OTHER, 0.0) - ended
    # (the process's clock is read after the threads': the rest can come
    # out a hair under zero, and a thread that slept all through at zero)
    rows = sorted(((r, u) for r, u in roles.items() if u > 0),
                  key=lambda kv: -kv[1])
    total = sum(used for _role, used in rows)
    return {
        "window_s": seconds,
        "process_cores": total / seconds,
        "roles": [
            {"role": role, "threads": threads[role], "cores": used / seconds,
             "share": used / total if total else 0.0}
            for role, used in rows
        ],
    }


def main(argv=None) -> int:
    from benchmark import harness
    from benchmark import run as R

    readings = []
    load_module = harness.load_module

    def load_and_watch(kind, name):
        mod = load_module(kind, name)  # (a fresh module each call)
        if kind == "deployments":
            snapshot = mod.Cluster.snapshot

            def watched(self):
                snap = snapshot(self)
                readings.append((time.monotonic(), read_threads()))
                return snap

            mod.Cluster.snapshot = watched
        return mod

    harness.load_module = load_and_watch
    result_line = R.result_line

    def result_and_table(bench, run, trace):
        if len(readings) >= 2:
            (t0, before), (t1, after) = readings[0], readings[-1]
            print(json.dumps({"line": "thread_cpu",
                              **table(before, after, t1 - t0)}), flush=True)
        return result_line(bench, run, trace)

    R.result_line = result_and_table
    return R.main(argv)


if __name__ == "__main__":
    sys.exit(main())
