#!/usr/bin/env python3
"""How steady a cell's window is: one cell of the benchmark through
``benchmark/run.py``'s own ``main()``, with a reading a second of the
coordinators' step counts and a few counters, and the acknowledged
operations bucketed by the second they ended in.

    python3 scripts/window_series.py --workload <cell> --seed <n> --seconds 40 --trace 0

Read-only, like ``scripts/thread_cpu.py``: the cell runs as
``benchmark/run.py`` runs it; a sampler thread wakes once a second
between the deployment's two snapshots (the window's start and end).
The series is a JSON line ``{"line": "window_series", ...}`` printed
just before the result line: per second ``ops`` (acknowledged
operations that ended in it), ``steps`` per coordinator, the deltas
of ``COUNTERS`` and the host time inside the wave ``PHASES``, summed
over the coordinators. A run whose ``ops`` series
holds a dip names the second to look at; runs that differ only in their
level have no stall to mend. PERF.md section 6 (PR 33) rests on it."""

import bisect
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

COUNTERS = ("gc_pause_ns", "gc_collections", "gc_full_collections",
            "detector_passes", "detector_cpu_ns", "wire_frames_out",
            "wire_msgs_out", "wire_frames_in", "wire_dropped")
WAL = ("fsyncs", "entries")
PHASES = ("ingress_drain", "host_pack", "device_step", "host_egress")


class Sampler(threading.Thread):
    def __init__(self, cluster):
        super().__init__(name="window-series", daemon=True)
        self.cluster = cluster
        self.rows = []
        self.done = threading.Event()
        # a reading and the last one exclude each other: a wake whose
        # wait ran out just as the window ended reads nothing after it
        self._lock = threading.Lock()
        self._closed = False

    def read(self, last=False):
        with self._lock:
            if self._closed:
                return
            self._closed = last
            row = {"t_ns": time.monotonic_ns(),
                   "steps": [c.steps for c in self.cluster.coords]}
            for k in COUNTERS:
                row[k] = sum(c.counters.to_dict().get(k, 0)
                             for c in self.cluster.coords)
            for k in WAL:
                row[k] = sum(w.counter.to_dict().get(k, 0)
                             for _t, w, _sw, _d in self.cluster.storage)
            hists = self.cluster._obs.histograms()
            for k in PHASES:  # host ns inside the wave phase so far
                hs = [hists.fetch(("wave", c.name, k))
                      for c in self.cluster.coords]
                row[k] = sum(h.total for h in hs if h is not None)
            self.rows.append(row)

    def run(self):
        while not self.done.wait(1.0):
            self.read()


def series(rows, done_ns):
    """Deltas between consecutive readings, and the operations that
    ended between them."""
    out = []
    done_ns = sorted(done_ns)
    for a, b in zip(rows, rows[1:]):
        row = {"s": round((b["t_ns"] - a["t_ns"]) / 1e9, 3),
               "ops": bisect.bisect_left(done_ns, b["t_ns"])
               - bisect.bisect_left(done_ns, a["t_ns"]),
               "steps": [y - x for x, y in zip(a["steps"], b["steps"])]}
        for k in COUNTERS + WAL:
            row[k] = b[k] - a[k]
        for k in PHASES:
            row[k + "_ms"] = round((b[k] - a[k]) / 1e6, 1)
        row["gc_pause_ms"] = round(row.pop("gc_pause_ns") / 1e6, 1)
        row["detector_cpu_ms"] = round(row.pop("detector_cpu_ns") / 1e6, 1)
        out.append(row)
    return out


def main(argv=None) -> int:
    from benchmark import harness
    from benchmark import run as R

    samplers = []
    inside = []  # a deployment's snapshot() calling its base class's
    load_module = harness.load_module

    def load_and_watch(kind, name):
        mod = load_module(kind, name)
        # (wired_cluster inherits batch_cluster's, loaded through here too)
        if kind == "deployments" and "snapshot" in vars(mod.Cluster):
            snapshot = mod.Cluster.snapshot

            def watched(self):
                # the first and last readings are taken here, on the
                # caller's thread, next to the window's own clock reads
                # (and laid on those below: result_and_series)
                if inside:
                    return snapshot(self)
                inside.append(self)
                try:
                    if not samplers:
                        snap = snapshot(self)
                        samplers.append(Sampler(self))
                        samplers[0].read()
                        samplers[0].start()
                        return snap
                    sampler = samplers[0]
                    sampler.done.set()
                    sampler.read(last=True)
                    sampler.join()
                    return snapshot(self)
                finally:
                    inside.pop()

            mod.Cluster.snapshot = watched
        return mod

    harness.load_module = load_and_watch
    result_line = R.result_line
    # the window's own edges, as run_cell hands them to op_window: the
    # series' first and last readings are laid on them, so that its
    # operations are the window's (a reading is taken a few
    # microseconds from its edge, and more when the interpreter lock
    # is handed to another thread in between)
    edges = []
    op_window = harness.op_window

    def op_window_at(op, t0_ns, t1_ns):
        edges[:] = [t0_ns, t1_ns]
        return op_window(op, t0_ns, t1_ns)

    def result_and_series(bench, run, trace):
        if samplers:
            rows = samplers[0].rows
            if edges:
                rows[0]["t_ns"], rows[-1]["t_ns"] = edges
            done = [t for op in run.history["ops"].values()
                    for t, ok in zip(op["t_done"], op["ok"]) if ok]
            print(json.dumps({"line": "window_series",
                              "rows": series(rows, done)}),
                  flush=True)
        return result_line(bench, run, trace)

    R.result_line = result_and_series
    harness.op_window = op_window_at
    try:
        return R.main(argv)
    finally:
        harness.op_window = op_window


if __name__ == "__main__":
    sys.exit(main())
