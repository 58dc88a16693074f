#!/usr/bin/env python3
"""One cell of the benchmark, traced, with the trace's two tables beside
its result line: ``benchmark/run.py``'s own ``main()`` with ``--trace 1``,
and before the trace is removed ``scripts/idle_gaps.py`` reads it.

    python3 scripts/traced_cell.py --workload <cell> --seed <n> --seconds 40

Read-only, as ``scripts/thread_cpu.py`` is: the cell runs as
``benchmark/run.py`` runs it. ``run_cell`` reduces the trace
(``trace_reduce.reduce_file``) and removes it; this wrapper reads the
same file through ``idle_gaps.read_trace`` just before that reduction
and prints three JSON lines, and a fourth from the run's own deltas, all
before the result line:

- ``{"line": "idle_gaps", ...}``: the device's idle time by the
  program's spans (``idle_gaps.idle_gaps``: rows by span and by thread
  role, per node, largest first);
- ``{"line": "dispatch_edges", ...}``: every step dispatch against the
  run of the step program it started (``idle_gaps.dispatch_edges``): per
  node the median from the call's start to the program's start and from
  the program's end to the call's return. The second, positive, is the
  step thread standing at the interpreter lock with the device done:
  PERF.md sets it beside ``gil_wait_p50_ms``;
- ``{"line": "host_events", ...}``: what lies inside the step dispatches
  on the step thread's own line of ``/host:CPU``: JAX's and PJRT's events
  (``PjitFunction``, ``DevicePut``, the client's ``Execute``...; host
  tracer level 1) and the program's span around the release of the old,
  donated state (``ra/step/host_pack/step_dispatch/release``; a
  collection's ``ra/gc/pause`` where one fell inside): per event name
  the count, the seconds, the median, and the median time from the
  dispatch's start to the event's. Which part of the
  statement the time is in: JAX's call, the release after it, or
  neither;
- ``{"line": "wave_account", ...}``: every wave phase and sub-phase of
  ``obs.WAVE_PHASES`` over the traced window, the coordinators added:
  samples, seconds, and ``mean_ms`` (the deltas the per-layer readers
  read; PERF.md section 5's leaf sums come from it), with the counters
  that turn a leaf's total into a time a message, and the turns whose
  program had finished before their egress sync (``sync_found_ready``,
  beside ``pipeline_steps``).

It runs beside the benchmark and is no code a cell runs."""

import bisect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "scripts")):
    if path not in sys.path:
        sys.path.insert(0, path)


def tables(xplane_path: str) -> list:
    """The three lines read off one trace file."""
    import idle_gaps

    device_ops, host_spans, step_runs = idle_gaps.read_trace(xplane_path)
    gaps = idle_gaps.idle_gaps(device_ops, host_spans) or {}
    return [{"line": "idle_gaps", **gaps},
            {"line": "dispatch_edges",
             **idle_gaps.dispatch_edges(step_runs, host_spans)},
            {"line": "host_events", "inside": idle_gaps.DISPATCH_SPAN,
             "rows": events_inside(xplane_path, idle_gaps.DISPATCH_SPAN)}]


def events_inside(xplane_path: str, span_name: str) -> list:
    """``[[event name, count, seconds, median ms, median ms from the
    span's start to the event's]]``, largest first, of the events on
    ``/host:CPU`` that lie inside a ``span_name`` span on the same line
    (a line is a thread)."""
    import gzip

    import idle_gaps
    from jax.profiler import ProfileData

    opener = gzip.open if xplane_path.endswith(".gz") else open
    with opener(xplane_path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    found = {}
    for plane in data.planes:
        if plane.name != idle_gaps.HOST_PLANE:
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            spans = sorted((lo, hi) for name, lo, hi in events
                           if name == span_name)
            if not spans:
                continue
            starts = [lo for lo, _hi in spans]
            for name, lo, hi in events:
                if name == span_name:
                    continue
                k = bisect.bisect_right(starts, lo) - 1
                if k >= 0 and hi <= spans[k][1]:
                    found.setdefault(name, []).append(
                        (hi - lo, lo - spans[k][0]))
    rows = [[name, len(d), sum(x[0] for x in d) / 1e9,
             idle_gaps.median(x[0] for x in d) / 1e6,
             idle_gaps.median(x[1] for x in d) / 1e6]
            for name, d in found.items()]
    return sorted(rows, key=lambda r: -r[2])


COUNTERS = ("routed_msgs", "follower_aers", "follower_entries",
            "rares_handled", "rares_early", "egress_thread_batches",
            "egress_thread_msgs", "pipeline_steps", "sync_found_ready")


def wave_account(run) -> dict:
    """The window's deltas of every wave histogram the snapshot took."""
    phases = {}
    for name in sorted(run.deltas.after["wave"]):
        h = run.deltas.hist("wave", name)
        phases[name] = {"n": h.n, "s": h.total_ns / 1e9,
                        "mean_ms": h.total_ns / 1e6 / h.n if h.n else None}
    return {"line": "wave_account", "window_s": run.window_s,
            "acked": run.acked, "phases": phases,
            "counters": {c: run.deltas.counter("coordinator", c)
                         for c in COUNTERS}}


def main(argv=None) -> int:
    from benchmark import run as R
    from benchmark import trace_reduce

    argv = list(sys.argv[1:] if argv is None else argv)
    if "--trace" not in argv:
        argv += ["--trace", "1"]
    reduce_file = trace_reduce.reduce_file

    def reduce_and_read(path):
        for line in tables(path):
            print(json.dumps(line), flush=True)
        return reduce_file(path)

    run_cell = R.run_cell

    def run_and_account(*args, **kw):
        run = run_cell(*args, **kw)
        print(json.dumps(wave_account(run)), flush=True)
        return run

    trace_reduce.reduce_file = reduce_and_read
    R.run_cell = run_and_account
    try:
        return R.main(argv)
    finally:
        trace_reduce.reduce_file = reduce_file
        R.run_cell = run_cell


if __name__ == "__main__":
    sys.exit(main())
