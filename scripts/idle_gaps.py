"""idle_gaps: the device's idle time, by what the host was doing.

From a profiler trace (``*.xplane.pb``, as ``api.profile`` and the
benchmark's traced runs write it): the device's idle intervals (the
complement of the union of the ``XLA Ops`` events on ``/device:TPU:0``
inside the traced window), each cut by the program's ``ra/...`` spans on
plane ``/host:CPU`` that overlap it, summed by span name per node, with
the share that no span covers (docs/INTERNALS.md, "Spans in the
profiler's trace").

Threads run side by side, so the rows add up to more than the idle
time: a row says for how much of the idle time SOME thread of that node
was inside that span; a child span (``ra/step/host_pack/mailbox_build``)
is also inside its parent's row. ``roles`` adds the spans up by thread
role (the second part of the name) per node: a client is inside a call
nearly always, so the share that the step thread's spans cover is the
one that says whether the wave loop's time is accounted for.

``idle_gaps()`` is a pure function on plain tuples, as
``benchmark/trace_reduce.reduce_planes`` is, so that the benchmark's
``breakdown.idle_gaps`` can call it.

``dispatch_edges()``, on the same tuples, lays every step dispatch
(span ``ra/step/host_pack/step_dispatch``) against the run of the step
program it started (``XLA Modules`` on the same device plane): how long
from the call's start to the program's start, and from the program's
end to the call's return. The second, where it is positive, is the step
thread standing at the interpreter lock with the device already done
(``scripts/traced_cell.py`` prints both tables for one cell).

Usage:
    python scripts/idle_gaps.py <trace.xplane.pb[.gz]> [--top 30] [--json]
"""
import argparse
import bisect
import gzip
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_PROGRAM = "consensus_step_packed"  # every step variant's name holds it
SPAN_PREFIX = "ra/"
DISPATCH_SPAN = "ra/step/host_pack/step_dispatch"
# a span that was opened at its end and says how far back it reaches
# (ra_tpu/runtime/gil_probe.py: an annotation cannot be opened in the past)
BACKDATED_BY = "wait_ns"


def merge(intervals):
    """Sorted, disjoint union of (start, end) pairs."""
    out = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return out


def complement(busy, lo, hi):
    """The parts of [lo, hi) that the disjoint sorted ``busy`` leaves."""
    out, at = [], lo
    for b_lo, b_hi in busy:
        if b_lo > at:
            out.append((at, min(b_lo, hi)))
        at = max(at, b_hi)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return out


def overlap(gaps, starts, spans) -> int:
    """Total length of the disjoint sorted ``gaps`` (``starts``: their
    starts) that the disjoint sorted ``spans`` cover."""
    total = 0
    for lo, hi in spans:
        k = max(0, bisect.bisect_right(starts, lo) - 1)
        while k < len(gaps) and gaps[k][0] < hi:
            total += max(0, min(hi, gaps[k][1]) - max(lo, gaps[k][0]))
            k += 1
    return total


def idle_gaps(device_ops, host_spans, window=None) -> dict:
    """``device_ops``: [(start_ns, end_ns)] of one device's operations;
    ``host_spans``: [(name, node, start_ns, end_ns)]; ``window``:
    (start_ns, end_ns), by default from the first to the last thing in
    the trace. Returns the idle seconds, the seconds of them inside any
    span (``covered_s``) and inside none, ``rows``: [(name, node,
    seconds of idle time with a thread of ``node`` inside ``name``)],
    largest first, and ``roles``: the same by thread role (``step`` of
    ``ra/step/...``) per node."""
    if window is None:
        edges = [t for lo, hi in device_ops for t in (lo, hi)] + \
                [t for _n, _d, lo, hi in host_spans for t in (lo, hi)]
        if not edges:
            return None
        window = (min(edges), max(edges))
    gaps = complement(merge(device_ops), *window)
    starts = [g[0] for g in gaps]
    idle_ns = sum(hi - lo for lo, hi in gaps)
    by_key, by_role = {}, {}
    for name, node, lo, hi in host_spans:
        by_key.setdefault((name, node), []).append((lo, hi))
        by_role.setdefault((name.split("/")[1], node), []).append((lo, hi))

    def summed(groups):
        got = ((key, node, overlap(gaps, starts, merge(spans)) / 1e9)
               for (key, node), spans in groups.items())
        return sorted((r for r in got if r[2] > 0), key=lambda r: -r[2])

    covered_ns = overlap(gaps, starts, merge(
        (lo, hi) for _n, _d, lo, hi in host_spans))
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "idle_s": idle_ns / 1e9,
        "covered_s": covered_ns / 1e9,
        "uncovered_s": (idle_ns - covered_ns) / 1e9,
        "rows": summed(by_key),
        "roles": summed(by_role),
    }


def median(values):
    v = sorted(values)
    return None if not v else (v[(len(v) - 1) // 2] + v[len(v) // 2]) / 2


def dispatch_edges(step_runs, host_spans) -> dict:
    """``step_runs``: [(start_ns, end_ns)] of the step programs' runs on
    one device; ``host_spans`` as for :func:`idle_gaps`. Every
    ``DISPATCH_SPAN``, in order of its start, takes the first run not
    yet taken that begins inside or after it (a run belongs to one
    dispatch; the nodes of a process share the device). Per node: the
    dispatches matched, the medians of call start -> program start
    (``to_start_ms``) and of program end -> call return
    (``after_end_ms``: negative where the call returned while the
    program still ran), and ``ended_before_return``: the share of steps
    whose program had ended before the call returned."""
    runs = sorted(step_runs)
    spans = sorted((lo, hi, node) for name, node, lo, hi in host_spans
                   if name == DISPATCH_SPAN)
    by_node, k = {}, 0
    for lo, hi, node in spans:
        while k < len(runs) and runs[k][0] < lo:
            k += 1
        if k == len(runs):
            break
        run_lo, run_hi = runs[k]
        k += 1
        by_node.setdefault(node, []).append((run_lo - lo, hi - run_hi))
    return {
        "dispatches": len(spans), "step_runs": len(runs),
        "rows": [
            {"node": node, "steps": len(edges),
             "to_start_ms": median(e[0] for e in edges) / 1e6,
             "after_end_ms": median(e[1] for e in edges) / 1e6,
             "ended_before_return":
                 sum(e[1] > 0 for e in edges) / len(edges)}
            for node, edges in sorted(by_node.items())
        ],
    }


def read_trace(path: str):
    """``(device_ops, host_spans, step_runs)`` of the first TPU plane and
    the host plane of an ``xplane.pb`` (or one gzipped); ``step_runs``:
    the runs of the step programs on that plane, for
    :func:`dispatch_edges`."""
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    device_ops, host_spans, step_runs = [], [], []
    device = min((p.name for p in data.planes if DEVICE_PLANE.match(p.name)),
                 default=None)
    for plane in data.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops += [(e.start_ns, e.start_ns + e.duration_ns)
                                   for e in line.events]
                elif line.name == MODULES_LINE:
                    step_runs += [(e.start_ns, e.start_ns + e.duration_ns)
                                  for e in line.events
                                  if STEP_PROGRAM in e.name]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        stats = dict(e.stats)
                        lo = e.start_ns - int(stats.get(BACKDATED_BY, 0))
                        host_spans.append((e.name, str(stats.get("node", "?")),
                                           lo, e.start_ns + e.duration_ns))
    return device_ops, host_spans, step_runs


def render(got: dict, top: int) -> str:
    idle = got["idle_s"] or float("nan")
    out = [
        f"window {got['window_s']:.3f} s, device idle {got['idle_s']:.3f} s "
        f"({100 * got['idle_s'] / got['window_s']:.1f} %); inside a span "
        f"{100 * got['covered_s'] / idle:.1f} %, inside none "
        f"{100 * got['uncovered_s'] / idle:.1f} %",
        "",
        "| span | node | idle s inside it | share of idle |",
        "|---|---|---|---|",
    ]
    for name, node, s in got["rows"][:top]:
        out.append(f"| `{name}` | {node} | {s:.3f} | {100 * s / idle:.1f} % |")
    out += ["",
            "| thread role | node | idle s inside its spans | share of idle |",
            "|---|---|---|---|"]
    for role, node, s in got["roles"]:
        out.append(f"| {role} | {node} | {s:.3f} | {100 * s / idle:.1f} % |")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    device_ops, host_spans, _step_runs = read_trace(args.trace)
    if not device_ops:
        print("idle_gaps: no operation on a TPU plane in this trace",
              file=sys.stderr)
        return 1
    got = idle_gaps(device_ops, host_spans)
    print(json.dumps(got) if args.json else render(got, args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
