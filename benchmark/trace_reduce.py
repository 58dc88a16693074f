"""From a profiler trace (``*.xplane.pb``, read with
``jax.profiler.ProfileData``) to the numbers the benchmark reports:
the seconds in which an operation ran on the device, the device time of
each program and of the step programs, the operations that took most
time, and the idle gaps by the programs that bound them.

A TPU's plane is ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one
event per device operation and ``XLA Modules`` one per program run
(``jit_<name>(<fingerprint>)``). Busy time is the union of the
operations' intervals (of the programs' where a trace has no operation
line), averaged over the device planes that ran anything.

Checked by ``tests/test_trace_reduce.py`` against the small trace
recorded beside it on a TPU v5e.
"""

import bisect
import glob
import gzip
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_PROGRAM = "consensus_step_packed"  # every step variant's name holds it
FULL_WIDTH_STEP = re.compile(r"consensus_step_packed(_scat)?_impl$")
TOP = 10


def find_xplane(trace_dir: str) -> str:
    got = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not got:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return got[-1]


def program_name(event_name: str) -> str:
    """``jit__consensus_step_packed_scat_impl(123)`` -> the jitted
    function's name."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """An operation's event name is its whole HLO line (``%fusion.28 =
    pred[256]{...} fusion(...)``): keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) pairs, in their unit."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def reduce_planes(planes) -> dict:
    """``planes``: [(plane name, {line name: [(event name, start_ns,
    end_ns)]})]. None when no operation ran on a device."""
    busy, ops, programs, gaps = [], {}, {}, {}
    for name, lines in planes:
        if not DEVICE_PLANE.match(name):
            continue
        modules = lines.get(MODULES_LINE, [])
        work = lines.get(OPS_LINE) or modules
        if not work:
            continue
        busy.append(union_seconds((lo, hi) for _n, lo, hi in work) / 1e9)
        modules = sorted(modules, key=lambda e: e[1])
        starts = [m[1] for m in modules]
        for op, lo, hi in lines.get(OPS_LINE, []):
            # an instruction's name is unique only inside its program:
            # the program is the one running when the operation starts
            k = bisect.bisect_right(starts, lo) - 1
            inside = k >= 0 and lo < modules[k][2]
            key = (f"{program_name(modules[k][0]) if inside else '?'}"
                   f"/{op_name(op)}")
            ops[key] = ops.get(key, 0.0) + (hi - lo) / 1e9
        prev_name, prev_end = None, None
        for ev, lo, hi in modules:
            prog = program_name(ev)
            n, s = programs.get(prog, (0, 0.0))
            programs[prog] = (n + 1, s + (hi - lo) / 1e9)
            if prev_end is not None and lo > prev_end:
                key = f"{prev_name} -> {prog}"
                gaps[key] = gaps.get(key, 0.0) + (lo - prev_end) / 1e9
            prev_name, prev_end = prog, max(hi, prev_end or hi)
    if not busy or not sum(busy) > 0:
        return None
    steps = [(n, s) for p, (n, s) in programs.items() if STEP_PROGRAM in p]
    full = [(n, s) for p, (n, s) in programs.items() if FULL_WIDTH_STEP.search(p)]

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "devices": len(busy),
        "busy_s": sum(busy) / len(busy),
        "programs": {p: {"count": n, "seconds": s}
                     for p, (n, s) in programs.items()},
        "step_count": sum(n for n, _s in steps),
        "step_seconds": sum(s for _n, s in steps),
        "full_step_count": sum(n for n, _s in full),
        "full_step_seconds": sum(s for _n, s in full),
        "device_ops": top(ops) or top({p: s for p, (_n, s) in programs.items()}),
        "idle_gaps": top(gaps),
    }


def _profile(path: str):
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def reduce_file(path: str) -> dict:
    """``path``: an ``*.xplane.pb``, or one gzipped."""
    data = _profile(path)
    return reduce_planes([
        (plane.name, {line.name: _events(line) for line in plane.lines})
        for plane in data.planes if DEVICE_PLANE.match(plane.name)
    ])

