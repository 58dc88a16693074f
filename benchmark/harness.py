"""What every cell shares: finding a cell's files by name, compile
counting, deltas of the program's counters and histograms over the
window, and the record (``Run``) the metric readers read."""

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# -- files, by name -----------------------------------------------------------


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                     f"(has: {[c['name'] for c in bench['workloads']]})")


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, imported by path: a metric's name
    may hold dots and dashes, which no import statement takes."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"benchmark: {kind}/{name}.py is missing")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, section: str, cell_name: str) -> List[dict]:
    """The metrics of ``section`` that ``cell_name`` reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]


# -- compilations ---------------------------------------------------------------


class CompileStats:
    """Compilations and compile seconds as JAX itself reports them (a
    persistent-cache hit counts as a compilation whose seconds are the
    retrieval time). Copy of ``chip_smoke.CompileStats``."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return (self.n, self.seconds)

    def since(self, mark=(0, 0.0)) -> dict:
        return {"compilations": self.n - mark[0],
                "compile_seconds": self.seconds - mark[1]}


# -- histograms of the program (ra_tpu.obs.LogHistogram), as deltas ----------------

SUB_BITS = 5
SUB_BUCKETS = 1 << SUB_BITS


def bucket_bounds(b: int):
    """Inclusive [lo, hi] of bucket ``b`` of the program's log-bucketed
    histograms (the layout of ``ra_tpu.obs.bucket_of``; the benchmark's
    tests hold this copy to the original)."""
    if b < SUB_BUCKETS:
        return b, b
    shift = (b >> SUB_BITS) - 1
    lo = ((b & (SUB_BUCKETS - 1)) + SUB_BUCKETS) << shift
    return lo, lo + (1 << shift) - 1


class HistDelta:
    """What one histogram recorded between two snapshots (nanoseconds)."""

    def __init__(self, before, after):
        zero = (np.zeros_like(after[0]), 0, 0)
        before = before or zero
        self.counts = after[0] - before[0]
        self.n = int(after[1] - before[1])
        self.total_ns = int(after[2] - before[2])

    def percentile_ns(self, p: float) -> Optional[float]:
        total = int(self.counts.sum())
        if total <= 0:
            return None
        cum = np.cumsum(self.counts)
        rank = max(1, min(total, int(np.ceil(p / 100.0 * total))))
        lo, hi = bucket_bounds(int(np.searchsorted(cum, rank)))
        return (lo + hi) / 2.0


class Deltas:
    """The window's deltas of two ``Cluster.snapshot()``s."""

    def __init__(self, before: dict, after: dict):
        self.before, self.after = before, after
        self.seconds = after["t"] - before["t"]

    def scalar(self, key: str) -> int:
        return self.after[key] - self.before[key]

    def counter(self, family: str, name: str) -> int:
        return self.after[family].get(name, 0) - self.before[family].get(name, 0)

    def hist(self, family: str, name: str) -> Optional[HistDelta]:
        after = self.after[family].get(name)
        if after is None:
            return None
        return HistDelta(self.before[family].get(name), after)


# -- latencies --------------------------------------------------------------------


def percentile(values, p: float) -> Optional[float]:
    """The ``p``-th percentile by nearest rank; None of nothing."""
    if len(values) == 0:
        return None
    v = np.sort(np.asarray(values))
    rank = max(1, min(len(v), int(np.ceil(p / 100.0 * len(v)))))
    return float(v[rank - 1])


@dataclass
class OpWindow:
    """One kind of operation inside the window: latencies of those
    acknowledged in it (ns), and how many failed in it."""

    lat_ns: np.ndarray
    failed: int

    @property
    def acked(self) -> int:
        return int(len(self.lat_ns))

    def p_ms(self, p: float) -> Optional[float]:
        got = percentile(self.lat_ns, p)
        return None if got is None else got / 1e6


def op_window(op: dict, t0_ns: int, t1_ns: int) -> OpWindow:
    """``op`` holds ``t_send``/``t_done`` (ns) and ``ok`` per operation;
    an operation belongs to the window in which it ended."""
    t_send = np.asarray(op["t_send"], np.int64)
    t_done = np.asarray(op["t_done"], np.int64)
    ok = np.asarray(op["ok"], bool)
    inside = (t_done >= t0_ns) & (t_done < t1_ns)
    return OpWindow((t_done - t_send)[inside & ok], int((inside & ~ok).sum()))


# -- the record the metric readers read ----------------------------------------------


@dataclass
class Run:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    window_s: float = 0.0
    setup_s: float = 0.0
    ops: Dict[str, OpWindow] = field(default_factory=dict)
    issued: Dict[str, int] = field(default_factory=dict)  # generator's counts, window
    deltas: Optional[Deltas] = None
    events: List[dict] = field(default_factory=list)  # flight recorder, window
    trace: Optional[dict] = None  # trace_reduce.reduce() of the traced part
    device: Dict[str, Any] = field(default_factory=dict)
    step_bytes: Optional[int] = None  # of one full-width step, from shapes
    history: Optional[dict] = None
    observed: Optional[dict] = None
    violations: List[str] = field(default_factory=list)

    @property
    def acked(self) -> int:
        return sum(o.acked for o in self.ops.values())

    @property
    def failed(self) -> int:
        return sum(o.failed for o in self.ops.values())

    def acked_of(self, kind: str) -> int:
        return self.ops[kind].acked if kind in self.ops else 0
