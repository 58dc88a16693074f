"""Observability: log-bucketed histograms, flight recorder, exposition.

The measurement layer for ROADMAP item 2 ("publish a top-5 cost table"):
before any commit/read optimization ports (arxiv 1905.10786), the wave
loop and the commit path need per-phase timing and a post-mortem trace.
Four primitives, all safe on the hot path:

- :class:`LogHistogram` — HdrHistogram-style log-bucketed latency
  histogram (power-of-two octaves with linear sub-buckets, int64 numpy
  slots, same single-writer discipline as ``ra_tpu.counters.Counters``).
  Relative quantile error is bounded by ``1/SUB_BUCKETS`` (~3.1%).
  Values are recorded in NANOSECONDS; exports convert.

- :class:`FlightRecorder` — bounded ring buffer of structured events
  (role changes, elections, depositions, snapshot installs, watchdog
  strikes, admission rejects, failpoint fires, WAL failures, health
  transitions, phi suspect/unsuspect flips) with
  monotonic timestamps, group id and term. Appends are lock-free
  (CPython: slot assignment is atomic; sequence numbers come from an
  ``itertools.count``, whose ``next`` is atomic), so any thread —
  detector, WAL writer, step loop — may record. Reads are best-effort
  snapshots, exactly like counter reads.

- :func:`span` — a host span in the JAX profiler's trace, on the same
  clock as the device's operations; inert while no profiler session
  runs. The wave-phase and commit-stage histograms are the accounts
  that are always on; the spans are their timeline.

- exposition — ``prometheus_text()`` renders every registered counter
  (with the kind/help from its field specs) and histogram (as a summary
  with p50/p90/p99/p99.9 quantiles in seconds) in Prometheus text
  format; ``api.system_overview`` bundles the same data as one dict
  (parity with the reference's ``ra:overview/1`` over seshat counters).

The reference keeps this layer in ``ra_counters``/seshat plus the
per-server overview (``src/ra.erl`` overview/1); a TPU-batched hot path
additionally needs distributions (one smoothed gauge cannot answer
"where do 92.5 ms go") and a wave-phase breakdown, recorded here.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# log-bucketed histogram

SUB_BITS = 5
SUB_BUCKETS = 1 << SUB_BITS  # linear sub-buckets per power-of-two octave
# enough buckets for any int64 nanosecond value (shift <= 63 - SUB_BITS)
N_BUCKETS = ((64 - SUB_BITS) << SUB_BITS) + SUB_BUCKETS


def bucket_of(v: int) -> int:
    """Bucket index for a non-negative int. Buckets are exact below
    ``SUB_BUCKETS`` and cover ``[lo, lo + 2**shift)`` ranges above, with
    ``SUB_BUCKETS`` linear sub-buckets per octave (HdrHistogram
    bucketing; max relative error 1/SUB_BUCKETS)."""
    if v < SUB_BUCKETS:
        return v if v >= 0 else 0
    shift = v.bit_length() - 1 - SUB_BITS
    b = ((shift + 1) << SUB_BITS) + ((v >> shift) - SUB_BUCKETS)
    return b if b < N_BUCKETS else N_BUCKETS - 1


def bucket_bounds(b: int) -> Tuple[int, int]:
    """Inclusive [lo, hi] value range of bucket ``b`` (inverse of
    :func:`bucket_of`)."""
    if b < SUB_BUCKETS:
        return b, b
    shift = (b >> SUB_BITS) - 1
    lo = ((b & (SUB_BUCKETS - 1)) + SUB_BUCKETS) << shift
    return lo, lo + (1 << shift) - 1


class LogHistogram:
    """Lock-free log-bucketed histogram (single-writer slots, like
    ``Counters``; readers may see slightly stale values). Records
    non-negative integers — by convention nanoseconds.

    ``locked=True`` adds a writer lock for histograms shared by
    CONCURRENT writers (e.g. the per-node commit-stage family, written
    by every actor server on the node across scheduler worker threads
    plus any coordinator step thread): ``arr[b] += n`` is a
    read-modify-write, so multi-writer updates would lose increments
    and drift ``n``/``total`` from the bucket sums. Recording is
    sampled on those paths, so the lock is off the per-command cost.

    The buckets are a plain list: ``counts[b] += 1`` on a list costs a
    third less than on a numpy vector (which boxes a scalar each way),
    and the wave loop records some twenty values a step. ``arr`` is the
    int64 vector the readers take, built on demand."""

    __slots__ = ("name", "help", "unit", "_counts", "n", "total", "max_v",
                 "_lock")

    def __init__(self, name, help: str = "", unit: str = "ns",
                 locked: bool = False):
        self.name = name
        self.help = help
        self.unit = unit
        self._counts = [0] * N_BUCKETS
        self.n = 0
        self.total = 0
        self.max_v = 0
        self._lock = threading.Lock() if locked else None

    @property
    def arr(self) -> np.ndarray:
        """The bucket counts as an int64 vector (a copy)."""
        return np.array(self._counts, dtype=np.int64)

    def record(self, v: int, count: int = 1) -> None:
        v = int(v)
        if v < 0:
            v = 0
        if v < SUB_BUCKETS:
            b = v
        else:
            shift = v.bit_length() - 1 - SUB_BITS
            b = ((shift + 1) << SUB_BITS) + ((v >> shift) - SUB_BUCKETS)
            if b >= N_BUCKETS:
                b = N_BUCKETS - 1
        lock = self._lock
        if lock is not None:
            with lock:
                self._counts[b] += count
                self.n += count
                self.total += v * count
                if v > self.max_v:
                    self.max_v = v
            return
        self._counts[b] += count
        self.n += count
        self.total += v * count
        if v > self.max_v:
            self.max_v = v

    def record_seconds(self, s: float, count: int = 1) -> None:
        self.record(int(s * 1e9), count)

    # -- reads -------------------------------------------------------------

    def percentile(self, p: float) -> int:
        """Value at percentile ``p`` (0..100), as the midpoint of the
        covering bucket; 0 when empty."""
        return self.percentiles((p,))[0]

    def percentiles(self, ps: Sequence[float]) -> List[int]:
        counts = self.arr  # a snapshot: the writer may race the scan
        total = int(counts.sum())
        if total == 0:
            return [0] * len(ps)
        cum = np.cumsum(counts)
        out = []
        for p in ps:
            # rank of the p-th percentile observation (1-based)
            rank = max(1, min(total, int(np.ceil(p / 100.0 * total))))
            b = int(np.searchsorted(cum, rank))
            lo, hi = bucket_bounds(b)
            out.append((lo + hi) // 2)
        return out

    def mean(self) -> float:
        return self.total / self.n if self.n else 0.0

    def to_dict(self) -> Dict[str, Any]:
        """Summary percentiles in milliseconds (assuming ns records)."""
        p50, p90, p99, p999 = self.percentiles((50, 90, 99, 99.9))
        return {
            "count": self.n,
            "sum_ms": round(self.total / 1e6, 3),
            "mean_ms": round(self.mean() / 1e6, 4),
            "max_ms": round(self.max_v / 1e6, 3),
            "p50_ms": round(p50 / 1e6, 4),
            "p90_ms": round(p90 / 1e6, 4),
            "p99_ms": round(p99 / 1e6, 4),
            "p99_9_ms": round(p999 / 1e6, 4),
        }

    def nonzero_buckets(self) -> List[Tuple[int, int, int]]:
        """(lo, hi, count) for every non-empty bucket (debug/export)."""
        return [(*bucket_bounds(b), c)
                for b, c in enumerate(list(self._counts)) if c]

    def merge(self, other: "LogHistogram") -> None:
        """Fold another histogram's buckets into this one (aggregation
        across nodes/shards; both must use the same unit)."""
        mine = self._counts
        for b, c in enumerate(list(other._counts)):
            if c:
                mine[b] += c
        self.n += other.n
        self.total += other.total
        if other.max_v > self.max_v:
            self.max_v = other.max_v

    def reset(self) -> None:
        self._counts[:] = [0] * N_BUCKETS
        self.n = 0
        self.total = 0
        self.max_v = 0


class HistogramRegistry:
    """Process-global registry: name -> LogHistogram (mirrors
    CounterRegistry; ``new`` returns the existing histogram when the
    name is already registered)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._tab: Dict[object, LogHistogram] = {}

    def new(self, name, help: str = "", unit: str = "ns",
            locked: bool = False) -> LogHistogram:
        with self._lock:
            h = self._tab.get(name)
            if h is None:
                h = LogHistogram(name, help=help, unit=unit, locked=locked)
                self._tab[name] = h
            return h

    def fetch(self, name) -> Optional[LogHistogram]:
        with self._lock:
            return self._tab.get(name)

    def delete(self, name) -> None:
        with self._lock:
            self._tab.pop(name, None)

    def names(self) -> List[object]:
        with self._lock:
            return list(self._tab.keys())

    def overview(self) -> Dict[object, Dict[str, Any]]:
        with self._lock:
            items = list(self._tab.items())
        return {k: h.to_dict() for k, h in items if h.n}


_hists = HistogramRegistry()


def histograms() -> HistogramRegistry:
    return _hists


def histogram(name, help: str = "", unit: str = "ns",
              locked: bool = False) -> LogHistogram:
    return _hists.new(name, help=help, unit=unit, locked=locked)


# -- well-known histogram families ------------------------------------------

# coordinator wave-loop phases (per step; docs/INTERNALS.md §13).
# WAVE_STEP_PHASES are DISJOINT slices of one coordinator step — they
# sum to the step-loop wall time and are the share denominator in
# attribution tools; WAVE_SUBSET_PHASES are finer-grained views RECORDED
# WITHIN a step phase (never added to the denominator), and two waits
# that stand outside every phase and are in no share's denominator
# either: send_queue lies BETWEEN the phases' threads and the sender,
# gil_wait BENEATH all of them (whatever a thread of the process does,
# it pays that wait whenever it comes back for the interpreter lock).
WAVE_STEP_PHASES = (
    ("ingress_drain", "drain ingress queues + route messages + append "
                      "client commands; its leaves: ingress_classify, "
                      "step_lock_wait, ingress_route, ingest_append, "
                      "ingest_fanout"),
    ("host_pack", "apply queued device scatters + pack the mailbox"),
    ("device_step", "step dispatched -> egress synced and the state lock "
                    "held: ticket_queue + egress_sync + egress_lock_wait"),
    ("host_egress", "realise egress: acks, role changes, apply, replies, "
                    "rare messages (also those of a ticket that stepped "
                    "nothing); its leaves: egress_follow, egress_mirror, "
                    "egress_apply, egress_rare"),
    ("aer_fanout", "build + send outbound AER batches (a dispatching "
                   "pass's, ahead of its host_pack, and the commit-driven "
                   "one at realisation)"),
)
WAVE_SUBSET_PHASES = {
    "classify_native": "subset of ingress_drain (GIL-released native "
                       "class partition of the drained burst; zero "
                       "samples when the native path is off)",
    "pack_native": "subset of host_pack (GIL-released native mailbox "
                   "scatter; zero samples when the native path is off)",
    # the three blind phases, split (step thread writes the first four,
    # the thread that realises tickets the last three)
    "step_lock_wait": "subset of ingress_drain (classified -> the state "
                      "lock held)",
    "scatter_dispatch": "subset of host_pack (queued set_roles / "
                        "record_appended scatters, staged-run "
                        "bookkeeping, active-set selection)",
    "mailbox_build": "subset of host_pack (pack the step's mailbox)",
    "step_dispatch": "subset of host_pack (the jitted step call: "
                     "argument transfer + dispatch)",
    "ticket_queue": "subset of device_step (step dispatched -> the "
                    "ticket popped for realisation: the wait in the "
                    "pipe queue)",
    "egress_sync": "subset of device_step (np.asarray of the egress: "
                   "the host's true wait for the device)",
    "egress_lock_wait": "subset of device_step (egress synced -> the "
                        "state lock held)",
    # what only a busy fleet works: one record per pass over ALL its
    # groups
    "ingest_append": "subset of ingress_drain (log appends + WAL "
                     "hand-off of the pass's client commands, all "
                     "groups; no sample on a pass without commands)",
    "egress_apply": "subset of host_egress (machine apply + client "
                    "replies of every group the step committed; no "
                    "sample on a step that committed nothing)",
    "effects_realise": "subset of host_egress, and of egress_apply where "
                       "an apply returned them (machine effects realised: "
                       "send_msg, monitors, release cursors, ...; one "
                       "clock pair a _realise_effects call, added up; no "
                       "sample on a step that realised none)",
    # the rest of ingress_drain and host_egress, to the leaf (step
    # thread the first three, the realising thread the next three)
    "ingress_classify": "subset of ingress_drain (the burst popped off "
                        "the ingress lanes and classified, outside the "
                        "state lock; recorded where ingress_drain is)",
    "ingress_route": "subset of ingress_drain (every drained protocol "
                     "message to its handler, and the replies that "
                     "routing produced handed to the sender; no sample "
                     "on a pass without messages)",
    "ingest_fanout": "subset of ingress_drain (an ingest-only pass's "
                     "AppendEntries fan-out for the groups it appended "
                     "to; a dispatching pass's is aer_fanout's)",
    "egress_follow": "subset of host_egress (the loop over the step's "
                     "consumed messages: vote and AppendEntries replies, "
                     "a follower's log writes and acks; no sample on a "
                     "step that consumed none)",
    "egress_mirror": "subset of host_egress (the sweep over the touched "
                     "groups: roles, terms, meta store, became-leader, "
                     "term hints, and the step's replies handed to the "
                     "sender; less the applies inside it)",
    "egress_rare": "subset of host_egress (the step's rare messages: "
                   "consistent queries registered, heartbeats answered, "
                   "elections, snapshots, membership; no sample on a "
                   "step without any)",
    "send_queue": "no phase's: a batch published to the sender's ring "
                  "-> the sender thread drains it (one sample a batch; "
                  "an inline send waits for nobody and records none)",
    "gil_wait": "no phase's: how much later than asked a thread of this "
                "process wakes from a 20 ms sleep, which is the wait of "
                "any thread that comes back from a call that let go of "
                "the interpreter lock (a jitted call, an fsync, a "
                "socket, a numpy call); kernel timer slack included "
                "(0.06-0.25 ms when nothing contends); sampled 50 times "
                "a second by one thread a process, on the first started "
                "coordinator only; the process's, no one thread's",
}
WAVE_PHASES = WAVE_STEP_PHASES + tuple(WAVE_SUBSET_PHASES.items())

# commit-latency decomposition stages (sampled per command; both backends)
COMMIT_STAGES = (
    ("submit_append", "client submit -> leader log append"),
    ("append_durable", "log append -> WAL durable watermark covers it"),
    ("durable_commit", "durable -> quorum commit observed"),
    ("commit_apply", "commit observed -> machine apply done"),
    ("apply_reply", "machine apply -> client reply issued"),
)


def wave_hists(node_name: str) -> Dict[str, LogHistogram]:
    return {
        ph: histogram(("wave", node_name, ph), help=h)
        for ph, h in WAVE_PHASES
    }


def staleness_hist(node_name: str) -> LogHistogram:
    """Observed staleness bound claimed at each bounded local read
    (api.local_query max_staleness_s path, docs/INTERNALS.md §20) —
    recorded in ns of leader wall time, whether the read was served or
    rejected, so the distribution shows how fresh followers really run."""
    return histogram(
        ("follower_read_staleness", node_name),
        help="leader-stamped staleness bound evaluated for bounded "
             "local reads (max_staleness_s, docs/INTERNALS.md §20)",
        locked=True,
    )


def commit_hists(node_name: str) -> Dict[str, LogHistogram]:
    # locked: one family per NODE, but every actor server on the node
    # (scheduler worker threads) and any coordinator step thread write
    # it concurrently — recording is sampled, so the lock is cheap
    return {
        st: histogram(("commit", node_name, st), help=h, locked=True)
        for st, h in COMMIT_STAGES
    }


# ---------------------------------------------------------------------------
# flight recorder


class FlightRecorder:
    """Bounded ring of structured events for post-mortem debugging.

    Events: ``(t_monotonic, seq, kind, node, group, term, detail)``.
    Appends are lock-free and safe from any thread; the ring holds the
    most recent ``capacity`` events. ``dump()`` renders them oldest
    first — the shape a liveness flake is debugged from."""

    def __init__(self, capacity: int = 4096):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self._slots: List[Optional[Tuple]] = [None] * capacity
        self._ctr = itertools.count()

    def record(self, kind: str, node: Optional[str] = None,
               group: Optional[str] = None, term: Optional[int] = None,
               detail: Any = None) -> None:
        n = next(self._ctr)  # atomic in CPython
        self._slots[n % self.capacity] = (
            time.monotonic(), n, kind, node, group, term, detail
        )

    def events(self, last: Optional[int] = None) -> List[Dict[str, Any]]:
        """Events oldest -> newest (optionally only the last ``n``)."""
        got = [s for s in list(self._slots) if s is not None]
        got.sort(key=lambda s: s[1])
        if last is not None:
            got = got[-last:]
        return [
            {"ts": s[0], "seq": s[1], "kind": s[2], "node": s[3],
             "group": s[4], "term": s[5], "detail": s[6]}
            for s in got
        ]

    def clear(self) -> None:
        self._slots = [None] * self.capacity

    def dump(self, file=None, last: int = 200, header: str = "") -> None:
        """Human-readable dump of the most recent events (stderr by
        default) — called automatically when a kv_harness/nemesis run
        fails so liveness flakes arrive with their trace attached."""
        f = file or sys.stderr
        evts = self.events(last=last)
        print(f"-- flight recorder dump ({len(evts)} events){header} --",
              file=f)
        if not evts:
            print("   (no events recorded)", file=f)
            return
        t0 = evts[0]["ts"]
        for e in evts:
            grp = f" group={e['group']}" if e["group"] is not None else ""
            trm = f" term={e['term']}" if e["term"] is not None else ""
            det = f" {e['detail']}" if e["detail"] is not None else ""
            print(
                f"  +{e['ts'] - t0:9.3f}s #{e['seq']:<6d} "
                f"{e['kind']:<18s} node={e['node']}{grp}{trm}{det}",
                file=f,
            )


_recorder = FlightRecorder()


def flight_recorder() -> FlightRecorder:
    return _recorder


def record_event(kind: str, node: Optional[str] = None,
                 group: Optional[str] = None, term: Optional[int] = None,
                 detail: Any = None) -> None:
    _recorder.record(kind, node=node, group=group, term=term, detail=detail)


# ---------------------------------------------------------------------------
# spans, in the profiler's trace


class _NoSpan:
    """What :func:`span` hands out in a process that has not imported
    JAX (an actor-only node): no profiler session can run there."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


def span(name: str, **stats):
    """A ``jax.profiler.TraceAnnotation``: a host span in the profiler's
    own trace (plane ``/host:CPU`` of the ``xplane.pb`` that also holds
    ``/device:TPU:<n>``, on the same clock), with ``stats`` as the
    event's stats. Inert while no profiler session runs, so there is no
    switch: ``api.profile`` or any ``jax.profiler.start_trace`` turns
    every span on.

    Names are ``ra/<thread role>/<what>`` (children add ``/<child>``);
    every span carries ``node=``, because the trace names every Python
    thread's line ``python3``. One span per step, batch or client call,
    never one per group, message or entry inside a wave
    (docs/INTERNALS.md, "Spans in the profiler's trace").

    Inert is not free: beside the wave loop's real work one annotation
    built, entered and left costs 2.6 us on the v5e's host (PERF.md
    section 6, PR 24), so the loops that turn hundreds of times a second
    ask :func:`tracing` once a turn and :func:`begin` their spans only
    under it; ``with obs.span(...)`` is for the paths that run a few
    times a second.

    Call it as ``obs.span(...)``: once JAX is imported the name is
    rebound to the annotation class itself."""
    global span
    if "jax" not in sys.modules:
        return _NO_SPAN
    from jax.profiler import TraceAnnotation

    span = TraceAnnotation
    return TraceAnnotation(name, **stats)


def tracing() -> bool:
    """True while a profiler session records host spans (the
    profiler's own state, one C call: ``TraceAnnotation.is_enabled``).
    Call it as ``obs.tracing()``: rebound like :func:`span`."""
    global tracing
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation

    tracing = TraceAnnotation.is_enabled
    return tracing()


def begin(name: str, **stats):
    """Open a :func:`span` without a ``with``: for the hot loops, as
    ``if tr: sp = obs.begin(...)`` ... ``if tr: obs.end(sp)`` with
    ``tr = obs.tracing()`` read once a turn. A span left open by an
    exception ends when its object is collected."""
    sp = span(name, **stats)
    sp.__enter__()
    return sp


def end(sp) -> None:
    sp.__exit__(None, None, None)


def profile_options():
    """The profiler options of ``api.profile`` and of the benchmark's
    traced runs: host spans (``TraceAnnotation``) and the device's
    operations, no Python function tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def xplane_path(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` the profiler wrote under
    ``trace_dir``."""
    import glob

    return sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]


# ---------------------------------------------------------------------------
# exposition


def _metric_name(name) -> str:
    """Flatten a registry key into a Prometheus metric-name suffix."""
    if isinstance(name, tuple):
        flat = "_".join(str(p) for p in name)
    else:
        flat = str(name)
    return "".join(c if c.isalnum() or c == "_" else "_" for c in flat)


def _label_of(name) -> str:
    s = str(name).replace("\\", "\\\\").replace('"', '\\"').replace("\n", " ")
    return f'name="{s}"'


def prometheus_text() -> str:
    """Prometheus text exposition of every registered counter vector and
    histogram. Counters keep their field kind/help (the describe() path
    ``overview()`` drops); histograms export as summaries in SECONDS
    plus ``_count``/``_sum``."""
    from ra_tpu import counters as _counters

    out: List[str] = []
    # counters: one metric family per field name; vectors become labels.
    # Collect (field -> kind, help, [(owner, value)]) across the registry.
    fields: Dict[str, Tuple[str, str, List[Tuple[object, int]]]] = {}
    reg = _counters.registry()
    for owner in reg.names():
        c = reg.fetch(owner)
        if c is None:
            continue
        vals = c.to_dict()
        for fname, kind, help_txt in c.fields:
            ent = fields.get(fname)
            if ent is None:
                ent = fields[fname] = (kind, help_txt, [])
            ent[2].append((owner, vals[fname]))
    for fname in sorted(fields):
        kind, help_txt, rows = fields[fname]
        metric = f"ra_{_metric_name(fname)}"
        out.append(f"# HELP {metric} {help_txt}")
        out.append(f"# TYPE {metric} {'counter' if kind == 'counter' else 'gauge'}")
        for owner, v in rows:
            out.append(f"{metric}{{{_label_of(owner)}}} {v}")
    # histograms: summaries with fixed quantiles, values in seconds
    for name in sorted(_hists.names(), key=str):
        h = _hists.fetch(name)
        if h is None:
            continue
        metric = f"ra_{_metric_name(name)}_seconds"
        out.append(f"# HELP {metric} {h.help or 'latency histogram'}")
        out.append(f"# TYPE {metric} summary")
        ps = h.percentiles((50, 90, 99, 99.9))
        for q, v in zip(("0.5", "0.9", "0.99", "0.999"), ps):
            out.append(f'{metric}{{quantile="{q}"}} {v / 1e9:.9f}')
        out.append(f"{metric}_sum {h.total / 1e9:.9f}")
        out.append(f"{metric}_count {h.n}")
    return "\n".join(out) + "\n"
