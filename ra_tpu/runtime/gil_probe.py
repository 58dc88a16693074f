"""The interpreter lock's wait, sampled by the process itself
(docs/INTERNALS.md §13, "The interpreter lock's wait").

A thread that asks for a timed sleep gets the core back only when it
also gets the interpreter lock back, so how much later than asked it
wakes is the wait that any thread of the process pays when it comes
back from a call that let go of the lock: a jitted call, an fsync, a
socket, a numpy call over a few hundred elements. One daemon thread a
process, alive while any coordinator of the process is started
(``runtime/heap.py``'s ``enter`` / ``leave`` start and end it, and its
registry of serving coordinators says whose histogram takes the
samples: the first started one's, as for a collector pause, because
both are the process's and observers add the coordinators up). It
sleeps ``PERIOD_S``, reads ``perf_counter_ns`` on both sides and records
``woke - asked - PERIOD_S``, floored at 0, in the wave sub-phase
``gil_wait``. Kernel timer slack is in every sample (0.06-0.25 ms when
nothing contends). It says how long a thread stands at the lock, not
which thread held it.

Under a profiler session a wait over ``SPAN_OVER_NS`` is also the span
``ra/probe/gil_wait``. An annotation cannot be opened in the past, so
the span is opened and closed at the wake and carries ``wait_ns``: it
reaches that far back (``scripts/idle_gaps.read_trace`` lays it from the
due time to the wake).
"""

import threading
import time

from ra_tpu import obs as _obs

PERIOD_S = 0.020  # 50 samples a second; never an option
_PERIOD_NS = int(PERIOD_S * 1e9)
SPAN_OVER_NS = 1_000_000
THREAD_NAME = "ra-gil-probe"

_thread = None  # the one that may record; written under heap's lock


def start(serving: list) -> None:
    """The first coordinator of the process starts (``heap.enter``)."""
    global _thread
    _thread = t = threading.Thread(target=_run, args=(serving,),
                                   name=THREAD_NAME, daemon=True)
    t.start()


def stop() -> None:
    """The last one stops (``heap.leave``): the thread ends at its next
    wake, within one period."""
    global _thread
    _thread = None


def _run(serving: list) -> None:
    me = threading.current_thread()
    clock, sleep = time.perf_counter_ns, time.sleep
    node = hist = None
    while True:
        asked = clock()
        sleep(PERIOD_S)
        woke = clock()
        if _thread is not me:
            return  # stopped (a later start() has a thread of its own)
        first = next(iter(serving), None)  # one call: leave() may race
        if first is None:
            continue
        if first[1] != node:
            node = first[1]
            hist = _obs.histogram(("wave", node, "gil_wait"))
        wait = woke - asked - _PERIOD_NS
        hist.record(wait)  # (floored at 0 there)
        if wait > SPAN_OVER_NS and _obs.tracing():
            _obs.end(_obs.begin("ra/probe/gil_wait", node=node, wait_ns=wait))
