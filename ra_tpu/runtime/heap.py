"""The cyclic collector's policy of a process that hosts started
``BatchCoordinator``s (docs/INTERNALS.md §22, "The collector while a
node serves").

What a started node keeps (logs, group hosts, machines, programs) is
no garbage and keeps its shape while it serves; what a wave makes
(entries, commands, peer messages) dies by reference count a wave
later. Under CPython's defaults those containers are still walked
young, promoted and walked again, and the whole old heap with them each
time it has grown by a quarter, every thread stopped: a third of the
wall at 10,240 x 3 (PERF.md section 6, PR 27). So, while any
coordinator of the process is started:

- what was built before its ``start()`` is collected once and frozen
  (the permanent generation: never walked, still freed by count);
- generation 0 holds what one full-width wave of the largest started
  coordinator keeps alive, so a wave's allocations and releases cancel
  before a collection starts, and a full collection of the unfrozen
  rest is rare. The collector stays on: a cycle is still collected;
- what survives a young collection on a serving node is mostly its
  logs' entries, which live until their log is cut: the middle
  generation is collected with every second young one, not with every
  eleventh, so no stop is longer than two young generations' walk (at
  ten it was one stop of half a second in a window of forty: PERF.md
  section 6, PR 33). A full collection comes as rarely as it did;
- one ``gc.callbacks`` hook books every pause on ONE started
  coordinator's counters (a pause is the process's; observers add the
  coordinators up) and, under a profiler session, as ``ra/gc/pause``.

The last ``stop()`` gives it all back: ``gc.unfreeze()`` and the
thresholds that were found. Called from ``BatchCoordinator.start()``
and ``stop()`` only.

The process's other account rides the same registry and the same two
calls: ``runtime/gil_probe.py``'s thread, which samples the wait for the
interpreter lock, lives from the first ``enter`` to the last ``leave``
and books on the first started coordinator too.
"""

import gc
import threading
import time

from ra_tpu import obs as _obs
from ra_tpu.runtime import gil_probe as _gil_probe

# containers a group replica keeps alive across a full-width wave (its
# entry, command, triple, AERs and replies in flight; PERF.md, PR 27)
_YOUNG_PER_REPLICA = 8
_MIDDLE_AFTER = 0  # young collections between two of the middle generation
_FULL_EVERY = 500  # middle-generation collections to one full one

_lock = threading.RLock()  # a finaliser run by enter()'s collection may stop()
_serving: list = []  # [(counters, node name, capacity * num_peers)]
_found = None  # gc.get_threshold() before the first start()
_t0 = 0
_span = None


def _on_gc(phase: str, info: dict) -> None:
    """Two clock reads a collection; the first started coordinator's
    counters take the pause."""
    global _t0, _span
    first = next(iter(_serving), None)  # one call: leave() may race
    if phase == "start":
        if first is not None and _obs.tracing():
            _span = _obs.begin("ra/gc/pause", node=first[1],
                               generation=info["generation"])
        _t0 = time.perf_counter_ns()
        return
    took = time.perf_counter_ns() - _t0
    if _span is not None:
        _obs.end(_span)
        _span = None
    if first is not None:
        cnt = first[0]
        cnt.incr("gc_pause_ns", took)
        cnt.incr("gc_collections")
        if info["generation"] == 2:
            cnt.incr("gc_full_collections")


def _size_generations() -> None:
    young = _YOUNG_PER_REPLICA * max(size for _c, _n, size in _serving)
    gc.set_threshold(max(_found[0], young), min(_found[1], _MIDDLE_AFTER),
                     max(_found[2], _FULL_EVERY))


def enter(coord) -> None:
    """``coord`` starts: freeze what the process has built, size the
    generations to the largest started coordinator."""
    global _found
    with _lock:
        gc.collect()
        gc.freeze()
        first = not _serving
        if first:
            _found = gc.get_threshold()
            gc.callbacks.append(_on_gc)
        _serving.append((coord.counters, coord.name,
                         coord.capacity * coord.P))
        _size_generations()
        if first:
            _gil_probe.start(_serving)


def leave(coord) -> None:
    """``coord`` stops (harmless if it never started, or stopped
    already): the last one out gives the collector back as found."""
    global _found
    with _lock:
        mine = [s for s in _serving if s[0] is coord.counters]
        if not mine:
            return
        _serving.remove(mine[0])
        if _serving:
            _size_generations()
            return
        _gil_probe.stop()
        gc.callbacks.remove(_on_gc)
        gc.set_threshold(*_found)
        _found = None
        gc.unfreeze()
