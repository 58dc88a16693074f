"""The cyclic collector's policy of a process that hosts started
coordinators (ISSUE 27; ``ra_tpu/runtime/heap.py``; docs/INTERNALS.md
§22): installed at the first ``start()``, one for all the coordinators
of the process, given back as it was found at the last ``stop()``, with
every pause booked on exactly one coordinator."""

import gc
import threading
import time
import types
import weakref

import jax
import pytest

from ra_tpu import api
from ra_tpu.machine import SimpleMachine
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import ElectionTimeout
from ra_tpu.runtime import heap
from ra_tpu.runtime.coordinator import BatchCoordinator

FOUND = (901, 11, 12)  # what the "embedding application" had set
# while a node serves: the middle generation goes with every second
# young collection, a full one comes once in heap._FULL_EVERY of those
MIDDLE, FULL = min(FOUND[1], heap._MIDDLE_AFTER), heap._FULL_EVERY
GC_COUNTERS = ("gc_collections", "gc_full_collections", "gc_pause_ns")


@pytest.fixture(autouse=True)
def collector_as_found():
    """Every test starts from a process with no policy in (a started
    coordinator that an earlier test of this worker never stopped is
    taken out; the interpreter itself starts with a few hundred objects
    in the permanent generation) and with thresholds that are not the
    interpreter's defaults, and hands the defaults back."""
    for counters, _node, _size in list(heap._serving):
        heap.leave(types.SimpleNamespace(counters=counters))
    gc.unfreeze()
    before = gc.get_threshold()
    gc.set_threshold(*FOUND)
    made = []
    yield made
    for c in made:
        c.stop()
    assert heap._serving == []
    gc.set_threshold(*before)


def _coord(made, name, capacity=64):
    c = BatchCoordinator(name, capacity=capacity, num_peers=3,
                         detector_poll_s=0.05, tick_interval_s=0.2)
    made.append(c)
    return c


def _policy():
    return (gc.get_threshold(), gc.get_freeze_count(),
            gc.callbacks.count(heap._on_gc))


def _gc_counters(c):
    got = c.counters.to_dict()
    return tuple(got[k] for k in GC_COUNTERS)


def test_installed_at_the_first_start_not_at_construction(collector_as_found):
    c = _coord(collector_as_found, "hp_a0")
    assert _policy() == (FOUND, 0, 0)
    c.start()
    threshold, frozen, hooks = _policy()
    # generation 0 by the one rule: 8 containers a group replica
    assert threshold == (8 * 64 * 3, MIDDLE, FULL)
    assert frozen > 0 and hooks == 1
    c.stop()
    assert _policy() == (FOUND, 0, 0)


def test_one_install_for_three_sized_to_the_largest(collector_as_found):
    sizes = {"hp_b0": 64, "hp_b1": 256, "hp_b2": 128}
    coords = [_coord(collector_as_found, n, cap) for n, cap in sizes.items()]
    frozen = []
    for c in coords:
        c.start()
        frozen.append(gc.get_freeze_count())
        assert gc.callbacks.count(heap._on_gc) == 1
    assert min(frozen) > 0
    assert gc.get_threshold() == (8 * 256 * 3, MIDDLE, FULL)
    # the largest leaves: the rule is read again from those that serve
    coords[1].stop()
    assert gc.get_threshold() == (8 * 128 * 3, MIDDLE, FULL)
    assert gc.get_freeze_count() > 0
    coords[0].stop()
    assert gc.callbacks.count(heap._on_gc) == 1
    coords[2].stop()
    assert _policy() == (FOUND, 0, 0)


def test_a_small_coordinator_never_lowers_what_was_found(collector_as_found):
    c = _coord(collector_as_found, "hp_c0", capacity=8)  # 8 * 8 * 3 < 901
    gc.set_threshold(FOUND[0], FOUND[1], 2 * FULL)
    c.start()
    assert gc.get_threshold() == (FOUND[0], MIDDLE, 2 * FULL)
    c.stop()
    assert gc.get_threshold() == (FOUND[0], FOUND[1], 2 * FULL)
    gc.set_threshold(*FOUND)


def test_built_and_never_started_changes_nothing(collector_as_found):
    idle = _coord(collector_as_found, "hp_d0", capacity=512)
    serving = _coord(collector_as_found, "hp_d1")
    serving.start()
    threshold, frozen, hooks = _policy()
    assert threshold == (8 * 64 * 3, MIDDLE, FULL)  # not idle's 512
    assert frozen > 0 and hooks == 1
    idle.stop()  # stop() without start(): the policy stays in
    # (frozen, not the same count: the permanent generation holds what
    # every earlier test file of this worker left behind, and loses one
    # whenever such an object is freed, as a thread that ends frees it)
    still = _policy()
    assert (still[0], still[1] > 0, still[2]) == (threshold, True, hooks)
    serving.stop()
    assert _policy() == (FOUND, 0, 0)


def test_stop_twice_and_stop_without_start_are_harmless(collector_as_found):
    a = _coord(collector_as_found, "hp_e0")
    b = _coord(collector_as_found, "hp_e1")
    never = _coord(collector_as_found, "hp_e2")
    a.start()
    b.start()
    a.stop()
    a.stop()  # the second stop() must not take b's policy out
    assert gc.get_freeze_count() > 0 and gc.callbacks.count(heap._on_gc) == 1
    never.stop()
    never.stop()
    assert gc.callbacks.count(heap._on_gc) == 1
    b.stop()
    b.stop()
    assert _policy() == (FOUND, 0, 0)


def test_pauses_are_booked_on_exactly_one_of_three(collector_as_found):
    coords = [_coord(collector_as_found, f"hp_f{i}") for i in range(3)]
    for c in coords:
        c.start()
    before = [_gc_counters(c) for c in coords]
    gc.collect()
    gc.collect(0)
    after = [_gc_counters(c) for c in coords]
    rose = [i for i in range(3) if after[i] != before[i]]
    assert rose == [0]  # the one that installed the policy
    n, full, ns = (a - b for a, b in zip(after[0], before[0]))
    assert n >= 2 and 1 <= full < n and ns > 0
    # the booking coordinator stops: the next started one takes over
    coords[0].stop()
    frozen = _gc_counters(coords[0])
    gc.collect()
    assert _gc_counters(coords[0]) == frozen
    assert _gc_counters(coords[1])[0] > after[1][0]
    assert _gc_counters(coords[2]) == after[2]
    # and nothing is booked once the last has stopped
    coords[1].stop()
    coords[2].stop()
    last = [_gc_counters(c) for c in coords]
    gc.collect()
    assert [_gc_counters(c) for c in coords] == last


def test_a_cycle_made_while_started_is_still_collected(collector_as_found):
    """The collector is slowed, not off: no ``gc.collect()`` here, only
    as many live containers as generation 0 holds."""
    c = _coord(collector_as_found, "hp_g0")
    c.start()
    assert gc.isenabled()

    class Node:
        pass

    a, b = Node(), Node()
    a.other, b.other = b, a
    gone = weakref.ref(a)
    del a, b
    assert gone() is not None  # no reference count frees a cycle
    before = _gc_counters(c)[0]
    keep = [[] for _ in range(4 * gc.get_threshold()[0])]
    assert gone() is None
    assert _gc_counters(c)[0] > before
    del keep


def test_a_frozen_object_is_still_freed_by_reference_count(collector_as_found):
    class Thing:
        pass

    thing = Thing()
    thing.payload = [1, 2, 3]
    gone = weakref.ref(thing)
    c = _coord(collector_as_found, "hp_h0")
    c.start()  # thing is in the permanent generation now
    del thing
    assert gone() is None


def test_groups_serve_under_the_policy_and_after_it(collector_as_found):
    """Three started coordinators, one group: commands commit while the
    policy is in, and the stop order does not matter to the survivors."""
    names = ["hp_i0", "hp_i1", "hp_i2"]
    coords = [BatchCoordinator(n, capacity=8, num_peers=3,
                               election_timeout_s=0.15, detector_poll_s=0.05,
                               tick_interval_s=0.2) for n in names]
    collector_as_found.extend(coords)
    ids = [("hg", n) for n in names]
    for c in coords:
        c.add_group("hg", "hp_cluster", ids,
                    SimpleMachine(lambda cmd, s: s + cmd, 0))
        c.start()
    coords[0].deliver(ids[0], ElectionTimeout(), None)
    deadline = time.monotonic() + 30
    while coords[0].by_name["hg"].role != C.R_LEADER:
        assert time.monotonic() < deadline
        time.sleep(0.02)
    total = 0
    for _ in range(20):
        total, _leader = api.process_command(ids[0], 1, timeout=10)
        gc.collect(0)
    assert total == 20
    assert _gc_counters(coords[0])[0] >= 20
    assert _gc_counters(coords[1])[0] == 0


def test_the_log_keeps_no_reply_handle_once_it_is_in(collector_as_found):
    """A caller's handle is held until its reply is out, by the
    pending-reply table; the entry, which lives until its log is cut,
    holds neither the handle nor the submit stamp, so a handle that is a
    closure dies by reference count with its reply (PR 33)."""
    from ra_tpu.protocol import USR, Command

    names = ["hp_k0", "hp_k1", "hp_k2"]
    coords = [BatchCoordinator(n, capacity=8, num_peers=3,
                               election_timeout_s=0.15, detector_poll_s=0.05,
                               tick_interval_s=0.2) for n in names]
    collector_as_found.extend(coords)
    ids = [("hk", n) for n in names]
    for c in coords:
        c.add_group("hk", "hp_cluster_k", ids,
                    SimpleMachine(lambda cmd, s: s + cmd, 0))
        c.start()
    coords[0].deliver(ids[0], ElectionTimeout(), None)
    deadline = time.monotonic() + 30
    while coords[0].by_name["hk"].role != C.R_LEADER:
        assert time.monotonic() < deadline
        time.sleep(0.02)

    class Handle:
        def __init__(self):
            self.got = threading.Event()

        def __call__(self, reply):
            self.reply = reply
            self.got.set()

    handles = [Handle() for _ in range(5)]
    gone = [weakref.ref(h) for h in handles]
    coords[0].deliver_many([
        (ids[0], Command(kind=USR, data=1, reply_mode="await_consensus",
                         from_ref=h, ts=time.monotonic_ns()), None)
        for h in handles])
    for h in handles:
        assert h.got.wait(10)
    assert sorted(h.reply[1] for h in handles) == [1, 2, 3, 4, 5]
    g = coords[0].by_name["hk"]
    assert not g.pending_replies
    last = g.log.last_index_term()[0]
    held = [g.log.fetch(i) for i in range(last - 4, last + 1)]
    assert [e.cmd.data for e in held] == [1] * 5
    assert all(e.cmd.from_ref is None and e.cmd.ts is None for e in held)
    del handles, h
    assert all(ref() is None for ref in gone)


def test_no_stop_walks_more_than_two_young_generations(collector_as_found):
    """What outlives a young collection on a serving node is mostly log
    entries: the middle generation goes with every second young
    collection (generation 0, 1, 0, 1, ...), never ten young
    generations' worth at once."""
    c = _coord(collector_as_found, "hp_l0")
    c.start()
    seen = []

    def watch(phase, info):
        if phase == "start":
            seen.append(info["generation"])

    gc.callbacks.append(watch)
    try:
        keep = [[] for _ in range(6 * gc.get_threshold()[0])]
    finally:
        gc.callbacks.remove(watch)
    del keep
    assert len(seen) >= 5 and 2 not in seen
    assert all(a != b for a, b in zip(seen, seen[1:])), seen


def test_a_pause_is_a_span_under_a_profiler_session(collector_as_found,
                                                    tmp_path):
    from ra_tpu import obs

    c = _coord(collector_as_found, "hp_j0")
    c.start()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=obs.profile_options())
    try:
        gc.collect()
        gc.collect(1)
    finally:
        jax.profiler.stop_trace()
    gc.collect()  # no session: no span, and nothing raised
    data = jax.profiler.ProfileData.from_file(obs.xplane_path(str(tmp_path)))
    pauses = [dict(e.stats) for plane in data.planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name == "ra/gc/pause"]
    assert [p["generation"] for p in pauses] == [2, 1]
    assert {p["node"] for p in pauses} == {"hp_j0"}


def test_the_hook_survives_a_stop_that_races_a_collection(collector_as_found):
    """``leave()`` empties the list the hook reads from another thread:
    the hook must find nobody to book on, not raise."""
    coords = [_coord(collector_as_found, f"hp_k{i}") for i in range(3)]
    for c in coords:
        c.start()
    stop = threading.Event()
    errors = []

    def churn():
        try:
            while not stop.is_set():
                gc.collect(0)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    t = threading.Thread(target=churn)
    t.start()
    try:
        for c in coords:
            c.stop()
    finally:
        stop.set()
        t.join()
    assert errors == [] and _policy() == (FOUND, 0, 0)
