"""Async command plane: lock-free ingress rings, event-driven wakeups,
explicit backpressure (docs/INTERNALS.md §16).

Deterministic coverage for the concurrency the command plane
introduced: SPSC ring wraparound and full-ring behavior, the
multi-lane ingress fuzz (8 producer threads over 3 shared lanes), the
full-ring -> admission-reject integration (with the gate waiter woken
by the drain, not a sleep), failpoints fired during ring handoff of
both mailbox shapes, step_once ≡ started loop on a seeded command
sequence, and the zero-spurious-wakeups invariant of the idle step loop.
"""

import os
import sys
import threading
import time

import pytest

from ra_tpu import api, faults, leaderboard
from ra_tpu.log.log import Log
from ra_tpu.log.segment_writer import SegmentWriter
from ra_tpu.log.tables import TableRegistry
from ra_tpu.log.wal import Wal
from ra_tpu.machine import SimpleMachine
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import (
    AppendEntriesReply, Command, ElectionTimeout, Entry, HeartbeatReply, USR,
)
from ra_tpu.rings import IngressRings, SpscRing, WaitGate
from ra_tpu.runtime.coordinator import BatchCoordinator
from ra_tpu.runtime.transport import NodeRegistry
from ra_tpu.utils.seq import Seq


@pytest.fixture(autouse=True)
def _clean():
    faults.disarm_all()
    leaderboard.clear()
    yield
    faults.disarm_all()
    leaderboard.clear()


def await_(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(0.02)
    raise AssertionError(f"timeout waiting for {what}")


# ---------------------------------------------------------------------------
# SpscRing


def test_ring_fifo_across_wraparound():
    r = SpscRing(8)
    assert r.capacity == 8
    seq = 0
    out = []
    for _round in range(10):  # 50 items through an 8-slot ring
        for _ in range(5):
            assert r.try_push(seq)
            seq += 1
        got = []
        assert r.pop_many(got) == 5
        out.extend(got)
    assert out == list(range(50))
    assert len(r) == 0


def test_ring_full_returns_false_never_drops():
    r = SpscRing(4)
    for i in range(4):
        assert r.try_push(i)
    assert not r.try_push(99)  # full: explicit False, nothing lost
    out = []
    assert r.pop_many(out) == 4
    assert out == [0, 1, 2, 3]
    assert r.try_push(4)  # space freed


def test_ring_pop_many_limit_and_slot_release():
    r = SpscRing(8)
    for i in range(6):
        r.try_push(i)
    out = []
    assert r.pop_many(out, limit=4) == 4
    assert out == [0, 1, 2, 3]
    assert len(r) == 2
    # drained slots are released (no lingering refs for the GC)
    assert r._buf[0] is None
    assert r.pop_many(out) == 2
    assert out == list(range(6))


def test_ring_capacity_rounds_to_power_of_two():
    assert SpscRing(5).capacity == 8
    assert SpscRing(8).capacity == 8
    assert SpscRing(9).capacity == 16


# ---------------------------------------------------------------------------
# WaitGate


def test_ring_length_never_negative_for_a_third_thread():
    """``len(ring)`` is asked by threads that are neither the producer
    nor the consumer (the coordinator's egress and WAL threads poll
    ``pending()``). Descheduled between its two index loads, a reader
    that loads the tail first sees a head that has overtaken it: a
    negative ``__len__`` raises ValueError and kills the asking thread
    (seen on the 13-core chip host, never on the 1-core sandbox)."""
    import sys

    ring = SpscRing(64)
    stop = threading.Event()
    errors = []

    def producer():
        i = 0
        while not stop.is_set():
            i += ring.try_push(i)

    def consumer():
        out = []
        while not stop.is_set():
            ring.pop_many(out)
            out.clear()

    def observer():
        try:
            while not stop.is_set():
                len(ring)  # ValueError when negative
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(e)

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    threads = [threading.Thread(target=f, daemon=True)
               for f in (producer, consumer, observer, observer, observer)]
    try:
        for t in threads:
            t.start()
        time.sleep(1.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)
        sys.setswitchinterval(prev)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]


def test_wait_gate_wakes_parked_waiter_once():
    g = WaitGate()
    e = g.waiter()
    assert not e.is_set()
    g.open()
    assert e.is_set()
    e2 = g.waiter()
    assert not e2.is_set()  # later waiters park on a FRESH event
    g.open()
    assert e2.is_set()


def test_wait_gate_unarmed_open_is_noop():
    g = WaitGate()
    g.open()  # nobody armed: must not pre-set the next waiter's event
    assert not g.waiter().is_set()


# ---------------------------------------------------------------------------
# IngressRings: lanes + concurrent producer fuzz


def test_ingress_rings_one_lane_per_producer_thread():
    rings = IngressRings(lane_slots=16)
    rings.publish("main")
    done = threading.Event()
    threading.Thread(
        target=lambda: (rings.publish("other"), done.set()), daemon=True
    ).start()
    assert done.wait(5)
    assert rings.lanes() == 2
    out = []
    assert rings.drain(out) == 2
    assert set(out) == {"main", "other"}
    assert not rings.pending()


def test_ingress_rings_wake_event_set_on_publish():
    wake = threading.Event()
    rings = IngressRings(lane_slots=16, wake=wake)
    assert not wake.is_set()
    rings.publish(1)
    assert wake.is_set()


def test_concurrent_producer_fuzz_8_threads_3_lanes():
    """8 producer threads share 3 bounded lanes (producer locks armed
    past the cap) while a consumer drains concurrently: every item
    arrives exactly once and per-producer FIFO order survives."""
    rings = IngressRings(lane_slots=64, max_lanes=3)
    n_threads, per_thread = 8, 500
    drained: list = []
    stop = threading.Event()

    def consumer():
        buf: list = []
        while not stop.is_set() or rings.pending():
            if rings.drain(buf):
                drained.extend(buf)
                buf.clear()
            else:
                time.sleep(0.0002)

    ct = threading.Thread(target=consumer, daemon=True)
    ct.start()

    def producer(tid):
        for seq in range(per_thread):
            while not rings.publish((tid, seq)):  # full: retry, no drop
                time.sleep(0.0002)

    threads = [
        threading.Thread(target=producer, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    stop.set()
    ct.join(timeout=30)

    assert rings.lanes() <= 3
    assert len(drained) == n_threads * per_thread
    assert len(set(drained)) == len(drained), "duplicated items"
    by_tid: dict = {}
    for tid, seq in drained:
        by_tid.setdefault(tid, []).append(seq)
    for tid, seqs in by_tid.items():
        assert seqs == sorted(seqs), f"producer {tid} order broken"


# ---------------------------------------------------------------------------
# full-ring backpressure -> admission integration


def _elect_single(c, sid):
    c.deliver(sid, ElectionTimeout(), None)
    for _ in range(50):
        c.step_once()
        if c.by_name[sid[0]].role == C.R_LEADER:
            return
    raise AssertionError("no leader")


def test_full_ring_rejects_client_command_with_gate():
    """A client command hitting a full ingress lane is rejected through
    the admission path — never enqueued (exactly-once retry safe),
    never silently dropped — and the reject carries a gate waiter the
    next space-freeing drain SETS (event-driven retry, no sleep poll)."""
    c = BatchCoordinator("fr0", capacity=4, num_peers=1, idle_sleep_s=0,
                         ingress_ring_slots=8)
    sid = ("fg", "fr0")
    try:
        c.add_group("fg", "frcl", [sid], SimpleMachine(lambda cm, s: s + cm, 0))
        _elect_single(c, sid)
        base_rej = c.counters.get("commands_rejected")
        # fill this thread's lane (8 slots) without stepping
        for _ in range(8):
            assert c.deliver(
                sid, Command(kind=USR, data=1, reply_mode="noreply"), None
            )
        fut = api.Future()
        cmd = Command(kind=USR, data=1, reply_mode="await_consensus",
                      from_ref=fut)
        assert c.deliver(sid, cmd, None)  # handled: rejected, not lost
        assert fut.done()
        assert fut.value[:2] == ("reject", "overloaded")
        gate_evt = fut.value[2]
        assert isinstance(gate_evt, threading.Event)
        assert not gate_evt.is_set()
        assert c.counters.get("commands_rejected") == base_rej + 1
        assert c.counters.get("ingress_ring_full") >= 1
        # the next drain frees lane space and wakes the parked client
        c.step_once()
        assert gate_evt.is_set(), "drain did not wake the rejected client"
        # the rejected command was NEVER enqueued: state advances by
        # exactly the 8 accepted commands
        for _ in range(20):
            c.step_once()
        assert c.by_name["fg"].machine_state == 8
    finally:
        c.stop()


def test_full_ring_drops_lossy_protocol_traffic_counted():
    """Peer protocol traffic (retried by its sender) is shed with a
    counter on a full lane — the transport contract; deliver returns
    False so the in-proc sender counts the drop too."""
    c = BatchCoordinator("lp0", capacity=4, num_peers=1, idle_sleep_s=0,
                         ingress_ring_slots=8)
    sid = ("lg", "lp0")
    try:
        c.add_group("lg", "lpcl", [sid], SimpleMachine(lambda cm, s: s + cm, 0))
        for _ in range(8):
            c.deliver(sid, Command(kind=USR, data=1, reply_mode="noreply"),
                      None)
        base = c.counters.get("ingress_ring_full")
        ok = c.deliver(sid, HeartbeatReply(term=1, query_index=0),
                       ("lg", "peer"))
        assert ok is False
        assert c.counters.get("ingress_ring_full") == base + 1
    finally:
        c.stop()


def test_full_lane_peer_batch_sheds_only_lossy_subset():
    """A peer batch hitting a full lane must NOT be dropped wholesale:
    the lossy protocol subset sheds (returned for the sender's drop
    accounting), everything else rides the overflow queue and is
    processed by the next drain — a batch-level drop would stall
    snapshot transfers and swallow leadership transfers."""
    c = BatchCoordinator("ob0", capacity=4, num_peers=1, idle_sleep_s=0,
                         ingress_ring_slots=8)
    sid = ("og", "ob0")
    try:
        c.add_group("og", "obcl", [sid], SimpleMachine(lambda cm, s: s + cm, 0))
        _elect_single(c, sid)
        for _ in range(8):
            c.deliver(sid, Command(kind=USR, data=1, reply_mode="noreply"),
                      None)
        batch = [
            ("og", ("og", "peer"), HeartbeatReply(term=1, query_index=0)),
            ("og", None, Command(kind=USR, data=1, reply_mode="noreply")),
        ]
        shed = c.ingest_batch(batch)
        assert shed == 1  # only the heartbeat
        assert c.counters.get("ingress_overflow_msgs") == 1
        for _ in range(20):
            c.step_once()
        # 8 ring commands + the overflow-queued batch command applied
        assert c.by_name["og"].machine_state == 9
        assert len(c._overflow_q) == 0
    finally:
        c.stop()


def test_drainer_self_publish_diverts_to_internal_queue():
    """A drainer thread (step/egress loop) whose must-deliver publish
    hits a full lane must NOT gate-wait on itself: the item rides
    _internal_q into its own next drain."""
    c = BatchCoordinator("dq0", capacity=4, num_peers=1, idle_sleep_s=0,
                         ingress_ring_slots=8)
    sid = ("dg", "dq0")
    try:
        c.add_group("dg", "dqcl", [sid], SimpleMachine(lambda cm, s: s + cm, 0))
        _elect_single(c, sid)
        for _ in range(8):
            c.deliver(sid, Command(kind=USR, data=1, reply_mode="noreply"),
                      None)
        ident = threading.get_ident()
        c._drainer_idents.add(ident)
        try:
            item = (c._R_CMD, "dg",
                    Command(kind=USR, data=1, internal=True))
            assert c._publish_blocking(item)  # returns immediately
            assert list(c._internal_q) == [item]
        finally:
            c._drainer_idents.discard(ident)
        for _ in range(20):
            c.step_once()
        assert c.by_name["dg"].machine_state == 9  # 8 ring + 1 internal
    finally:
        c.stop()


# ---------------------------------------------------------------------------
# WAL-backed cluster scaffolding


def _wal_backed(c, d):
    """Put coordinator ``c`` on its own Wal + SegmentWriter under ``d``,
    written events handled on the WAL writer's thread."""
    tables = TableRegistry()
    sw = SegmentWriter(os.path.join(d, "data"), tables, c.wal_notify)
    sw.fault_scope = c.name
    wal = Wal(os.path.join(d, "wal"), tables, c.wal_notify,
              segment_writer=sw)
    wal.notify_many = c.wal_notify_many
    wal.fault_scope = c.name
    return tables, wal, sw, d


def _close_storage(storage):
    for _t, wal, sw, _d in storage:
        try:
            wal.close()
            sw.close()
        except Exception:  # noqa: BLE001
            pass


class _WalCluster:
    def __init__(self, tmp_path, tag, active_set="auto"):
        self.names = [f"{tag}{i}" for i in range(3)]
        self.coords = []
        self.storage = {}
        for n in self.names:
            c = BatchCoordinator(
                n, capacity=8, num_peers=3, active_set=active_set,
                election_timeout_s=0.15, detector_poll_s=0.05,
                tick_interval_s=0.2,
            )
            self.storage[n] = _wal_backed(c, str(tmp_path / n))
            self.coords.append(c)
        self.ids = [("wg", n) for n in self.names]
        for i, c in enumerate(self.coords):
            n = self.names[i]
            tables, wal, _sw, d = self.storage[n]
            log = Log("wg", os.path.join(d, "data", "wg"), tables, wal)
            c.add_group("wg", f"{tag}cl", self.ids,
                        SimpleMachine(lambda cm, s: s + cm, 0), log=log)
            c.start()
        self.coords[0].deliver(self.ids[0], ElectionTimeout(), None)
        await_(self._leader, what="leader elected")

    def _leader(self):
        for i, c in enumerate(self.coords):
            if c.by_name["wg"].role == C.R_LEADER:
                return self.ids[i]
        return None

    def leader(self):
        return await_(self._leader, what="leader")

    def states(self):
        return [c.by_name["wg"].machine_state for c in self.coords]

    def stop(self):
        for c in self.coords:
            c.stop()
        _close_storage(self.storage.values())


# ---------------------------------------------------------------------------
# step_once ≡ the started loop: the one test driver is faithful to the
# served path


_EQ_GROUPS = 6
_EQ_CMDS = 60
_EQ_MOD = 1_000_003


def _eq_sequence(seed):
    """The seeded command sequence, as bursts of ``(group, payload)``,
    and what a plain fold of it leaves in each group."""
    import random

    rng = random.Random(seed)
    cmds = [(rng.randrange(_EQ_GROUPS), rng.randrange(1, 1000))
            for _ in range(_EQ_CMDS)]
    bursts, k = [], 0
    while k < len(cmds):
        n = rng.randrange(1, 9)
        bursts.append(cmds[k:k + n])
        k += n
    fold = [0] * _EQ_GROUPS
    count = [0] * _EQ_GROUPS
    for g, x in cmds:
        fold[g] = (fold[g] * 31 + x) % _EQ_MOD
        count[g] += 1
    return bursts, fold, count


@pytest.mark.parametrize("logs", ["memory", "wal"])
@pytest.mark.parametrize("active_set", ["auto", "never"])
@pytest.mark.parametrize("driver", ["step_once", "started"])
def test_step_once_and_the_started_loop_commit_identically(
        tmp_path, driver, active_set, logs):
    """The same seeded command sequence, through ``step_once`` and
    through ``start()``-ed coordinators, on the sub-width program
    ("auto": what a lightly loaded node runs) and the full-width one
    ("never"), on memory logs and on WAL-backed logs: every case ends
    with the plain fold's machine state and the same last index and
    term on all three replicas, so the cases equal one another."""
    tag = f"eq{driver[2]}{active_set[0]}{logs[0]}"
    reg = NodeRegistry()
    # six groups of 32: never more than capacity / 4 busy, so "auto"
    # always takes the sub-width program. No election but the one asked
    # for: term 1 in every case
    coords = [
        BatchCoordinator(f"{tag}{i}", capacity=32, num_peers=3, nodes=reg,
                         active_set=active_set, election_timeout_s=100.0)
        for i in range(3)
    ]
    names = [f"eg{g}" for g in range(_EQ_GROUPS)]
    storage = []
    if logs == "wal":
        storage = [_wal_backed(c, str(tmp_path / c.name)) for c in coords]

    def log_of(i, n):
        if not storage:
            return None
        tables, wal, _sw, d = storage[i]
        return Log(n, os.path.join(d, "data", n), tables, wal)

    for i, c in enumerate(coords):
        c.add_groups([
            (n, f"{tag}cl{g}", [(n, k.name) for k in coords],
             SimpleMachine(lambda cm, s: (s * 31 + cm) % _EQ_MOD, 0),
             log_of(i, n))
            for g, n in enumerate(names)
        ])

    if driver == "started":
        for c in coords:
            c.start()

        def step():
            return False
    else:
        def step():
            return any([c.step_once() for c in coords])

    def drive(cond, what):
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            worked = step()
            if cond():
                return
            if not worked:
                time.sleep(0.001)
        raise AssertionError(f"timeout waiting for {what}")

    bursts, fold, count = _eq_sequence(7)
    try:
        coords[0].deliver_many(
            [((n, coords[0].name), ElectionTimeout(), None) for n in names])
        drive(lambda: all(coords[0].by_name[n].role == C.R_LEADER
                          for n in names), "leaders")
        for burst in bursts:
            for g, x in burst:
                coords[0].deliver(
                    (names[g], coords[0].name),
                    Command(kind=USR, data=x, reply_mode="noreply"), None)
            step()
        drive(lambda: all(c.by_name[n].machine_state == fold[g]
                          for c in coords for g, n in enumerate(names)),
              "every replica at the fold")
        # the leader's noop, then the group's commands, all in term 1
        want = [(count[g] + 1, 1) for g in range(_EQ_GROUPS)]
        drive(lambda: all(
            [c.by_name[n].log.last_index_term() for n in names] == want
            for c in coords), "every log at the same last index and term")
        assert coords[0].counters.get("ingress_ring_msgs") > 0
        for c in coords:
            assert c.sub_steps == (c.steps if active_set == "auto" else 0)
        if driver == "started":
            assert sum(c.counters.get("pipeline_steps") for c in coords) > 0
        else:
            assert all(c.counters.get("pipeline_steps") == 0 for c in coords)
    finally:
        for c in coords:
            c.stop()
        _close_storage(storage)


# ---------------------------------------------------------------------------
# failpoints during ring handoff


def _commit_n(cl, n, start=0):
    total = start
    deadline = time.monotonic() + 40
    while total < start + n and time.monotonic() < deadline:
        try:
            r, _ = api.process_command(cl.leader(), 1, timeout=5,
                                       retry_on_timeout=True)
            total = max(total, r)
        except Exception:  # noqa: BLE001 — mid-heal redirect/maybe
            time.sleep(0.05)
    assert total >= start + n, f"stalled at {total}"
    return total


@pytest.mark.parametrize("active_set", ["auto", "never"])
def test_fsync_failpoint_during_ring_handoff(tmp_path, active_set):
    """An fsync failure injected while commands stream through the
    ingress rings poisons the WAL un-acked, commits keep flowing on the
    quorum, and reopen() heals — on the sub-width and on the full-width
    mailbox, with the ring counters proving the rings actually carried
    the traffic."""
    cl = _WalCluster(tmp_path, "rf" + active_set[0], active_set=active_set)
    try:
        total = _commit_n(cl, 2)
        victim = cl.leader()[1]
        faults.arm("wal.fsync", ("raise", "eio"), ("one_shot",),
                   scope=victim)
        total = _commit_n(cl, 6, start=total)
        _t, wal, _sw, _d = cl.storage[victim]
        assert wal.counter.get("failures") >= 1, "failpoint never fired"
        await_(lambda: wal.reopen(), timeout=20, what="wal reopen")
        total = _commit_n(cl, 2, start=total)
        final = total
        await_(lambda: set(cl.states()) == {final},
               what="replicas converge post-heal")
        assert sum(
            c.counters.get("ingress_ring_msgs") for c in cl.coords
        ) > 0, "traffic never rode the rings"
    finally:
        cl.stop()


# ---------------------------------------------------------------------------
# event-driven idle: zero spurious wakeups


def test_idle_step_loop_blocks_with_zero_spurious_wakeups():
    """A started pipelined coordinator that has gone idle must park on
    the wake event — no timed polls — and every wakeup must find work:
    step_spurious_wakeups stays 0 across traffic AND a full idle
    second."""
    c = BatchCoordinator("zw0", capacity=4, num_peers=1,
                         tick_interval_s=30.0, detector_poll_s=5.0)
    sid = ("zg", "zw0")
    try:
        c.add_group("zg", "zwcl", [sid], SimpleMachine(lambda cm, s: s + cm, 0))
        c.start()
        c.deliver(sid, ElectionTimeout(), None)
        await_(lambda: c.by_name["zg"].role == C.R_LEADER, what="leader")
        for _ in range(3):
            api.process_command(sid, 1, timeout=10)
        assert c.by_name["zg"].machine_state == 3
        await_(lambda: c.counters.get("step_wakeups") > 0,
               what="the traffic woke the idle loop at least once")
        # let the pipeline tail settle (the last command's realisation
        # wake + durable-watermark pass can land just after the ack)
        def _settled():
            n = c.counters.get("step_wakeups")
            time.sleep(0.25)
            return n if c.counters.get("step_wakeups") == n else None
        before = await_(_settled, what="wakeups quiesce")
        # now fully idle: the loop must be parked, consuming nothing
        time.sleep(1.0)
        assert c.counters.get("step_wakeups") == before, \
            "idle coordinator woke without work arriving"
        assert c.counters.get("step_spurious_wakeups") == 0
        # a fresh command wakes it exactly as the protocol promises
        api.process_command(sid, 1, timeout=10)
        assert c.by_name["zg"].machine_state == 4
    finally:
        c.stop()


def test_election_storm_wider_than_lane_fully_elects():
    """Regression (found by the 10240-group bench soak): the rare-path
    election fan-out used to ship one ring item PER GROUP, so a storm
    wider than a peer's ingress lane overflowed it, the overflow was
    shed as lossy traffic, and the un-retried tail of the storm wedged
    mid-election (exactly lane-capacity groups elected). The fan-out
    now batches per destination across the whole rare loop — a storm
    4x wider than the lane must fully elect with zero drops."""
    reg = NodeRegistry()
    groups = 256
    coords = [
        BatchCoordinator(f"st{i}", capacity=groups, num_peers=3, nodes=reg,
                         idle_sleep_s=0, ingress_ring_slots=64)
        for i in range(3)
    ]
    members = lambda g: [(f"g{g}", f"st{i}") for i in range(3)]  # noqa: E731
    try:
        for c in coords:
            c.add_groups([
                (f"g{g}", f"stcl{g}", members(g),
                 SimpleMachine(lambda cm, s: s + cm, 0), None)
                for g in range(groups)
            ])
        coords[0].deliver_many([
            ((f"g{g}", "st0"), ElectionTimeout(), None)
            for g in range(groups)
        ])

        def step_all():
            return any([c.step_once() for c in coords])

        deadline = time.monotonic() + 60
        idle = 0
        while time.monotonic() < deadline and idle < 100:
            idle = 0 if step_all() else idle + 1
        n = sum(coords[0].by_name[f"g{g}"].role == C.R_LEADER
                for g in range(groups))
        assert n == groups, (
            f"only {n}/{groups} groups elected — the election storm "
            f"wedged on a full ingress lane "
            f"(drops: {[c.transport.dropped for c in coords]})"
        )
        assert all(c.transport.dropped == 0 for c in coords)
    finally:
        for c in coords:
            c.stop()


def test_egress_sender_thread_ships_the_fanout():
    """On a started pipelined cluster the AER/ack fan-out leaves
    through the dedicated sender thread, not the step loop."""
    coords = [
        BatchCoordinator(f"es{i}", capacity=4, num_peers=3,
                         election_timeout_s=0.15, detector_poll_s=0.05,
                         tick_interval_s=0.2)
        for i in range(3)
    ]
    ids = [("sg", f"es{i}") for i in range(3)]
    try:
        for c in coords:
            c.add_group("sg", "escl", ids,
                        SimpleMachine(lambda cm, s: s + cm, 0))
            c.start()
        coords[0].deliver(ids[0], ElectionTimeout(), None)
        await_(lambda: any(c.by_name["sg"].role == C.R_LEADER
                           for c in coords), what="leader")
        leader = next(ids[i] for i, c in enumerate(coords)
                      if c.by_name["sg"].role == C.R_LEADER)
        for _ in range(10):
            api.process_command(leader, 1, timeout=10)
        await_(lambda: all(c.by_name["sg"].machine_state == 10
                           for c in coords), what="replicas converge")
        assert sum(
            c.counters.get("egress_thread_batches") for c in coords
        ) > 0, "fan-out never used the sender thread"
        assert sum(
            c.counters.get("egress_thread_msgs") for c in coords
        ) > 0
    finally:
        for c in coords:
            c.stop()


# ---------------------------------------------------------------------------
# the WAL writer's hand-off: rows ``(uid, term, lo, hi)`` against the
# per-uid ``("written", term, seq)`` events that the bulk hook carried
# before them


def _handle_events(c, items):
    """``wal_notify_many`` as it stood when its items were ``(uid,
    ("written", term, seq))``: ``Log.handle_event`` per uid, then the
    same staging and deferred-ack release. The reference."""
    route_out = {}
    with c._wal_lock:
        for uid, evt in items:
            g = c.by_name.get(uid)
            if g is None:
                continue
            g.log.handle_event(evt)
            wi, wt = g.log.last_written()
            if c._staged_written.get(g.gid, 0) < wi:
                c._staged_written[g.gid] = wi
            if g.pending_ack is not None and wi >= g.pending_ack[1]:
                leader_sid, cover = g.pending_ack
                g.pending_ack = None
                ack = min(wi, cover)
                at = g.log.fetch_term(ack)
                route_out.setdefault(leader_sid[1], []).append(
                    (leader_sid,
                     AppendEntriesReply(g.term, True, ack + 1, ack,
                                        at if at is not None else wt),
                     (g.name, c.name)))
    for node_name, msgs in route_out.items():
        c._send_batch(node_name, msgs)


class _HandoffNode:
    """An unstarted coordinator over a flush()-driven Wal that keeps
    what the writer hands over instead of delivering it."""

    GROUPS = 6

    def __init__(self, tmp_path, name, bulk):
        self.c = c = BatchCoordinator(name, capacity=8, num_peers=3)
        self.tables = TableRegistry()
        self.events, self.rows, self.sent = [], [], []
        self.wal = Wal(str(tmp_path / name / "wal"), self.tables,
                       lambda uid, evt: self.events.append((uid, evt)),
                       threaded=False)
        if bulk:
            self.wal.notify_many = self.rows.extend
        c._send_batch = lambda node, msgs: self.sent.append((node, msgs))
        self.logs = {}
        for k in range(self.GROUPS):
            gname = f"h{k}"
            log = Log(gname, str(tmp_path / name / "data" / gname),
                      self.tables, self.wal)
            c.add_group(gname, f"hcl{k}",
                        [(gname, name), (gname, "peer1"), (gname, "peer2")],
                        SimpleMachine(lambda cm, s: s + cm, 0), log=log)
            self.logs[gname] = log

    def write(self, gname, first, terms):
        self.logs[gname].write(
            [Entry(first + k, t, Command(USR, 1)) for k, t in enumerate(terms)])

    def owe_ack(self, gname, cover):
        self.c.by_name[gname].pending_ack = ((gname, "peer1"), cover)

    def state(self):
        c = self.c
        return {
            "written": {n: log.last_written() for n, log in self.logs.items()},
            "pending_ack": {n: c.by_name[n].pending_ack for n in self.logs},
            "staged": dict(c._staged_written),
            # (one by one: the single door sends each as it is released;
            # the sender's own name differs between the two nodes)
            "acks": [(node, to, msg, frm[0]) for node, msgs in self.sent
                     for to, msg, frm in msgs],
        }


def _first_round(node):
    node.write("h0", 1, [1, 1, 1])
    node.owe_ack("h0", 3)          # released at what it covers
    node.write("h1", 1, [1] * 5)
    node.owe_ack("h1", 2)          # released below the watermark
    node.write("h2", 1, [1, 1])
    node.owe_ack("h2", 4)          # not covered yet: stays owed
    node.write("h3", 1, [1, 1, 1])
    node.owe_ack("h3", 3)
    node.write("h4", 1, [1, 1, 2])  # two terms: two events, two rows
    node.write("h5", 1, [3])
    node.wal.flush()
    # h3's suffix is rewritten before the hand-off arrives: its event
    # names an entry that is gone (the rewrite itself is still queued)
    node.write("h3", 3, [2])


def _second_round(node):
    node.write("h2", 3, [1, 1])
    node.write("h5", 2, [3, 3])
    node.wal.flush()


@pytest.mark.parametrize("door", ["notify_many", "notify"])
def test_rows_move_what_the_written_events_moved(tmp_path, door):
    """The same writes on two nodes; one is handed rows through ``door``,
    the other the events through the per-uid ``handle_event`` loop.
    After each round: the same durable watermarks, deferred acks (sent
    and still owed) and staged scatter; a stale row moves nothing."""
    new = _HandoffNode(tmp_path, "hn_new", bulk=door == "notify_many")
    old = _HandoffNode(tmp_path, "hn_old", bulk=False)
    try:
        for round_ in (_first_round, _second_round):
            round_(new)
            round_(old)
            ghost = ("ghost", ("written", 1, Seq.from_range(1, 9)))
            if door == "notify_many":
                assert new.events == [] and len(new.rows) > 1
                assert [r[:2] for r in new.rows] == \
                    [(uid, evt[1]) for uid, evt in old.events]
                new.c.wal_notify_many(new.rows + [("ghost", 1, 1, 9)])
            else:
                assert new.events == old.events
                for uid, evt in new.events + [ghost]:
                    new.c.wal_notify(uid, evt)
            _handle_events(old.c, old.events + [ghost])
            assert new.state() == old.state()
            events = len(old.events)
            assert new.c.counters.get("wal_notify_events") == events
            assert new.c.counters.get("wal_notify_batches") == \
                (1 if door == "notify_many" else events + 1)
            for node in (new, old):
                del node.events[:], node.rows[:]
                node.c.counters.put("wal_notify_events", 0)
                node.c.counters.put("wal_notify_batches", 0)
            if round_ is _first_round:
                got = new.state()
                assert got["written"]["h0"] == (3, 1)
                assert got["written"]["h3"] == (0, 0)  # the stale row
                assert got["pending_ack"]["h3"] is not None
                assert got["pending_ack"]["h2"] == (("h2", "peer1"), 4)
                assert got["written"]["h4"] == (3, 2)
                assert [(to[0], msg.last_index)
                        for _n, to, msg, _f in got["acks"]] == [("h0", 3), ("h1", 2)]
        got = new.state()
        assert got["written"]["h3"] == (3, 2) and got["pending_ack"]["h3"] is None
        assert got["written"]["h2"] == (4, 1) and got["pending_ack"]["h2"] is None
        assert got["staged"] == {new.c.by_name[n].gid: w[0]
                                 for n, w in got["written"].items()}
    finally:
        for node in (new, old):
            node.c.stop()
            node.wal.close()


def test_wal_notify_takes_a_seq_of_several_ranges_range_by_range(tmp_path):
    node = _HandoffNode(tmp_path, "hn_seq", bulk=False)
    try:
        node.write("h0", 1, [1] * 6)
        node.wal.flush()
        node.c.wal_notify("h0", ("written", 1, Seq([(1, 2), (4, 4)])))
        assert node.logs["h0"].last_written() == (4, 1)
        assert node.c.counters.get("wal_notify_events") == 2
        node.c.wal_notify("h0", ("written", 1, Seq.empty()))
        node.c.wal_notify("h0", ("written", 1, None))
        assert node.c.counters.get("wal_notify_batches") == 1
    finally:
        node.c.stop()
        node.wal.close()


def test_no_ack_ahead_of_its_fsync_under_a_short_switch_interval(tmp_path):
    """Three started nodes over threaded WAL writers, six groups, four
    callers, the interpreter handing the core over every 10 us: no
    follower's ack and no node's durable watermark ever names an index
    that its WAL had not written and synced first (what the writer
    hands over is noted on its way to the coordinator)."""
    names = [f"sw{i}" for i in range(3)]
    groups = [f"s{k}" for k in range(6)]
    coords, storage, durable, doors, ahead = [], [], {}, {}, []

    def tap(c, wal):
        mine = durable[c.name] = {}
        used = doors[c.name] = {"notify_many": 0, "notify": 0}

        def note(uid, hi):
            if hi > mine.get(uid, 0):
                mine[uid] = hi

        def many(rows):
            used["notify_many"] += 1
            for uid, _term, _lo, hi in rows:
                note(uid, hi)
            c.wal_notify_many(rows)

        def one(uid, evt):
            if evt[0] == "written":
                used["notify"] += 1
                note(uid, evt[2].last())
            c.wal_notify(uid, evt)

        def on_send(to, msg):
            if type(msg) is AppendEntriesReply and msg.success \
                    and msg.last_index > mine.get(to[0], 0):
                ahead.append((c.name, to[0], msg.last_index, mine.get(to[0], 0)))
            return False

        wal.notify, wal.notify_many = one, many
        c.transport.drop_fn = on_send

    interval = sys.getswitchinterval()
    stop = threading.Event()
    done = [0] * 4
    errors = []
    callers = []
    try:
        for n in names:
            c = BatchCoordinator(n, capacity=8, num_peers=3,
                                 election_timeout_s=0.5, detector_poll_s=0.1,
                                 tick_interval_s=0.2)
            storage.append(_wal_backed(c, str(tmp_path / n)))
            tap(c, storage[-1][1])
            coords.append(c)
        for c, (tables, wal, _sw, d) in zip(coords, storage):
            for g in groups:
                c.add_group(g, f"swcl_{g}", [(g, n) for n in names],
                            SimpleMachine(lambda cm, s: s + cm, 0),
                            log=Log(g, os.path.join(d, "data", g), tables, wal))
            c.start()
        coords[0].deliver_many(
            [((g, names[0]), ElectionTimeout(), None) for g in groups])
        await_(lambda: all(coords[0].by_name[g].role == C.R_LEADER
                           for g in groups), what="six leaders")

        def call(k):
            i = k
            while not stop.is_set():
                try:
                    api.process_command((groups[i % len(groups)], names[0]), 1,
                                        timeout=30)
                    done[k] += 1
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return
                i += 1

        sys.setswitchinterval(1e-5)
        callers = [threading.Thread(target=call, args=(k,), daemon=True)
                   for k in range(4)]
        for t in callers:
            t.start()
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            for c in coords:
                for g in groups:
                    wi = c.by_name[g].log.last_written()[0]
                    # (read after the watermark: the note comes first)
                    if wi > durable[c.name].get(g, 0):
                        ahead.append((c.name, g, "watermark", wi))
            time.sleep(0.005)
        stop.set()
        for t in callers:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in callers)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        for c in coords:
            c.stop()
        _close_storage(storage)
    assert errors == [] and ahead == []
    assert sum(done) >= 20, done
    # both doors of the hand-off ran under it
    assert all(d["notify_many"] > 0 for d in doors.values()), doors
    assert sum(d["notify"] for d in doors.values()) > 0, doors
    total = sum(done)
    assert sum(c.by_name[g].machine_state for g in groups
               for c in coords[:1]) == total
