"""Pipelined wave loop + adaptive group-commit WAL + native write path.

Deterministic coverage for the concurrency the pipeline introduced
(docs/INTERNALS.md §15): failpoints fired DURING a pipelined handoff
must poison and recover on both mailbox shapes; the native
serialize+write+fsync batch path must be byte-identical with the pure-
Python fallback (and degrade to it when the .so is missing); the
adaptive group-commit policy must coalesce bursts but never delay an
idle write; the started loop must commit with each ticket realised by
the thread that dispatched it; and a coordinator offers two ways to
turn it, the started loop and ``step_once``, with no argument that
picks another.
"""

import os
import threading
import time

import pytest

from ra_tpu import api, faults, leaderboard, obs
from ra_tpu import native as ra_native
from ra_tpu.log.log import Log
from ra_tpu.log.segment_writer import SegmentWriter
from ra_tpu.log.tables import TableRegistry
from ra_tpu.log.wal import Wal
from ra_tpu.machine import SimpleMachine
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import Command, ElectionTimeout, USR
from ra_tpu.runtime.coordinator import BatchCoordinator
from ra_tpu.runtime.transport import NodeRegistry


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
# WAL-backed pipelined cluster scaffolding (started two-stage loops,
# decoupled durable acks — the production tpu_batch shape)


class _Cluster:
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
            d = str(tmp_path / n)
            tables = TableRegistry()
            sw = SegmentWriter(os.path.join(d, "data"), tables, c.wal_notify)
            sw.fault_scope = n
            wal = Wal(os.path.join(d, "wal"), tables, c.wal_notify,
                      segment_writer=sw)
            wal.notify_many = c.wal_notify_many
            wal.fault_scope = n
            self.storage[n] = (tables, wal, sw, d)
            self.coords.append(c)
        self.ids = [("pg", n) for n in self.names]
        for i, c in enumerate(self.coords):
            n = self.names[i]
            tables, wal, _sw, d = self.storage[n]
            log = Log("pg", os.path.join(d, "data", "pg"), tables, wal)
            c.add_group("pg", f"{tag}cl", self.ids,
                        SimpleMachine(lambda cm, s: s + cm, 0), log=log)
            c.start()
        self.coords[0].deliver(self.ids[0], ElectionTimeout(), None)
        await_(self._leader, what="leader elected")

    def _leader(self):
        for i, c in enumerate(self.coords):
            if c.by_name["pg"].role == C.R_LEADER:
                return self.ids[i]
        return None

    def leader(self):
        return await_(self._leader, what="leader")

    def states(self):
        return [c.by_name["pg"].machine_state for c in self.coords]

    def stop(self):
        for c in self.coords:
            c.stop()
        for n in self.names:
            tables, wal, sw, _d = self.storage[n]
            try:
                wal.close()
                sw.close()
            except Exception:  # noqa: BLE001
                pass


def _commit_n(cl, n, start=0):
    """Commit ``n`` increments through whatever leader is current;
    returns the final total. Retries around heal windows."""
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
def test_fsync_failure_during_pipelined_handoff(tmp_path, active_set):
    """An injected fsync failure while the pipelined loop is streaming
    commands must poison that WAL (no acks from the failed batch),
    commits must keep flowing on the surviving quorum, and reopen()
    must heal — on the sub-width mailbox and on the full-width one."""
    cl = _Cluster(tmp_path, "pf" + active_set[0], active_set=active_set)
    try:
        total = _commit_n(cl, 2)
        victim = cl.leader()[1]  # leader's WAL: worst case for acks
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
    finally:
        cl.stop()


def test_torn_write_during_pipelined_handoff(tmp_path):
    """A torn write mid-stream fails the batch un-acked; the memtable
    copy survives, resend-after-reopen makes it durable, and no acked
    command is lost."""
    cl = _Cluster(tmp_path, "pt")
    try:
        total = _commit_n(cl, 2)
        victim = cl.names[2]
        if cl.leader()[1] == victim:
            victim = cl.names[1]
        faults.arm("wal.write", ("torn", 0.4), ("one_shot",), scope=victim)
        total = _commit_n(cl, 6, start=total)
        _t, wal, _sw, _d = cl.storage[victim]
        assert wal.counter.get("failures") >= 1, "failpoint never fired"
        await_(lambda: wal.reopen(), timeout=20, what="wal reopen")
        total = _commit_n(cl, 2, start=total)
        final = total
        await_(lambda: set(cl.states()) == {final},
               what="replicas converge after torn write")
    finally:
        cl.stop()


def test_wal_thread_crash_during_pipelined_handoff(tmp_path):
    """A crashed WAL writer thread under pipelined traffic leaves the
    queue intact; revive_thread() drains it and the cluster converges
    with zero acked-command loss."""
    cl = _Cluster(tmp_path, "pc")
    try:
        total = _commit_n(cl, 2)
        victim = cl.names[1]
        if cl.leader()[1] == victim:
            victim = cl.names[2]
        faults.arm("wal.thread", ("crash",), ("one_shot",), scope=victim)
        _t, wal, _sw, _d = cl.storage[victim]
        total = _commit_n(cl, 6, start=total)
        await_(lambda: not wal.thread_alive(), timeout=20,
               what="writer thread died")
        wal.revive_thread()
        assert wal.thread_alive()
        total = _commit_n(cl, 2, start=total)
        final = total
        await_(lambda: set(cl.states()) == {final},
               what="replicas converge after thread crash")
    finally:
        cl.stop()


# ---------------------------------------------------------------------------
# native serialize+write+fsync path: byte parity + fallback


_RECORDS = [
    (1, 1, 3, 0, b"uid"),                                # uid-def
    (2, 1, 5, 2, b"payload-x"),                          # entry
    (100, 1, 6, [2, 2, 3], [b"a", b"bb", b"ccc" * 40]),  # run
    (3, 1, 9, 0, b""),                                   # trunc
    (4, 1, 11, 2, b"sparse"),                            # sparse
]


@pytest.mark.skipif(not ra_native.available(), reason="native lib absent")
def test_native_write_batch_bytes_match_python_framer(tmp_path):
    tables = TableRegistry()
    wal = Wal(str(tmp_path / "w"), tables, lambda u, e: None,
              threaded=False, native=False)
    py_bytes = wal._frame(_RECORDS)
    wal.close()
    path = str(tmp_path / "native.bin")
    fd = os.open(path, os.O_CREAT | os.O_WRONLY)
    try:
        w, fsync_ns = ra_native.write_batch(_RECORDS, fd, "datasync")
    finally:
        os.close(fd)
    disk = open(path, "rb").read()
    assert disk == py_bytes
    assert w == len(py_bytes)
    assert fsync_ns > 0


def _write_sequence(wal):
    import pickle

    wal.write("u1", 1, 1, pickle.dumps("a"))
    wal.write_run("u1", 2, [1, 1, 2], [pickle.dumps(x) for x in "bcd"])
    wal.write("u2", 1, 2, pickle.dumps("zz" * 100))
    wal.truncate_write("u1", 4)
    wal.write("u1", 4, 2, pickle.dumps("d2"))
    wal.write("u3", 7, 3, pickle.dumps("sp"), sparse=True)
    wal.flush()


@pytest.mark.skipif(not ra_native.available(), reason="native lib absent")
def test_native_and_python_wal_files_byte_identical(tmp_path):
    """The same logical write sequence through the native path and the
    pure-Python path must leave byte-identical WAL files on disk."""
    outs = {}
    for mode, use_native in (("nat", True), ("py", False)):
        tables = TableRegistry()
        wal = Wal(str(tmp_path / mode), tables, lambda u, e: None,
                  threaded=False, native=use_native)
        _write_sequence(wal)
        assert wal.counter.get("native_batches") == (1 if use_native else 0)
        path = wal._file_path
        wal.close()
        outs[mode] = open(path, "rb").read()
    assert outs["nat"] == outs["py"]
    assert len(outs["nat"]) > 4  # magic + records


def test_so_missing_falls_back_to_python(tmp_path, monkeypatch):
    """With the native lib unavailable the WAL must transparently use
    the Python framer — same events, valid file."""
    monkeypatch.setattr(ra_native, "_lib", None)
    monkeypatch.setattr(ra_native, "_tried", True)
    assert ra_native.available() is False
    assert ra_native.frame_batch(_RECORDS) is None
    assert ra_native.write_batch(_RECORDS, 0, "datasync") is None
    events = []
    tables = TableRegistry()
    wal = Wal(str(tmp_path / "fb"), tables,
              lambda u, e: events.append((u, e)), threaded=False)
    assert wal._native is False  # resolved at construction, off-path
    _write_sequence(wal)
    assert wal.counter.get("native_batches") == 0
    assert [e for _u, e in events if e[0] == "written"]
    path = wal._file_path
    wal.close()
    # the file recovers cleanly (prefix + truncate + rewrite honored)
    tables2 = TableRegistry()
    wal2 = Wal(str(tmp_path / "fb"), tables2, lambda u, e: None,
               threaded=False)
    assert wal2.last_writer_seq("u1") == 4
    assert tables2.mem_table("u1").get(4) is not None
    wal2.close()


def test_native_path_defers_to_python_when_failpoints_armed(tmp_path):
    """Armed wal.write/wal.fsync failpoints must route the batch through
    the Python path so injection semantics stay exact."""
    import pickle

    tables = TableRegistry()
    wal = Wal(str(tmp_path / "fp"), tables, lambda u, e: None,
              threaded=False)
    wal.write("u1", 1, 1, pickle.dumps("a"))
    faults.arm("wal.fsync", ("raise", "eio"), ("one_shot",))
    wal.flush()
    assert wal.failed  # the injected fsync error fired (Python path)
    assert wal.counter.get("native_batches") == 0 or not ra_native.available()
    wal.close()


# ---------------------------------------------------------------------------
# adaptive group commit


def test_group_commit_idle_write_never_waits(tmp_path):
    import pickle

    tables = TableRegistry()
    wal = Wal(str(tmp_path / "gc1"), tables, lambda u, e: None,
              threaded=False, group_commit_max_delay_s=0.05)
    wal.write("u1", 1, 1, pickle.dumps("a"))
    batch = wal._take_batch_locked()
    t0 = time.perf_counter()
    out = wal._coalesce(batch)
    dt = time.perf_counter() - t0
    assert out == batch
    assert dt < 0.02, f"idle write waited {dt * 1e3:.1f} ms on a timer"
    assert wal.counter.get("group_commit_waits") == 0
    assert wal.counter.get("group_commit_delay_us") == 0
    wal.close()


def test_group_commit_coalesces_arriving_burst(tmp_path):
    import pickle

    tables = TableRegistry()
    wal = Wal(str(tmp_path / "gc2"), tables, lambda u, e: None,
              threaded=False, group_commit_max_delay_s=0.2)
    wal.write("u1", 1, 1, pickle.dumps("a"))
    wal.write("u1", 2, 1, pickle.dumps("b"))
    batch = wal._take_batch_locked()
    assert len(batch) == 2
    wal._gc_rate.rate = 1e6  # a burst is in progress per the estimator

    def feeder():
        for i in range(3, 9):
            time.sleep(0.01)
            wal.write("u1", i, 1, pickle.dumps(f"x{i}"))

    t = threading.Thread(target=feeder)
    t.start()
    out = wal._coalesce(batch)
    t.join()
    assert len(out) >= 6, f"burst not coalesced: {len(out)} items"
    assert wal.counter.get("group_commit_waits") == 1
    assert wal.counter.get("group_commit_delay_us") > 0
    # one flush covers the coalesced burst
    wal._write_batch(out)
    assert wal.counter.get("batches") == 1
    wal.close()


def test_group_commit_bounded_by_max_delay(tmp_path):
    import pickle

    tables = TableRegistry()
    wal = Wal(str(tmp_path / "gc3"), tables, lambda u, e: None,
              threaded=False, group_commit_max_delay_s=0.04)
    wal.write("u1", 1, 1, pickle.dumps("a"))
    wal.write("u1", 2, 1, pickle.dumps("b"))
    batch = wal._take_batch_locked()
    wal._gc_rate.rate = 1e6

    stop = threading.Event()

    def feeder():  # keeps arriving past the bound
        i = 3
        while not stop.is_set():
            time.sleep(0.005)
            wal.write("u1", i, 1, pickle.dumps("y"))
            i += 1

    t = threading.Thread(target=feeder, daemon=True)
    t.start()
    t0 = time.perf_counter()
    wal._coalesce(batch)
    dt = time.perf_counter() - t0
    stop.set()
    t.join()
    assert dt < 0.2, f"coalescing overran its bound: {dt * 1e3:.1f} ms"
    wal.close()


# ---------------------------------------------------------------------------
# the two drivers: overlap proof, and no argument that picks a third


def _mk_coop(tag, nodes, **kw):
    reg = NodeRegistry()
    coords = [
        BatchCoordinator(f"{tag}{i}", capacity=8, num_peers=3, nodes=reg,
                         **kw)
        for i in range(3)
    ]
    ids = [("cg", f"{tag}{i}") for i in range(3)]
    for c in coords:
        c.add_group("cg", f"{tag}cl", ids,
                    SimpleMachine(lambda cm, s: s + cm, 0))
    return coords, ids


def _drive(coords, step, cond, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        worked = step()
        if cond():
            return
        if not worked:
            time.sleep(0.001)
    raise AssertionError("drive timeout")


REALISE_PHASES = ("ticket_queue", "egress_sync", "egress_lock_wait",
                  "device_step")


def test_started_loop_realises_each_ticket_on_the_dispatching_thread():
    """The started loop commits commands with one thread turning the
    wave: every ticket is realised by the thread that dispatched it,
    before that thread dispatches again, and every stepped ticket adds
    one sample to each sub-phase of ``device_step`` and to the phase."""
    coords = [
        BatchCoordinator(f"tp{i}", capacity=8, num_peers=3,
                         election_timeout_s=0.15,
                         detector_poll_s=0.05, tick_interval_s=0.2)
        for i in range(3)
    ]
    ids = [("tg", f"tp{i}") for i in range(3)]
    events = {c.name: [] for c in coords}
    # (a histogram outlives its coordinator: count from here)
    base = {c.name: {ph: c._wave_h[ph].n for ph in REALISE_PHASES}
            for c in coords}

    def watch(c):
        dispatch, realise = c._drain_and_dispatch, c._realise
        log = events[c.name]

        def drain_and_dispatch(pre):
            ticket = dispatch(pre)
            if ticket is not None:
                log.append(("d", threading.get_ident(), ticket))
            return ticket

        def realise_(ticket, lock=None):
            log.append(("r", threading.get_ident(), ticket))
            realise(ticket, lock)

        c._drain_and_dispatch = drain_and_dispatch
        c._realise = realise_

    try:
        for c in coords:
            c.add_group("tg", "tpcl", ids,
                        SimpleMachine(lambda cm, s: s + cm, 0))
            watch(c)
            c.start()
        coords[0].deliver(ids[0], ElectionTimeout(), None)
        await_(lambda: any(c.by_name["tg"].role == C.R_LEADER
                           for c in coords), what="leader")
        leader = next(ids[i] for i, c in enumerate(coords)
                      if c.by_name["tg"].role == C.R_LEADER)
        for _ in range(50):
            total, _ = api.process_command(leader, 1, timeout=10)
        assert total == 50
        await_(lambda: all(c.by_name["tg"].machine_state == 50
                           for c in coords), what="replicas converge")
        for c in coords:
            # the step thread, the sender and the detector: no other
            # thread of the coordinator's own
            assert c._egress_thread is None
            mine = sorted(t.name for t in threading.enumerate()
                          if t.name.endswith(c.name)
                          and t.name.startswith("ra-batch"))
            assert mine == sorted([f"ra-batch-{c.name}",
                                   f"ra-batch-det-{c.name}",
                                   f"ra-batch-snd-{c.name}"]), mine
    finally:
        for c in coords:
            c.stop()
    for c in coords:
        log = events[c.name]
        assert log and len(log) % 2 == 0
        step_ident = c._step_thread.ident
        stepped = 0
        for (d, d_ident, d_ticket), (r, r_ident, r_ticket) in zip(
                log[::2], log[1::2]):
            assert (d, r) == ("d", "r")
            assert r_ticket is d_ticket
            assert d_ident == r_ident == step_ident
            stepped += d_ticket.eg_packed is not None
        assert stepped > 0
        assert c.counters.get("pipeline_steps") == len(log) // 2
        for ph in REALISE_PHASES:
            assert c._wave_h[ph].n - base[c.name][ph] == stepped, \
                (c.name, ph)
        assert 0 <= c.counters.get("sync_found_ready") <= stepped


def test_a_turn_whose_program_finished_before_its_sync_is_counted_once():
    """``sync_found_ready``: the started turn reads the program's
    readiness once, before its egress sync. A program held to its end
    before the sync is counted, once a stepped ticket; ``step_once``
    books neither it nor ``pipeline_steps``."""
    c = BatchCoordinator("sfr0", capacity=4, num_peers=1)
    try:
        c.add_group("sg", "sfrcl", [("sg", "sfr0")],
                    SimpleMachine(lambda cm, s: s + cm, 0))
        c.deliver(("sg", "sfr0"), ElectionTimeout(), None)
        while c.step_once():
            pass
        assert c.by_name["sg"].role == C.R_LEADER
        assert c.counters.get("pipeline_steps") == 0
        assert c.counters.get("sync_found_ready") == 0
        dispatch = c._drain_and_dispatch
        stepped = []

        def dispatch_and_wait(pre):
            ticket = dispatch(pre)
            if ticket is not None and ticket.eg_packed is not None:
                ticket.eg_packed.block_until_ready()
                stepped.append(ticket)
            return ticket

        c._drain_and_dispatch = dispatch_and_wait
        for k in range(3):
            c.deliver(("sg", "sfr0"),
                      Command(kind=USR, data=1, reply_mode="noreply"), None)
            while c._turn(served=True):
                pass
        assert c.by_name["sg"].machine_state == 3
        assert len(stepped) >= 3
        assert c.counters.get("sync_found_ready") == len(stepped)
        assert c.counters.get("pipeline_steps") >= len(stepped)
    finally:
        c.stop()


def test_constructor_offers_no_path_selector():
    """One started loop, one test driver, one ingress: nothing in the
    constructor picks a control twin and no other driver is left."""
    import inspect

    params = inspect.signature(BatchCoordinator.__init__).parameters
    # (names in two pieces: a search of the tree for them finds nothing)
    for gone in ("pipeline", "rings", "egress" + "_async", "suffix_k"):
        assert gone not in params
    for gone in ("stage", "finish", "pipelined"):
        assert not hasattr(BatchCoordinator, "step_" + gone)
    assert callable(BatchCoordinator.step_once)
    assert callable(BatchCoordinator.start)


# ---------------------------------------------------------------------------
# the hand-off to the device (ISSUE 25; docs/INTERNALS.md §15): one host
# buffer, one entry into JAX per wave, host work first


JAX_ENTRIES = ("consensus_step_packed", "consensus_step_packed_scat",
               "consensus_step_packed_sub", "consensus_step_packed_sub_scat",
               "record_appended", "record_appended_runs", "record_written",
               "set_roles")


def _count_jax_entries(monkeypatch, entries):
    """Append ``(name, type of the last argument)`` to ``entries`` for
    every call the coordinator's module can make into JAX on the wave
    path: the jitted programs of ``ops/consensus`` and the two explicit
    transfers."""
    import jax
    import jax.numpy as jnp

    def counted(name, fn):
        def call(*a, **kw):
            entries.append((name, type(a[-1])))
            return fn(*a, **kw)
        return call

    for name in JAX_ENTRIES:
        monkeypatch.setattr(C, name, counted(name, getattr(C, name)))
    monkeypatch.setattr(jnp, "asarray", counted("jnp.asarray", jnp.asarray))
    monkeypatch.setattr(jax, "device_put",
                        counted("jax.device_put", jax.device_put))


def _coop_leader(tag, active_set):
    """Three cooperative coordinators, ``cg`` led by the first, driven
    until nothing is left to do (no role scatter pending)."""
    coords, ids = _mk_coop(tag, 3, active_set=active_set)

    def step():
        return any([c.step_once() for c in coords])

    coords[0].deliver(ids[0], ElectionTimeout(), None)
    _drive(coords, step, lambda: coords[0].by_name["cg"].role == C.R_LEADER)
    while step():
        pass
    return coords, ids, step


@pytest.mark.parametrize("active_set,variant", [
    ("always", "consensus_step_packed_sub_scat"),
    ("never", "consensus_step_packed_scat"),
])
def test_dispatching_pass_enters_jax_once_with_a_numpy_buffer(
        monkeypatch, active_set, variant):
    """Between ``_ingest`` and the ticket a wave with no pending role
    scatter makes ONE call into JAX: the jitted step, on the numpy
    buffer its builder filled (no ``jnp.asarray``, no ``device_put``),
    which goes back to the pool once the egress is synced."""
    import numpy as np

    coords, ids, step = _coop_leader("hb" + active_set[0], active_set)
    try:
        entries = []
        _count_jax_entries(monkeypatch, entries)
        steps0 = sum(c.steps for c in coords)
        for k in range(3):
            coords[0].deliver(
                ids[0], Command(kind=USR, data=1, reply_mode="noreply"),
                None)
            _drive(coords, step,
                   lambda: all(c.by_name["cg"].machine_state == k + 1
                               for c in coords))
        waves = sum(c.steps for c in coords) - steps0
        assert waves >= 6  # leader, followers and the leader again, 3 times
        assert entries == [(variant, np.ndarray)] * waves
        width = 8
        shape = (BatchCoordinator._NROWS + (active_set == "always"), width)
        for c in coords:
            assert c._mbox_pool and all(
                b.shape == shape for b in c._mbox_pool)
    finally:
        for c in coords:
            c.stop()


def test_served_waves_compile_nothing_after_warm_steps():
    """``warm_steps`` runs the shapes and the argument kinds the loop
    sends (numpy in), so election and traffic compile nothing: counted
    as the benchmark's set-up line counts compilations."""
    from benchmark import harness

    coords, ids = _mk_coop("wm", 3)
    try:
        # the full-width step, the 8-wide active-set step, set_roles at
        # 1, 2, 4 and 8 rows
        assert [c.warm_steps() for c in coords] == [6, 6, 6]
        stats = harness.CompileStats()

        def step():
            return any([c.step_once() for c in coords])

        coords[0].deliver(ids[0], ElectionTimeout(), None)
        _drive(coords, step,
               lambda: coords[0].by_name["cg"].role == C.R_LEADER)
        for k in range(4):
            coords[0].deliver(
                ids[0], Command(kind=USR, data=1, reply_mode="noreply"),
                None)
        _drive(coords, step,
               lambda: all(c.by_name["cg"].machine_state == 4
                           for c in coords))
        assert sum(c.sub_steps for c in coords) > 0
        assert stats.since()["compilations"] == 0
    finally:
        for c in coords:
            c.stop()


def test_idle_leaders_aer_leaves_before_the_device_hand_off(monkeypatch):
    """Host work first: a command that finds the loop idle is appended
    and its AppendEntries is on the sender's ring BEFORE the pass packs
    its mailbox and calls the step; ``aer_groups_before_pack`` counts
    the group. The fan-out's time rides the ticket into ``aer_fanout``
    and is in neither ``ingress_drain`` nor ``host_pack``."""
    from ra_tpu.protocol import AppendEntriesRpc

    coords, ids, step = _coop_leader("hf", "auto")
    leader = coords[0]
    try:
        # as a started loop has it: sends go to the sender thread's ring
        leader._egress_on = True
        on_ring_at_dispatch = []
        real = C.consensus_step_packed_sub_scat

        def dispatching(state, packed):
            leader._egress_rings.drain(on_ring_at_dispatch)
            return real(state, packed)

        monkeypatch.setattr(C, "consensus_step_packed_sub_scat", dispatching)
        before = leader.counters.get("aer_groups_before_pack")
        phases = {ph: obs.histograms().fetch(("wave", leader.name, ph))
                  for ph in ("ingress_drain", "host_pack")}
        wall0 = sum(h.total for h in phases.values())
        tickets = []
        realise = leader._realise

        def realising(ticket, lock=None):
            # what the pass left before its ticket is realised; what the
            # realisation sends goes inline, as without a sender thread
            tickets.append((ticket, sum(h.total for h in phases.values())))
            assert not ticket.aer_dirty
            leader._egress_on = False
            realise(ticket, lock)

        monkeypatch.setattr(leader, "_realise", realising)
        leader.deliver(
            ids[0], Command(kind=USR, data=7, reply_mode="noreply"), None)
        assert leader.step_once()
        (ticket, wall1), = tickets
        assert ticket.stepped and ticket.aer0_ns > 0
        assert leader.counters.get("aer_groups_before_pack") == before + 1
        # (a ring item: destination, messages, when it was published)
        assert sorted(node for node, _, _t in on_ring_at_dispatch) == [
            "hf1", "hf2"]
        for _node, msgs, t_pub in on_ring_at_dispatch:
            assert ticket.t_in < t_pub < ticket.t_pack
            (_to, rpc, frm), = msgs
            assert type(rpc) is AppendEntriesRpc and frm == ids[0]
            assert [e.cmd.data for e in rpc.entries] == [7]
        # each second of the pass in one phase: ingress_drain, then the
        # fan-out (booked when the ticket realises), then host_pack
        assert (wall1 - wall0 + ticket.aer0_ns
                == ticket.t_pack - ticket.t_in)
        for node, msgs, _t in on_ring_at_dispatch:
            leader._send_batch_inline(node, msgs)
        monkeypatch.undo()
        _drive(coords, step,
               lambda: all(c.by_name["cg"].machine_state == 7
                           for c in coords))
    finally:
        for c in coords:
            c.stop()


# ---------------------------------------------------------------------------
# stale detector triggers must not depose fresh leaders


def test_stale_election_timeout_is_dropped():
    reg = NodeRegistry()
    c = BatchCoordinator("se0", capacity=4, num_peers=3, nodes=reg,
                         detector_poll_s=10.0, election_timeout_s=100.0)
    sid = ("sg", "se0")
    try:
        c.add_group("sg", "secl", [sid],
                    SimpleMachine(lambda cm, s: s + cm, 0))
        g = c.by_name["sg"]
        # a trigger whose observation predates the group's last contact
        # (the stall-delayed detector shape) must be ignored
        stale = ElectionTimeout(armed_at=g.last_contact - 1.0)
        c.deliver(sid, stale, None)
        for _ in range(20):
            if not c.step_once():
                break
        assert g.role == C.R_FOLLOWER and g.term == 0
        # an explicit (unstamped) trigger always acts
        c.deliver(sid, ElectionTimeout(), None)
        for _ in range(50):
            c.step_once()
            if g.role == C.R_LEADER:
                break
        assert g.role == C.R_LEADER
    finally:
        c.stop()


def test_rare_messages_processed_exactly_once():
    """A turn's rare messages are handled by that turn and never seen
    again. Regression: a single explicit ElectionTimeout used to run TWO
    elections (term 2, a second pre-vote round piled onto a resolved
    one) when a pass kept an alias of the list it had routed into."""
    c = BatchCoordinator("ro0", capacity=4, num_peers=1, idle_sleep_s=0)
    try:
        c.add_group("rg", "rocl", [("rg", "ro0")],
                    SimpleMachine(lambda cm, s: s + cm, 0))
        g = c.by_name["rg"]
        c.deliver(("rg", "ro0"), ElectionTimeout(), None)
        c.step_once()
        for _ in range(10):
            c.step_once()
        assert g.role == C.R_LEADER
        assert g.term == 1, f"one timeout ran {g.term} elections"
    finally:
        c.stop()
