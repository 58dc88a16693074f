"""WAL-death self-healing, pre-init floors, and chunked recovery
(VERDICT r1 item 5; reference: src/ra_server.erl:653-693,1918-1961,
src/ra_log_pre_init.erl:31-45, src/ra_log_wal.erl:393-470)."""

import os
import pickle
import time

import pytest

from ra_tpu import api, effects as fx, leaderboard
from ra_tpu.log.tables import TableRegistry
from ra_tpu.log.wal import Wal
from ra_tpu.machine import Machine, SimpleMachine
from ra_tpu.protocol import Entry
from ra_tpu.runtime.transport import registry
from ra_tpu.system import SystemConfig
from ra_tpu.utils.seq import Seq


def await_(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(0.02)
    raise AssertionError(f"timeout waiting for {what}")


@pytest.fixture
def cluster(tmp_path):
    leaderboard.clear()
    names = ["sh0", "sh1", "sh2"]
    for n in names:
        api.start_node(n, SystemConfig(name="sh", data_dir=str(tmp_path / n)),
                       election_timeout_s=0.15, tick_interval_s=0.1,
                       detector_poll_s=0.05)
    ids = [(f"s{i}", names[i]) for i in range(3)]
    started, failed = api.start_cluster(
        "shc", lambda: SimpleMachine(lambda c, s: s + c, 0), ids, timeout=20
    )
    assert failed == []
    yield ids, names
    for n in names:
        try:
            api.stop_node(n)
        except Exception:
            pass
    leaderboard.clear()


def _fail_wal(node):
    def boom():
        raise OSError("injected wal death")

    node.wal._sync = boom


def _heal_wal(node):
    try:
        del node.wal.__dict__["_sync"]
    except KeyError:
        pass


def test_wal_death_on_leader_abdicates_and_heals(cluster):
    ids, names = cluster
    r, leader = api.process_command(ids[0], 1, timeout=15)
    assert r == 1
    lnode = registry().get(leader[1])
    _fail_wal(lnode)
    # drive a write into the dead WAL: the leader must notice, abdicate,
    # and the cluster must keep accepting commands via a new leader
    total = 1
    deadline = time.monotonic() + 40
    new_leader = None
    while time.monotonic() < deadline:
        try:
            r, new_leader = api.process_command(
                ids[(ids.index(leader) + 1) % 3], 1, timeout=3,
                retry_on_timeout=True,
            )
            total = r
            if new_leader != leader:
                break
        except Exception:
            pass
    assert new_leader is not None and new_leader != leader, (leader, new_leader)
    assert lnode.wal.failed or lnode.wal.counter.to_dict()["failures"] >= 1
    # heal: un-inject, let the restart loop bring the WAL back
    _heal_wal(lnode)
    await_(lambda: not lnode.wal.failed, timeout=20, what="wal reopen")
    # the whole cluster (including the ex-leader) commits again
    r, _ = api.process_command(ids[0], 1, timeout=20, retry_on_timeout=True)
    deadline = time.monotonic() + 20
    ok = False
    while time.monotonic() < deadline and not ok:
        vals = []
        for sid in ids:
            try:
                vals.append(api.local_query(sid, lambda s: s)[1])
            except Exception:
                vals.append(None)
        ok = len(set(vals)) == 1 and vals[0] is not None
        time.sleep(0.05)
    assert ok, vals


def test_wal_death_on_follower_heals_and_catches_up(cluster):
    ids, names = cluster
    r, leader = api.process_command(ids[0], 1, timeout=15)
    follower = next(sid for sid in ids if sid != leader)
    fnode = registry().get(follower[1])
    _fail_wal(fnode)
    # quorum of 2 keeps committing while the follower's WAL is down
    total = r
    for i in range(5):
        total, _ = api.process_command(leader, 1, timeout=15)
    assert total == 6
    _heal_wal(fnode)
    await_(lambda: not fnode.wal.failed, timeout=20, what="wal reopen")
    # the healed follower converges (wal_up resend + replication)
    await_(
        lambda: api.local_query(follower, lambda s: s)[1] == total,
        timeout=30, what="follower caught up",
    )
    # and its copy is durable again: the follower's server is out of
    # await_condition
    srv = fnode.procs[follower[0]].server
    # (where the restarts tripped the intensity throttle, healing comes
    # once a 10 s window: the wait has to be longer than one)
    await_(lambda: srv.role in ("follower", "leader"), timeout=30,
           what="role restored")


def test_wal_chunked_recovery_spans_boundaries(tmp_path, monkeypatch):
    """Streaming recovery with a tiny chunk size: records (incl. ones
    bigger than a chunk) must parse across boundaries identically."""
    monkeypatch.setattr(Wal, "RECOVER_CHUNK", 64)
    events = []
    tables = TableRegistry()
    wal = Wal(str(tmp_path / "wal"), tables, lambda u, e: events.append((u, e)),
              threaded=False, sync_method="none")
    payloads = {}
    for i in range(1, 30):
        p = pickle.dumps("x" * (i * 17 % 200 + 100))  # > chunk for many
        payloads[i] = p
        wal.write("u1", i, 1, p)
    wal.flush()
    wal.close()

    tables2 = TableRegistry()
    wal2 = Wal(str(tmp_path / "wal"), tables2, lambda u, e: None,
               threaded=False, sync_method="none")
    mt = tables2.mem_table("u1")
    for i in range(1, 30):
        e = mt.get(i)
        assert e is not None, i
        assert pickle.dumps(e.cmd) == payloads[i]
    wal2.close()


def test_pre_init_skips_dead_indexes_on_boot(tmp_path):
    """Snapshot floors must be registered before WAL recovery so dead
    indexes are not resurrected into memtables (ra_log_pre_init)."""

    class SnapEvery5(Machine):
        def init(self, config):
            return 0

        def apply(self, meta, cmd, state):
            state += cmd
            if meta["index"] % 5 == 0:
                return state, state, [fx.ReleaseCursor(meta["index"], state)]
            return state, state, []

    leaderboard.clear()
    cfg = SystemConfig(name="pi", data_dir=str(tmp_path / "n"),
                       min_snapshot_interval=0)
    api.start_node("pi0", cfg, election_timeout_s=0.1, tick_interval_s=0.1)
    sid = ("p0", "pi0")
    api.start_cluster("pic", SnapEvery5, [sid], timeout=15)
    for i in range(12):
        api.process_command(sid, 1, timeout=15)
    node = registry().get("pi0")
    uid = node.directory.uid_of("p0")
    await_(lambda: node.tables.snapshot_index(uid) >= 5, what="snapshot")
    snap_idx = node.tables.snapshot_index(uid)
    api.stop_node("pi0")
    leaderboard.clear()

    # cold boot of the storage layer on the same dir: pre-init loads the
    # floor, recovery must skip everything at/below it
    from ra_tpu.runtime.node import RaNode

    node2 = RaNode("pi0", cfg)
    try:
        mt = node2.tables.mem_table(uid)
        for i in range(1, snap_idx + 1):
            assert mt.get(i) is None, f"dead index {i} resurrected"
        # the tail above the floor survives
        assert any(mt.get(i) is not None for i in range(snap_idx + 1, 14))
    finally:
        node2.stop()
        leaderboard.clear()


def test_sparse_records_survive_recovery_without_truncation(tmp_path):
    """A sparse (snapshot pre-phase) record replayed at boot must not
    clip higher memtable entries or rewind the gap watermark."""
    tables = TableRegistry()
    wal = Wal(str(tmp_path / "wal"), tables, lambda u, e: None,
              threaded=False, sync_method="none")
    # normal tail 101..105, then a sparse live entry at 50
    for i in range(101, 106):
        wal.write("u1", i, 2, pickle.dumps(i))
    wal.write("u1", 50, 1, pickle.dumps("live"), sparse=True)
    wal.flush()
    wal.close()

    tables2 = TableRegistry()
    # floor at 100 with 50 live (as pre-init would register)
    tables2.set_snapshot_state("u1", 100, Seq.from_list([50]))
    wal2 = Wal(str(tmp_path / "wal"), tables2, lambda u, e: None,
               threaded=False, sync_method="none")
    mt = tables2.mem_table("u1")
    for i in range(101, 106):
        assert mt.get(i) is not None, i  # tail survived the sparse replay
    assert mt.get(50) is not None
    # gap watermark did not regress: appending 106 is in-seq
    events = []
    wal2.notify = lambda u, e: events.append(e)
    wal2.write("u1", 106, 2, pickle.dumps(106))
    wal2.flush()
    assert any(e[0] == "written" for e in events), events
    assert not any(e[0] == "resend_write" for e in events), events
    wal2.close()


# ---------------------------------------------------------------------------
# supervised restart of log infra (VERDICT r2 item 6; reference:
# one_for_all ra_system_sup / ra_log_sup, src/ra_system_sup.erl:26-40,
# src/ra_log_sup.erl:20-63; WAL/segment-writer crash injection on live
# clusters, test/coordination_SUITE.erl:31-61)


def _kill_wal_thread(node):
    """Kill the WAL writer THREAD itself (a BaseException escapes the
    per-batch failure handler) — one-shot: the class impl is restored
    for the revived thread."""

    def boom(batch):
        del node.wal.__dict__["_write_batch"]
        raise SystemExit("injected wal thread death")

    node.wal._write_batch = boom


def _kill_segwriter_thread(node):
    def boom():
        del node.sw.__dict__["_drain"]
        raise SystemExit("injected segment-writer thread death")

    node.sw._drain = boom


def test_wal_thread_death_self_heals_without_operator(cluster):
    ids, names = cluster
    r, leader = api.process_command(ids[0], 1, timeout=15)
    lnode = registry().get(leader[1])
    _kill_wal_thread(lnode)
    # traffic drives the kill; the node's own supervisor must notice the
    # dead thread and run the wal_down -> reopen -> wal_up cycle with NO
    # operator action (no _heal_wal call anywhere in this test)
    deadline = time.monotonic() + 40
    while time.monotonic() < deadline:
        try:
            api.process_command(ids[0], 1, timeout=3, retry_on_timeout=True)
        except Exception:
            pass
        if (
            "_write_batch" not in lnode.wal.__dict__
            and lnode.wal.thread_alive()
            and not lnode.wal.failed
        ):
            break
    # the injection actually fired (boom deletes itself when it raises)
    assert "_write_batch" not in lnode.wal.__dict__, "kill never fired"
    await_(lambda: lnode.wal.thread_alive() and not lnode.wal.failed,
           timeout=20, what="wal thread revived by supervisor")
    # commits flow across the whole cluster again
    r, _ = api.process_command(ids[0], 1, timeout=20, retry_on_timeout=True)
    deadline = time.monotonic() + 20
    ok = False
    while time.monotonic() < deadline and not ok:
        vals = []
        for sid in ids:
            try:
                vals.append(api.local_query(sid, lambda s: s)[1])
            except Exception:
                vals.append(None)
        ok = len(set(vals)) == 1 and vals[0] is not None
        time.sleep(0.05)
    assert ok, vals


def test_log_infra_kill_loop_sustains_traffic(cluster):
    """The coordination-suite crash-injection shape: repeated WAL thread
    kills on rotating nodes mid-traffic; the cluster must sustain
    commits across every kill with zero manual healing."""
    ids, names = cluster
    api.process_command(ids[0], 1, timeout=15)
    for rnd in range(3):
        victim = registry().get(names[rnd % 3])
        _kill_wal_thread(victim)
        committed = 0
        deadline = time.monotonic() + 40
        while committed < 4 and time.monotonic() < deadline:
            try:
                api.process_command(ids[(rnd + 1) % 3], 1, timeout=3,
                                    retry_on_timeout=True)
                committed += 1
            except Exception:
                pass
        assert committed >= 4, f"round {rnd}: traffic stalled after kill"
        assert "_write_batch" not in victim.wal.__dict__, (
            f"round {rnd}: kill never fired"
        )
        await_(lambda: victim.wal.thread_alive() and not victim.wal.failed,
               timeout=30, what=f"round {rnd} wal revived")
    # every replica converges on one value — nothing was healed by hand
    deadline = time.monotonic() + 30
    ok = False
    while time.monotonic() < deadline and not ok:
        vals = []
        for sid in ids:
            try:
                vals.append(api.local_query(sid, lambda s: s)[1])
            except Exception:
                vals.append(None)
        ok = len(set(vals)) == 1 and vals[0] is not None
        time.sleep(0.05)
    assert ok, vals


def test_segment_writer_death_under_load_self_heals(tmp_path):
    """Kill the segment-writer thread while rollovers are pumping flush
    jobs at it; the supervisor revives it (queue intact — retained WAL
    files flush on the new thread) and the cluster keeps committing."""
    leaderboard.clear()
    names = ["swk0", "swk1", "swk2"]
    for n in names:
        api.start_node(
            n, SystemConfig(name="swk", data_dir=str(tmp_path / n),
                            wal_max_size_bytes=2048),
            election_timeout_s=0.15, tick_interval_s=0.1,
            detector_poll_s=0.05,
        )
    ids = [(f"w{i}", names[i]) for i in range(3)]
    try:
        started, failed = api.start_cluster(
            "swkc", lambda: SimpleMachine(lambda c, s: s + c, 0), ids,
            timeout=20,
        )
        assert failed == []
        r, leader = api.process_command(ids[0], 1, timeout=15)
        lnode = registry().get(leader[1])
        _kill_segwriter_thread(lnode)
        # 2 KB WAL files roll over constantly under this load, feeding
        # flush jobs into the (about to die) segment writer
        for _ in range(40):
            api.process_command(leader, 1, timeout=15, retry_on_timeout=True)
        # rollovers really fed the writer and the kill really fired
        assert "_drain" not in lnode.sw.__dict__, "segwriter kill never fired"
        await_(lambda: lnode.sw.thread_alive(), timeout=30,
               what="segment writer revived by supervisor")
        # it is actually flushing again (drains to idle), and commits
        # still flow
        await_(lambda: lnode.sw.wait_idle(0.2), timeout=30,
               what="segment writer drains")
        api.process_command(ids[1], 1, timeout=15, retry_on_timeout=True)
        assert lnode.sw.counter.to_dict()["mem_tables_flushed"] > 0
    finally:
        for n in names:
            try:
                api.stop_node(n)
            except Exception:
                pass
        leaderboard.clear()
