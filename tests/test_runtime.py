"""Runtime end-to-end tests: full threaded stack through the public API.

Three in-proc nodes, real storage, real scheduler/timers/transport —
the counterpart of the reference's single-BEAM "multi-node" integration
suites (ra_SUITE / ra_2_SUITE / coordination_SUITE scenarios:
process_command, pipeline, queries, failover by killing the leader,
restart recovery, membership changes, snapshot catch-up).
"""

import os
import threading
import time

import pytest

from ra_tpu import api, leaderboard
from ra_tpu.machine import Machine, SimpleMachine
from ra_tpu.runtime.transport import registry
from ra_tpu.system import SystemConfig


@pytest.fixture
def cluster(tmp_path, request):
    """Three nodes + a 3-member cluster running an adder machine.

    Indirect-parametrize with True to start the cluster lease-enabled
    (docs/INTERNALS.md §20); the default stays lease-off.
    """
    lease = bool(getattr(request, "param", False))
    leaderboard.clear()
    nodes = []
    for n in ("nA", "nB", "nC"):
        cfg = SystemConfig(name="t", data_dir=str(tmp_path))
        nodes.append(api.start_node(n, cfg, election_timeout_s=0.1,
                                    tick_interval_s=0.1, detector_poll_s=0.05))
    ids = [("s1", "nA"), ("s2", "nB"), ("s3", "nC")]
    started, failed = api.start_cluster(
        "add", lambda: SimpleMachine(lambda c, s: s + c, 0), ids,
        extra_cfg={"lease": True} if lease else None,
    )
    assert failed == []
    yield ids
    for n in ("nA", "nB", "nC"):
        try:
            api.stop_node(n)
        except Exception:
            pass
    leaderboard.clear()


def test_start_cluster_elects_leader(cluster):
    leader = api.wait_for_leader("add")
    assert leader in cluster
    mem, _ = api.members(cluster[0])
    assert sorted(mem) == sorted(cluster)


def test_process_command_roundtrip(cluster):
    reply, leader = api.process_command(cluster[0], 5)
    assert reply == 5
    reply, _ = api.process_command(cluster[1], 7)  # via any member (redirect)
    assert reply == 12


@pytest.mark.parametrize("cluster", [False, True], indirect=True,
                         ids=["lease-off", "lease-on"])
def test_queries(cluster):
    api.process_command(cluster[0], 10)
    # local query on every member converges
    deadline = time.monotonic() + 3
    while time.monotonic() < deadline:
        vals = [api.local_query(sid, lambda s: s)[1] for sid in cluster]
        if vals == [10, 10, 10]:
            break
        time.sleep(0.02)
    assert vals == [10, 10, 10]
    assert api.leader_query(cluster[0], lambda s: s * 2)[1] == 20
    assert api.consistent_query(cluster[0], lambda s: s + 1)[1] == 11


def test_pipeline_command_notifications(cluster):
    got = []
    evt = threading.Event()

    def sink(from_sid, corrs):
        got.extend(corrs)
        if len(got) >= 3:
            evt.set()

    leader = api.wait_for_leader("add")
    api.register_client(leader[1], "client1", sink)
    for i in range(3):
        assert api.pipeline_command(leader, 1, f"corr{i}", "client1")
    assert evt.wait(3), got
    assert sorted(c for c, _ in got) == ["corr0", "corr1", "corr2"]


def test_leader_failover_by_killing_leader(cluster):
    api.process_command(cluster[0], 1)
    leader = api.wait_for_leader("add")
    api.stop_server(leader)
    # failure detector + randomized election timers elect a new leader
    deadline = time.monotonic() + 5
    new_leader = None
    while time.monotonic() < deadline:
        cand = leaderboard.lookup_leader("add")
        if cand is not None and cand != leader and api._is_running(cand):
            new_leader = cand
            break
        time.sleep(0.02)
    assert new_leader is not None, "no failover"
    reply, _ = api.process_command(new_leader, 9)
    assert reply == 10  # state survived the failover


def test_restart_server_recovers_state(cluster):
    for i in range(5):
        api.process_command(cluster[0], 2)
    leader = api.wait_for_leader("add")
    follower = next(sid for sid in cluster if sid != leader)
    api.restart_server(follower)
    api.process_command(cluster[0], 1)
    deadline = time.monotonic() + 3
    while time.monotonic() < deadline:
        v = api.local_query(follower, lambda s: s)[1]
        if v == 11:
            break
        time.sleep(0.02)
    assert v == 11


def test_add_and_remove_member(cluster, tmp_path):
    api.process_command(cluster[0], 3)
    cfg = SystemConfig(name="t", data_dir=str(tmp_path))
    api.start_node("nD", cfg, election_timeout_s=0.1, tick_interval_s=0.1,
                   detector_poll_s=0.05)
    sid4 = ("s4", "nD")
    api.start_server(sid4, "add", SimpleMachine(lambda c, s: s + c, 0), [sid4])
    out = api.add_member(cluster[0], sid4)
    assert out[0] == "ok"
    deadline = time.monotonic() + 3
    while time.monotonic() < deadline:
        if api.local_query(sid4, lambda s: s)[1] == 3:
            break
        time.sleep(0.02)
    assert api.local_query(sid4, lambda s: s)[1] == 3
    mem, _ = api.members(cluster[0])
    assert sid4 in mem
    out = api.remove_member(cluster[0], sid4)
    assert out[0] == "ok"
    mem, _ = api.members(cluster[0])
    assert sid4 not in mem
    api.stop_node("nD")


def test_transfer_leadership(cluster):
    leader = api.wait_for_leader("add")
    target = next(sid for sid in cluster if sid != leader)
    # transfer_leadership refuses targets that are not provably caught
    # up (match_index + 1 == next_index), and the chosen follower may
    # not be in the commit quorum yet — retry until it catches up
    deadline = time.monotonic() + 5
    out = api.transfer_leadership(cluster[0], target)
    while out[0] != "ok" and time.monotonic() < deadline:
        time.sleep(0.05)
        out = api.transfer_leadership(cluster[0], target)
    assert out[0] == "ok", out
    deadline = time.monotonic() + 3
    while time.monotonic() < deadline:
        if leaderboard.lookup_leader("add") == target:
            break
        time.sleep(0.02)
    assert leaderboard.lookup_leader("add") == target
    reply, _ = api.process_command(target, 100)
    assert reply == 100


def test_key_metrics_and_overview(cluster):
    api.process_command(cluster[0], 1)
    leader = api.wait_for_leader("add")
    km = api.key_metrics(leader)
    assert km["state"] == "leader"
    assert km["commit_index"] >= 2
    ov = api.member_overview(cluster[0])
    assert ov["id"] == cluster[0]
    nov = api.overview("nA")
    assert "servers" in nov and nov["wal"]["writers"] >= 1


def test_snapshot_catchup_for_lagging_follower(tmp_path):
    """A stopped follower falls behind a snapshot-compacted leader and
    catches up via the chunked snapshot transfer."""
    from ra_tpu.effects import ReleaseCursor

    class SnappyAdder(Machine):
        def init(self, config):
            return 0

        def apply(self, meta, cmd, state):
            state += cmd
            effs = []
            if meta["index"] % 10 == 0:
                effs.append(ReleaseCursor(meta["index"], state))
            return state, state, effs

    leaderboard.clear()
    nodes = []
    for n in ("sA", "sB", "sC"):
        cfg = SystemConfig(name="snap", data_dir=str(tmp_path))
        cfg.min_snapshot_interval = 5
        nodes.append(api.start_node(n, cfg, election_timeout_s=0.1,
                                    tick_interval_s=0.1, detector_poll_s=0.05))
    ids = [("z1", "sA"), ("z2", "sB"), ("z3", "sC")]
    try:
        api.start_cluster("snapc", SnappyAdder, ids)
        leader = api.wait_for_leader("snapc")
        lagging = next(sid for sid in ids if sid != leader)
        api.stop_server(lagging)
        leader = api.wait_for_leader("snapc", timeout=5)
        for _ in range(30):
            api.process_command(leader, 1, timeout=5)
        # leader compacted below what the lagging follower has
        lsrv = registry().get(leader[1]).procs[leader[0]].server
        assert lsrv.log.snapshot_index_term() is not None
        api.restart_server(lagging)
        deadline = time.monotonic() + 8
        v = None
        while time.monotonic() < deadline:
            v = api.local_query(lagging, lambda s: s)[1]
            if v is not None and v >= 30:
                break
            time.sleep(0.05)
        assert v is not None and v >= 30, f"lagging follower stuck at {v}"
        lag_srv = registry().get(lagging[1]).procs[lagging[0]].server
        assert lag_srv.log.snapshot_index_term() is not None
    finally:
        for n in ("sA", "sB", "sC"):
            api.stop_node(n)
        leaderboard.clear()


def test_many_groups_share_node_infra(tmp_path):
    """200 single-member groups on one node: one WAL, one scheduler."""
    leaderboard.clear()
    cfg = SystemConfig(name="many", data_dir=str(tmp_path))
    node = api.start_node("nM", cfg, election_timeout_s=0.1, tick_interval_s=0.2)
    try:
        G = 200
        for g in range(G):
            sid = (f"g{g}", "nM")
            api.start_server(sid, f"grp{g}", SimpleMachine(lambda c, s: s + c, 0), [sid])
            api.trigger_election(sid)
        for g in range(G):
            api.wait_for_leader(f"grp{g}", timeout=5)
        t0 = time.monotonic()
        for g in range(G):
            reply, _ = api.process_command((f"g{g}", "nM"), g)
            assert reply == g
        dt = time.monotonic() - t0
        # single shared WAL carried all groups
        assert node.wal.counter.get("writes") >= 2 * G
        assert node.wal.counter.get("batches") <= node.wal.counter.get("writes")
    finally:
        api.stop_node("nM")
        leaderboard.clear()


# ---------------------------------------------------------------------------
# adaptive failure detection (reference: aten) + monitor component routing
# (reference: ra_monitors)


def test_phi_accrual_detector_adapts():
    from ra_tpu.detector import PhiAccrualDetector

    d = PhiAccrualDetector(threshold=8.0)
    t = 100.0
    # steady 0.1s heartbeats
    for i in range(30):
        d.heartbeat("n1", now=t + i * 0.1)
    t2 = t + 30 * 0.1
    assert not d.suspect("n1", now=t2 + 0.1)  # one missed beat: fine
    assert d.suspect("n1", now=t2 + 5.0)  # long silence: suspect
    # a jittery node with 1s +/- heartbeats is NOT suspected at 2s
    tj = 200.0
    import random

    rng = random.Random(1)
    for i in range(30):
        tj += 0.5 + rng.random()
        d.heartbeat("n2", now=tj)
    assert not d.suspect("n2", now=tj + 2.0)
    assert d.suspect("n2", now=tj + 30.0)
    # unseen node: no evidence, no suspicion
    assert not d.suspect("ghost")
    d.forget("n1")
    assert not d.suspect("n1", now=t2 + 99)


def test_monitor_down_routed_by_component(tmp_path):
    """DOWNs dispatch to the registered component: machine gets the
    builtin command, aux gets a cast, snapshot senders a failure."""
    import time as _time

    from ra_tpu import api, leaderboard
    from ra_tpu.machine import Machine
    from ra_tpu.runtime.transport import registry
    from ra_tpu.system import SystemConfig

    seen = {"machine": [], "aux": []}

    class MonMachine(Machine):
        def init(self, config):
            return 0

        def apply(self, meta, cmd, state):
            if isinstance(cmd, tuple) and cmd and cmd[0] == "down":
                seen["machine"].append(cmd[1])
            return state, None, []

        def handle_aux(self, role, kind, cmd, aux_state, intern):
            if isinstance(cmd, tuple) and cmd and cmd[0] == "down":
                seen["aux"].append(cmd[1])
            return None, aux_state

    leaderboard.clear()
    api.start_node("mdA", SystemConfig(name="md", data_dir=str(tmp_path)),
                   election_timeout_s=0.1, tick_interval_s=0.05)
    sid = ("md1", "mdA")
    api.start_server(sid, "mdc", MonMachine(), (sid,))
    api.trigger_election(sid)
    api.process_command(sid, 1, timeout=10)
    node = registry().get("mdA")
    node.monitors.add(sid, "process", ("tgt1", "mdA"), "machine")
    node.monitors.add(sid, "process", ("tgt2", "mdA"), "aux")
    node.on_proc_down(("tgt1", "mdA"))
    node.on_proc_down(("tgt2", "mdA"))
    deadline = _time.monotonic() + 10
    while _time.monotonic() < deadline and not (seen["machine"] and seen["aux"]):
        _time.sleep(0.05)
    assert seen["machine"] == [("tgt1", "mdA")]
    assert seen["aux"] == [("tgt2", "mdA")]
    api.stop_node("mdA")
    leaderboard.clear()


def test_bg_work_per_server_ordering(tmp_path):
    """Background jobs for one server run strictly in order (snapshot
    writes / compactions must not reorder); different servers proceed
    concurrently (reference: per-server ra_worker)."""
    import threading
    import time as _time

    from ra_tpu import api, leaderboard, effects as fx
    from ra_tpu.runtime.transport import registry
    from ra_tpu.system import SystemConfig

    leaderboard.clear()
    api.start_node("bgA", SystemConfig(name="bg", data_dir=str(tmp_path)),
                   election_timeout_s=0.1, tick_interval_s=0.05)
    node = registry().get("bgA")
    order = []
    gate = threading.Event()

    def slow_a():
        _time.sleep(0.3)
        order.append("a1")

    def fast_a():
        order.append("a2")

    def job_b():
        order.append("b")
        gate.set()

    node.submit_bg(fx.BgWork(slow_a), key="uid_a")
    node.submit_bg(fx.BgWork(fast_a), key="uid_a")  # must wait for slow_a
    node.submit_bg(fx.BgWork(job_b), key="uid_b")   # independent: no wait
    assert gate.wait(5)
    deadline = _time.monotonic() + 5
    while _time.monotonic() < deadline and len(order) < 3:
        _time.sleep(0.02)
    assert order.index("b") < order.index("a1"), order  # b didn't queue behind a
    assert order.index("a1") < order.index("a2"), order  # per-key order kept
    # errors route to err_fn without killing the queue
    errs = []
    done = threading.Event()
    node.submit_bg(fx.BgWork(lambda: 1 / 0, errs.append), key="uid_a")
    node.submit_bg(fx.BgWork(lambda: done.set()), key="uid_a")
    assert done.wait(5)
    assert len(errs) == 1 and isinstance(errs[0], ZeroDivisionError)
    api.stop_node("bgA")
    leaderboard.clear()


def test_low_priority_commands_redirected_on_leadership_loss(cluster):
    """ADVICE r2 (low): a buffered low-priority command holding a reply
    future must hear ('redirect', leader) when leadership is lost, not
    hang until its caller times out."""
    from ra_tpu.protocol import Command, USR

    leader = api.wait_for_leader("add")
    node = registry().get(leader[1])
    proc = node.procs[leader[0]]
    fut = api.Future()
    # buffer a low directly (the drain runs only between main-queue
    # batches; state transitions clear the lane)
    proc._low_q.append(Command(kind=USR, data=1, reply_mode="await_consensus",
                               from_ref=fut, priority="low"))
    proc._on_state_enter("follower")
    out = fut.result(2)
    assert out[0] == "redirect"
