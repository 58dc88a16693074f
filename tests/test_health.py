"""Cluster health plane tests (ISSUE 7): the vectorized per-group
scanner's anomaly state machine with hysteresis, nemesis-driven
end-to-end classification (induced stuck and flapping groups on both
backends), the sharded-mesh scan smoke, the single-fetch-per-tick
discipline counter, and the phi-accrual detector's exported gauges
and transition events."""

import time

import numpy as np
import pytest

from ra_tpu import api, counters, faults, health, leaderboard, obs
from ra_tpu.detector import PhiAccrualDetector
from ra_tpu.li import VectorLeakyIntegrator
from ra_tpu.machine import SimpleMachine
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import Command, ElectionTimeout, USR
from ra_tpu.runtime.coordinator import BatchCoordinator
from ra_tpu.system import SystemConfig


def await_(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(0.02)
    raise AssertionError(f"timeout waiting for {what}")


def adder():
    return SimpleMachine(lambda cmd, s: s + cmd, 0)


# ---------------------------------------------------------------------------
# scanner unit tests (synthetic scans, no cluster)


def _scan(sc, now, slots, *, role=None, term=None, applied=None,
          commit=None, last=None, gap=None, leader=None):
    n = len(slots)
    z = lambda v: np.full(n, v, np.int64)  # noqa: E731
    sc.scan(
        now, slots,
        np.asarray(role if role is not None else z(0), np.int8),
        np.asarray(term if term is not None else z(1)),
        np.asarray(applied if applied is not None else z(0)),
        np.asarray(commit if commit is not None else z(0)),
        np.asarray(last if last is not None else z(0)),
        np.asarray(gap if gap is not None else z(0)),
        np.asarray(leader if leader is not None else z(0)),
    )


def test_scanner_stuck_detection_and_hysteresis_exit():
    sc = health.HealthScanner("hu1", capacity=4)
    s = np.array([sc.ensure("g0", "cl"), sc.ensure("g1", "cl")])
    now = 100.0
    _scan(sc, now, s, applied=[5, 5], commit=[5, 5], last=[5, 5])
    # g0 freezes with pending work; g1 stays clean
    for _ in range(sc.cfg.stuck_ticks + 1):
        now += 1
        _scan(sc, now, s, applied=[5, 5], commit=[9, 5], last=[9, 5])
    rows = {r["group"]: r for r in sc.rows()}
    assert rows["g0"]["state"] == "stuck"
    assert rows["g1"]["state"] == "quiet"
    assert sc.counters.get("health_stuck") == 1
    # one scan of recovery is NOT enough to clear (clear_ticks
    # hysteresis) ...
    now += 1
    _scan(sc, now, s, applied=[9, 5], commit=[9, 5], last=[9, 5])
    assert {r["group"]: r["state"] for r in sc.rows()}["g0"] == "stuck"
    # ... sustained calm is
    for _ in range(sc.cfg.clear_ticks):
        now += 1
        _scan(sc, now, s, applied=[9, 5], commit=[9, 5], last=[9, 5])
    assert {r["group"]: r["state"] for r in sc.rows()}["g0"] == "quiet"
    assert sc.counters.get("health_transitions") == 2


def test_scanner_progressing_group_under_load_stays_quiet():
    """Steady load means a nonzero instantaneous backlog at every scan;
    a group APPLYING through it must never classify stuck."""
    sc = health.HealthScanner("hu2", capacity=2)
    s = np.array([sc.ensure("g0", "cl")])
    now, applied = 10.0, 0
    for _ in range(10):
        now += 1
        applied += 50
        _scan(sc, now, s, role=[3], applied=[applied],
              commit=[applied + 5], last=[applied + 10])
    rows = sc.rows()
    assert rows[0]["state"] == "quiet"
    assert rows[0]["commit_rate"] > 0


def test_scanner_flapping_enter_and_exit():
    sc = health.HealthScanner("hu3", capacity=2)
    s = np.array([sc.ensure("g0", "cl")])
    now, term = 5.0, 1
    _scan(sc, now, s, term=[term])
    # term bumps every scan: churn EWMA climbs past churn_enter
    for _ in range(6):
        now += 1
        term += 1
        _scan(sc, now, s, term=[term])
    assert sc.rows()[0]["state"] == "flapping"
    assert sc.rows()[0]["churn"] > sc.cfg.churn_enter
    # a single calm scan holds the state (hysteresis)...
    now += 1
    _scan(sc, now, s, term=[term])
    assert sc.rows()[0]["state"] == "flapping"
    # ...sustained calm decays churn below churn_exit and clears
    for _ in range(12):
        now += 1
        _scan(sc, now, s, term=[term])
    assert sc.rows()[0]["state"] == "quiet"


def test_scanner_lagging_and_severity_order():
    sc = health.HealthScanner("hu4", capacity=2)
    s = np.array([sc.ensure("g0", "cl")])
    now = 1.0
    _scan(sc, now, s)
    # large follower match gap while still progressing -> lagging
    for k in range(3):
        now += 1
        _scan(sc, now, s, role=[3], applied=[10 * (k + 1)],
              commit=[10 * (k + 1)], last=[10 * (k + 1)],
              gap=[sc.cfg.lag_enter + 10])
    assert sc.rows()[0]["state"] == "lagging"
    # stuck outranks lagging once progress also freezes
    for _ in range(sc.cfg.stuck_ticks + 1):
        now += 1
        _scan(sc, now, s, role=[3], applied=[30], commit=[90], last=[90],
              gap=[sc.cfg.lag_enter + 10])
    assert sc.rows()[0]["state"] == "stuck"


def test_scanner_leader_stickiness_resets_on_leader_change():
    sc = health.HealthScanner("hu5", capacity=2)
    s = np.array([sc.ensure("g0", "cl")])
    _scan(sc, 10.0, s, leader=[1])
    _scan(sc, 20.0, s, leader=[1])
    age_same = health.scanners  # keep flake-proof: read via rows
    row = sc.rows()[0]
    assert row["leader_age_s"] >= 0  # wall-clock based, just sane
    since_before = float(sc.leader_since[s[0]])
    _scan(sc, 30.0, s, leader=[2])  # leader moved
    assert float(sc.leader_since[s[0]]) == 30.0 != since_before
    del age_same


def test_scanner_slot_recycling_and_growth():
    sc = health.HealthScanner("hu6", capacity=2)
    a = sc.ensure("a", "cl")
    b = sc.ensure("b", "cl")
    c = sc.ensure("c", "cl")  # forces growth past capacity 2
    assert len({a, b, c}) == 3 and sc.capacity >= 3
    sc.release("b")
    assert sc.ensure("d", "cl") == b  # freed slot recycled
    assert {r["group"] for r in sc.rows() if r["group"] != "d"} <= {"a", "c"}


def test_recycled_slot_does_not_inherit_previous_group_state():
    """A new group landing on a dead flapper's slot must start from
    zero EWMAs — not classify flapping on its first scan."""
    sc = health.HealthScanner("hu7", capacity=2)
    s = np.array([sc.ensure("old", "cl")])
    term = 1
    _scan(sc, 1.0, s, term=[term])
    for k in range(6):
        term += 1
        _scan(sc, 2.0 + k, s, term=[term])
    assert sc.rows()[0]["state"] == "flapping"
    assert float(sc.churn[s[0]]) > 0
    sc.release("old")
    slot = sc.ensure("new", "cl")
    assert slot == s[0]  # same slot recycled
    assert float(sc.churn[slot]) == 0.0
    assert float(sc.li.rate[slot]) == 0.0
    _scan(sc, 10.0, np.array([slot]), term=[100])
    row = sc.rows()[0]
    assert row["group"] == "new"
    assert row["state"] == "quiet" and row["churn"] == 0.0
    assert row["commit_rate"] == 0.0


def test_vector_leaky_integrator_matches_scalar():
    from ra_tpu.li import LeakyIntegrator

    v = VectorLeakyIntegrator(4, alpha=0.3)
    s0 = LeakyIntegrator(alpha=0.3)
    slots = np.array([1, 3])
    for counts in ([10, 2], [5, 0], [7, 9]):
        v.sample(slots, np.asarray(counts, np.float64), 2.0)
        s0.sample(counts[0], 2.0)
    assert v.rate[1] == pytest.approx(s0.rate)
    assert v.rate[0] == 0.0  # untouched slot
    v.grow(16)
    assert len(v.rate) == 16 and v.rate[3] > 0


def test_health_config_rejects_inverted_hysteresis():
    with pytest.raises(ValueError):
        health.HealthConfig(lag_enter=10, lag_exit=10)
    with pytest.raises(ValueError):
        health.HealthConfig(churn_enter=0.1, churn_exit=0.5)


# ---------------------------------------------------------------------------
# phi-accrual detector export (satellite)


def test_detector_exports_gauges_and_transition_events():
    det = PhiAccrualDetector(threshold=2.0, owner="dtn")
    try:
        t = 100.0
        for k in range(10):
            det.heartbeat("peer1", now=t + k * 0.1)
        assert det.suspect("peer1", now=t + 1.0) is False
        g = counters.fetch(("phi", "dtn", "peer1"))
        assert g is not None
        assert g.get("phi_suspect") == 0 and g.get("phi_intervals") > 0
        # silence far past the learned cadence -> suspect flip + event
        assert det.suspect("peer1", now=t + 60.0) is True
        assert g.get("phi_suspect") == 1 and g.get("phi_milli") > 2000
        evts = [e for e in obs.flight_recorder().events()
                if e["kind"] == "suspect" and e["node"] == "dtn"]
        assert evts and "peer1" in evts[-1]["detail"]
        # fresh evidence flips it back (unsuspect event)
        det.heartbeat("peer1", now=t + 60.1)
        assert any(
            e["kind"] == "unsuspect" and e["node"] == "dtn"
            for e in obs.flight_recorder().events()
        )
        assert g.get("phi_suspect") == 0
        ov = det.overview(now=t + 60.2)
        assert "peer1" in ov and ov["peer1"]["suspect"] is False
        det.forget("peer1")
        assert counters.fetch(("phi", "dtn", "peer1")) is None
    finally:
        det.close()


def test_detector_publish_refreshes_all_peers():
    det = PhiAccrualDetector(threshold=2.0, owner="dtp")
    try:
        for peer in ("a", "b"):
            for k in range(6):
                det.heartbeat(peer, now=50.0 + k * 0.1)
        det.publish(now=51.0)
        for peer in ("a", "b"):
            assert counters.fetch(("phi", "dtp", peer)) is not None
    finally:
        det.close()


# ---------------------------------------------------------------------------
# nemesis-driven end-to-end classification: batch backend


@pytest.fixture
def health_coords():
    leaderboard.clear()
    coords = [
        BatchCoordinator(
            f"hn{i}", capacity=8, num_peers=3, election_timeout_s=0.1,
            detector_poll_s=0.05, tick_interval_s=0.1,
        )
        for i in range(3)
    ]
    for c in coords:
        c.start()
    yield coords
    for c in coords:
        c.transport.unblock_all()
        c.stop()
    leaderboard.clear()


def _state_of(node, group):
    sc = health.scanners().get(node)
    if sc is None:
        return None
    for r in sc.rows():
        if r["group"] == group:
            return r["state"]
    return None


def test_batch_nemesis_stuck_group_detected_and_clears(health_coords):
    """An isolated leader with accepted-but-uncommittable commands must
    classify stuck within a bounded number of ticks; healing the
    partition drains it back to quiet (hysteresis exit)."""
    coords = health_coords
    members = [("sg", f"hn{i}") for i in range(3)]
    for c in coords:
        c.add_group("sg", "sgcl", members, adder())
    coords[0].deliver(("sg", "hn0"), ElectionTimeout(), None)
    await_(lambda: coords[0].by_name["sg"].role == C.R_LEADER,
           what="hn0 leader")
    api.process_command(("sg", "hn0"), 1)
    # isolate the leader, then feed it commands it can never commit
    for other in ("hn1", "hn2"):
        coords[0].transport.block("hn0", other)
        next(c for c in coords if c.name == other).transport.block(
            other, "hn0"
        )
    mark = obs.flight_recorder().events(last=1)
    seq0 = mark[0]["seq"] if mark else -1
    for k in range(4):
        coords[0].deliver(
            ("sg", "hn0"), Command(kind=USR, data=1, reply_mode="noreply"),
            None,
        )
    # bounded detection: stuck_ticks(3) scans at 0.1s tick + slack
    await_(lambda: _state_of("hn0", "sg") == "stuck", timeout=15,
           what="stuck classification on the isolated leader")
    assert any(
        e["kind"] == "health_transition" and e["group"] == "sg"
        and e["node"] == "hn0" and "->stuck" in str(e["detail"])
        and e["seq"] > seq0
        for e in obs.flight_recorder().events()
    )
    # the single-fetch-per-tick discipline held throughout (fetches
    # incr at tick start, scans at tick end: reading while one tick is
    # in flight may legitimately see fetches one ahead)
    sc = health.scanners()["hn0"]
    scans = sc.counters.get("health_scans")
    fetches = sc.counters.get("health_fetches")
    assert scans > 0 and 0 <= fetches - scans <= 1, (scans, fetches)
    # heal -> the group must eventually classify quiet again
    for c in coords:
        c.transport.unblock_all()
    await_(lambda: _state_of("hn0", "sg") == "quiet", timeout=30,
           what="stuck group cleared after heal")


def test_batch_nemesis_flapping_group_detected(health_coords):
    """Partition-churn-style election storms (terms bumping scan after
    scan) must classify flapping, then decay back to quiet."""
    coords = health_coords
    members = [("fg", f"hn{i}") for i in range(3)]
    for c in coords:
        c.add_group("fg", "fgcl", members, adder())
    coords[0].deliver(("fg", "hn0"), ElectionTimeout(), None)
    await_(lambda: any(
        c.by_name["fg"].role == C.R_LEADER for c in coords
    ), what="initial leader")

    deadline = time.monotonic() + 20
    k = 0
    while time.monotonic() < deadline:
        if _state_of("hn0", "fg") == "flapping":
            break
        coords[k % 3].deliver(("fg", f"hn{k % 3}"), ElectionTimeout(), None)
        k += 1
        time.sleep(0.08)
    assert _state_of("hn0", "fg") == "flapping", (
        f"never classified flapping (state={_state_of('hn0', 'fg')}, "
        f"term={coords[0].by_name['fg'].term})"
    )
    assert any(
        e["kind"] == "health_transition" and e["group"] == "fg"
        and "->flapping" in str(e["detail"])
        for e in obs.flight_recorder().events()
    )
    # churn stops -> EWMA decays through churn_exit -> quiet
    await_(lambda: _state_of("hn0", "fg") == "quiet", timeout=30,
           what="flapping group settled")


def test_sharded_mesh_health_scan_smoke():
    """MULTICHIP dryrun: the health scan's single device fetch works
    with GroupState sharded over the 8-device virtual mesh."""
    import jax
    from jax.sharding import Mesh
    from ra_tpu.runtime.transport import NodeRegistry

    leaderboard.clear()
    mesh = Mesh(np.array(jax.devices("cpu")[:8]), ("groups",))
    G = 16
    c = BatchCoordinator("hmsh", capacity=G, num_peers=3,
                         nodes=NodeRegistry(), mesh=mesh)
    try:
        c.add_groups([
            (f"g{g}", f"cl{g}", [(f"g{g}", "hmsh")], adder())
            for g in range(G)
        ])
        c.deliver_many(
            [((f"g{g}", "hmsh"), ElectionTimeout(), None) for g in range(G)]
        )
        for _ in range(200):
            if not c.step_once():
                break
        assert all(
            c.by_name[f"g{g}"].role == C.R_LEADER for g in range(G)
        ), "single-member self-election incomplete"
        c.deliver_many([
            ((f"g{g}", "hmsh"),
             Command(kind=USR, data=g + 1, reply_mode="noreply"), None)
            for g in range(G)
        ])
        for _ in range(200):
            if not c.step_once():
                break
        now = time.monotonic()
        c._health_scan(now)
        c._health_scan(now + 1.0)
        sc = health.scanners()["hmsh"]
        assert sc.counters.get("health_scans") == sc.counters.get("health_fetches") == 2
        rows = {r["group"]: r for r in sc.rows()}
        assert len(rows) == G
        assert all(r["role"] == "leader" for r in rows.values())
        assert all(r["state"] == "quiet" for r in rows.values())
        assert all(r["commit_gap"] == 0 for r in rows.values())
    finally:
        c.stop()
        leaderboard.clear()


# ---------------------------------------------------------------------------
# nemesis-driven end-to-end classification: actor backend


def test_actor_nemesis_stuck_group_via_poisoned_wal(tmp_path):
    """Disk-fault nemesis on the actor backend: a WAL whose fsync
    always fails poisons durability on the leader's node — appended
    commands can never commit, and the health plane must classify the
    group stuck within a bounded number of ticks."""
    leaderboard.clear()
    names = ["hw0", "hw1", "hw2"]
    for n in names:
        api.start_node(
            n, SystemConfig(name="hw", data_dir=str(tmp_path / n)),
            election_timeout_s=0.1, tick_interval_s=0.1,
            detector_poll_s=0.05,
        )
    try:
        ids = [(f"w{i}", names[i]) for i in range(3)]
        started, failed = api.start_cluster(
            "hwcl", adder, ids, timeout=20
        )
        assert failed == []
        leader = api.wait_for_leader("hwcl")
        api.process_command(leader, 1, timeout=10)
        # poison the whole cluster's WAL fsyncs: durability is gone
        # everywhere, so appended entries can never commit anywhere
        faults.arm("wal.fsync", ("raise", "eio"), ("always",), seed=7)
        for k in range(4):
            api.pipeline_command(leader, 1, correlation=k, who="hwclient")
        await_(
            lambda: any(
                r["state"] == "stuck"
                for sc in health.scanners().values()
                for r in sc.rows()
                if r["cluster"] == "hwcl"
            ),
            timeout=25, what="stuck classification under poisoned WAL",
        )
        # the feed surfaces it as a ranked anomaly
        ch = api.cluster_health()
        assert any(
            a["cluster"] == "hwcl" and a["state"] == "stuck"
            for a in ch["anomalies"]
        )
        assert any(
            e["kind"] == "health_transition" and "->stuck" in str(e["detail"])
            for e in obs.flight_recorder().events()
        )
    finally:
        faults.disarm_all()
        for n in names:
            try:
                api.stop_node(n)
            except Exception:  # noqa: BLE001
                pass
        leaderboard.clear()


def test_actor_nemesis_flapping_group_detected(tmp_path):
    leaderboard.clear()
    names = ["hf0", "hf1", "hf2"]
    for n in names:
        api.start_node(
            n, SystemConfig(name="hf", data_dir=str(tmp_path / n)),
            election_timeout_s=0.1, tick_interval_s=0.1,
            detector_poll_s=0.05,
        )
    try:
        ids = [(f"f{i}", names[i]) for i in range(3)]
        started, failed = api.start_cluster("hfcl", adder, ids, timeout=20)
        assert failed == []
        api.wait_for_leader("hfcl")

        def flapped():
            return any(
                r["state"] == "flapping"
                for sc in health.scanners().values()
                for r in sc.rows()
                if r["cluster"] == "hfcl"
            )

        deadline = time.monotonic() + 20
        k = 0
        while time.monotonic() < deadline and not flapped():
            try:
                api.trigger_election(ids[k % 3])
            except Exception:  # noqa: BLE001
                pass
            k += 1
            time.sleep(0.08)
        assert flapped(), "flapping never classified on actor backend"
    finally:
        for n in names:
            try:
                api.stop_node(n)
            except Exception:  # noqa: BLE001
                pass
        leaderboard.clear()


# ---------------------------------------------------------------------------
# feed surface


def test_cluster_health_feed_shape_and_anomaly_ranking():
    leaderboard.clear()
    sc = health.register("hcf0", backend="test")
    try:
        s = np.array([sc.ensure("a", "cl1"), sc.ensure("b", "cl1")])
        _scan(sc, 1.0, s, applied=[5, 5], commit=[5, 5], last=[5, 5])
        for k in range(sc.cfg.stuck_ticks + 1):
            _scan(sc, 2.0 + k, s, applied=[5, 5], commit=[9, 5],
                  last=[9, 5])
        leaderboard.record("cl1", ("a", "hcf0"), (("a", "hcf0"),))
        ch = api.cluster_health(last_events=5)
        assert ch["nodes"]["hcf0"]["backend"] == "test"
        assert ch["clusters"]["cl1"]["leader"] == ("a", "hcf0")
        assert set(ch["clusters"]["cl1"]["groups"]) == {"a@hcf0", "b@hcf0"}
        assert ch["anomalies"] and ch["anomalies"][0]["group"] == "a"
        assert ch["anomalies"][0]["state"] == "stuck"
        assert "events" in ch
    finally:
        health.unregister("hcf0")
        leaderboard.clear()
