"""Observability layer tests: histogram bucketing/percentile math,
flight-recorder wraparound + concurrent append, counter exposition, and
the live system_overview surface on both backends (ISSUE 6)."""

import threading
import time

import numpy as np
import pytest

from ra_tpu import api, counters, leaderboard, obs
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
        time.sleep(0.01)
    raise AssertionError(f"timeout waiting for {what}")


# ---------------------------------------------------------------------------
# histogram math


def test_bucket_of_monotone_and_continuous():
    prev = -1
    for v in range(0, 20000):
        b = obs.bucket_of(v)
        assert b in (prev, prev + 1), (v, b, prev)  # no gaps, no jumps back
        prev = b


def test_bucket_bounds_roundtrip_and_error_bound():
    for v in [0, 1, 31, 32, 33, 100, 1023, 1024, 12345, 10**6, 10**9,
              7 * 10**12, 2**62]:
        b = obs.bucket_of(v)
        lo, hi = obs.bucket_bounds(b)
        assert lo <= v <= hi, (v, b, lo, hi)
        mid = (lo + hi) // 2
        if v >= obs.SUB_BUCKETS:
            assert abs(mid - v) / v <= 1.0 / obs.SUB_BUCKETS + 1e-9
        else:
            assert mid == v  # exact below the linear threshold


def test_bucket_of_negative_clamps_to_zero():
    assert obs.bucket_of(-5) == 0


def test_histogram_percentiles_uniform():
    h = obs.LogHistogram("t")
    for v in range(1, 1001):
        h.record(v * 1000)  # 1000..1000000, well into log buckets
    assert h.n == 1000
    p50, p90, p99 = h.percentiles((50, 90, 99))
    for got, want in ((p50, 500_000), (p90, 900_000), (p99, 990_000)):
        assert abs(got - want) / want <= 2.0 / obs.SUB_BUCKETS, (got, want)
    assert h.percentile(100) >= h.percentile(99)


def test_histogram_empty_and_reset_and_count():
    h = obs.LogHistogram("t2")
    assert h.percentile(50) == 0 and h.n == 0 and h.mean() == 0.0
    h.record(100, count=7)
    assert h.n == 7 and h.total == 700 and h.max_v == 100
    assert h.percentile(50) in range(96, 105)
    h.reset()
    assert h.n == 0 and h.percentile(99) == 0 and int(h.arr.sum()) == 0


def test_histogram_merge():
    a = obs.LogHistogram("a")
    b = obs.LogHistogram("b")
    a.record(1000, count=10)
    b.record(64000, count=10)
    a.merge(b)
    assert a.n == 20 and a.max_v == 64000
    p50 = a.percentile(50)
    assert p50 < 64000 * (1 - 1.0 / obs.SUB_BUCKETS)


def test_histogram_record_seconds_and_to_dict():
    h = obs.LogHistogram("t3")
    h.record_seconds(0.002)  # 2 ms
    d = h.to_dict()
    assert d["count"] == 1
    assert 1.8 <= d["p50_ms"] <= 2.2
    assert d["p99_9_ms"] >= d["p50_ms"]


def test_histogram_registry_dedup_and_overview():
    r = obs.HistogramRegistry()
    h1 = r.new(("x", "y"), help="h")
    h2 = r.new(("x", "y"))
    assert h1 is h2
    assert r.overview() == {}  # empty histograms are omitted
    h1.record(5)
    assert ("x", "y") in r.overview()
    r.delete(("x", "y"))
    assert r.fetch(("x", "y")) is None


# ---------------------------------------------------------------------------
# flight recorder


def test_flight_recorder_wraparound_keeps_latest_in_order():
    fr = obs.FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("k", node="n", detail=i)
    evts = fr.events()
    assert len(evts) == 8
    assert [e["detail"] for e in evts] == list(range(12, 20))
    seqs = [e["seq"] for e in evts]
    assert seqs == sorted(seqs)
    assert evts[0]["ts"] <= evts[-1]["ts"]


def test_flight_recorder_concurrent_append():
    fr = obs.FlightRecorder(capacity=64)
    n_threads, per = 8, 500

    def writer(tid):
        for i in range(per):
            fr.record("evt", node=f"t{tid}", term=i)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evts = fr.events()
    assert len(evts) == 64  # full ring, nothing torn
    for e in evts:
        assert e["kind"] == "evt" and e["node"].startswith("t")
    seqs = [e["seq"] for e in evts]
    assert seqs == sorted(seqs) and len(set(seqs)) == 64
    # only loose bounds on WHICH seqs survive: a writer preempted
    # between seq allocation and its slot store may publish an
    # arbitrarily old event (fine for a best-effort ring), so assert
    # progression well past one ring generation, not exact tail-ness
    assert max(seqs) < n_threads * per
    assert max(seqs) >= 64


def test_flight_recorder_dump_and_last(capsys):
    fr = obs.FlightRecorder(capacity=16)
    for i in range(5):
        fr.record("role_change", node="nX", group=f"g{i}", term=i,
                  detail="f->l")
    assert len(fr.events(last=2)) == 2
    import io

    buf = io.StringIO()
    fr.dump(file=buf, header=" [test]")
    out = buf.getvalue()
    assert "flight recorder dump (5 events) [test]" in out
    assert "role_change" in out and "group=g4" in out and "term=4" in out


# ---------------------------------------------------------------------------
# counters exposition


def test_counters_describe_carries_kind_and_help():
    c = counters.Counters("t", counters.WAL_FIELDS)
    c.incr("fsyncs", 3)
    d = {row["name"]: row for row in c.describe()}
    assert d["fsyncs"]["value"] == 3
    assert d["fsyncs"]["kind"] == "counter"
    assert "fsync" in d["fsyncs"]["help"]
    assert d["batch_size"]["kind"] == "gauge"


def test_registry_describe_overview_and_locked_fetch():
    reg = counters.CounterRegistry()
    c = reg.new(("obs_t", 1), counters.SEGMENT_WRITER_FIELDS)
    c.incr("segments_created")
    ov = reg.describe_overview()
    rows = {r["name"]: r for r in ov[("obs_t", 1)]}
    assert rows["segments_created"]["value"] == 1
    assert rows["segments_created"]["help"]
    assert reg.fetch(("obs_t", 1)) is c
    assert reg.fetch(("missing", 0)) is None


def test_prometheus_text_renders_counters_and_histograms():
    counters.new(("prom_t", "s1"), counters.RA_SERVER_FIELDS).incr(
        "commands", 5
    )
    obs.histogram(("prom_t", "lat"), help="test latency").record(1_000_000)
    try:
        text = obs.prometheus_text()
        assert "# HELP ra_commands commands received by the leader" in text
        assert "# TYPE ra_commands counter" in text
        assert 'ra_commands{name="(\'prom_t\', \'s1\')"} 5' in text
        assert "# TYPE ra_prom_t_lat_seconds summary" in text
        assert 'ra_prom_t_lat_seconds{quantile="0.5"} 0.00' in text
        assert "ra_prom_t_lat_seconds_count 1" in text
        assert "nan" not in text.lower()
    finally:
        counters.delete(("prom_t", "s1"))
        obs.histograms().delete(("prom_t", "lat"))


# ---------------------------------------------------------------------------
# live integration: system_overview on both backends


@pytest.fixture
def three_coords():
    leaderboard.clear()
    coords = [
        BatchCoordinator(f"ot{i}", capacity=8, num_peers=3,
                         election_timeout_s=0.1, detector_poll_s=0.05)
        for i in range(3)
    ]
    for c in coords:
        c.start()
    yield coords
    for c in coords:
        c.stop()
    leaderboard.clear()


def test_system_overview_live_batch_cluster(three_coords):
    coords = three_coords
    members = [("og", f"ot{i}") for i in range(3)]
    for c in coords:
        c.add_group("og", "ocl", members, SimpleMachine(lambda cm, s: s + cm, 0))
    mark = next(iter(obs.flight_recorder().events(last=1)), None)
    seq0 = mark["seq"] if mark else -1
    coords[0].deliver(("og", "ot0"), ElectionTimeout(), None)
    await_(lambda: coords[0].by_name["og"].role == C.R_LEADER,
           what="ot0 leader")
    for k in range(4):
        out, _leader = api.process_command(("og", "ot0"), 1, timeout=10.0)
        assert out == k + 1

    ov = api.system_overview("ot0")
    assert ov["overview"]["backend"] == "tpu_batch"
    # wave phases non-zero under load: every slice of a step, and every
    # sub-phase a leader's step, realisation and apply of four client
    # commands must pass through. The native sub-phases have no sample
    # while the native path is off, effects_realise none while no
    # machine returns an effect, ingest_fanout none (every pass that
    # fans out dispatches), gil_wait none on a node that is not the
    # process's first started one
    # (tests/test_wave_account.py holds those two);
    # system_overview leaves an empty histogram out.
    wave = {k[2]: v for k, v in ov["histograms"].items()
            if isinstance(k, tuple) and k[0] == "wave" and k[1] == "ot0"}
    assert set(wave) <= {ph for ph, _ in obs.WAVE_PHASES}
    for ph, _ in obs.WAVE_STEP_PHASES + (("egress_apply", ""),):
        assert wave.get(ph, {}).get("count", 0) > 0, (ph, wave.keys())
        assert wave[ph]["sum_ms"] > 0, ph
    for ph in set(obs.WAVE_SUBSET_PHASES) - {"classify_native", "pack_native",
                                             "effects_realise",
                                             "ingest_fanout", "gil_wait"}:
        assert wave.get(ph, {}).get("count", 0) > 0, (ph, wave.keys())
    assert not {"apply", "wal_handoff"} & set(wave)
    # this machine returns no effect: nothing was realised
    assert "effects_realise" not in wave
    # one record per pass that had commands / per step that committed:
    # never more than the phases they are subsets of
    assert wave["ingest_append"]["count"] <= wave["ingress_drain"]["count"]
    assert wave["egress_apply"]["count"] <= wave["host_egress"]["count"]
    # all five commit-latency stages non-zero
    com = {k[2]: v for k, v in ov["histograms"].items()
           if isinstance(k, tuple) and k[0] == "commit" and k[1] == "ot0"}
    for st, _ in obs.COMMIT_STAGES:
        assert com.get(st, {}).get("count", 0) > 0, (st, com.keys())
    # counters carry kind/help metadata
    coord_rows = ov["counters"][("coordinator", "ot0")]
    assert all({"name", "kind", "help", "value"} <= set(r) for r in coord_rows)
    # cluster commit-rate wiring (leaderboard + li data, single source)
    assert ov["clusters"]["ocl"]["leader"] == ("og", "ot0")
    assert ov["clusters"]["ocl"]["commit_rate_scope"] == "node"

    # coherent event sequence across an induced election: depose ot0 by
    # electing the ot1 replica. process_command returns on a quorum, so
    # ot1 may still lack the last entry (its pre-vote is then refused)
    # and one ElectionTimeout is one attempt: wait for ot1 to catch up,
    # and ask again while it has not won
    await_(lambda: coords[1].by_name["og"].last_applied
           >= coords[0].by_name["og"].last_applied, what="ot1 caught up")
    for _attempt in range(15):
        coords[1].deliver(("og", "ot1"), ElectionTimeout(), None)
        try:
            await_(lambda: coords[1].by_name["og"].role == C.R_LEADER,
                   timeout=2.0, what="ot1 leader after induced election")
            break
        except AssertionError:
            continue
    assert coords[1].by_name["og"].role == C.R_LEADER
    evts = [e for e in obs.flight_recorder().events()
            if e["seq"] > seq0 and e["group"] in ("og",)]
    kinds = [e["kind"] for e in evts]
    assert "election" in kinds and "role_change" in kinds
    # ordering: an election on ot1 precedes its role change to leader
    el = next(i for i, e in enumerate(evts)
              if e["kind"] == "election" and e["node"] == "ot1")
    rc = next(i for i, e in enumerate(evts)
              if e["kind"] == "role_change" and e["node"] == "ot1"
              and str(e["detail"]).endswith("->leader"))
    assert el < rc
    seqs = [e["seq"] for e in evts]
    # seq is the total order (ts can invert by a few us across threads:
    # seq allocation and the timestamp are not one atomic step)
    assert seqs == sorted(seqs)


def test_commit_stages_actor_backend(tmp_path):
    leaderboard.clear()
    names = ("oaA", "oaB", "oaC")
    for n in names:
        api.start_node(n, SystemConfig(name="oa", data_dir=str(tmp_path)),
                       election_timeout_s=0.1, tick_interval_s=0.1,
                       detector_poll_s=0.05)
    try:
        ids = [("s1", "oaA"), ("s2", "oaB"), ("s3", "oaC")]
        started, failed = api.start_cluster(
            "oacl", lambda: SimpleMachine(lambda c, s: s + c, 0), ids
        )
        assert failed == []
        leader = api.wait_for_leader("oacl")
        # the server follows ONE sampled command at a time through the
        # stages; under load the sample can be stranded (its commit seen
        # before its durable stamp: no later stage is booked) and is
        # given up after 10 s, so go on writing until a sample has
        # passed through all of them
        deadline = time.monotonic() + 25
        while True:
            for _ in range(4):
                api.process_command(leader, 1, timeout=10.0)
            ov = api.system_overview(leader[1])
            com = {k[2]: v for k, v in ov["histograms"].items()
                   if isinstance(k, tuple) and k[0] == "commit"
                   and k[1] == leader[1]}
            if all(com.get(st, {}).get("count", 0) > 0
                   for st, _ in obs.COMMIT_STAGES) \
                    or time.monotonic() > deadline:
                break
            time.sleep(0.5)
        for st, _ in obs.COMMIT_STAGES:
            assert com.get(st, {}).get("count", 0) > 0, (st, com.keys())
        # per-server commit_rate gauge is the cluster's rate source
        assert ov["clusters"]["oacl"]["commit_rate_scope"] == "server"
        # the election trace reached the recorder
        assert any(
            e["kind"] == "role_change" and e["node"] == leader[1]
            for e in ov["events"]
        )
    finally:
        for n in names:
            try:
                api.stop_node(n)
            except Exception:  # noqa: BLE001
                pass
        leaderboard.clear()


def test_admission_reject_records_event():
    """An overloaded batch leader leaves an admission_reject trace."""
    leaderboard.clear()
    c = BatchCoordinator("oadm", capacity=4, num_peers=3,
                         max_command_backlog=2)
    c.start()
    try:
        sid = ("ag", "oadm")
        c.add_group("ag", "agcl", [sid], SimpleMachine(lambda cm, s: s + cm, 0))
        c.deliver(sid, ElectionTimeout(), None)
        await_(lambda: c.by_name["ag"].role == C.R_LEADER, what="leader")
        # flood past the backlog in ONE delivery round so the window
        # must shed (noreply -> dropped + counted + event)
        cmds = [Command(kind=USR, data=1) for _ in range(64)]
        c.deliver_many([(sid, m, None) for m in cmds])
        await_(
            lambda: c.counters.get("commands_dropped_overload") > 0,
            what="overload drop",
        )
        assert any(
            e["kind"] == "admission_reject" and e["node"] == "oadm"
            for e in obs.flight_recorder().events()
        )
    finally:
        c.stop()
        leaderboard.clear()
