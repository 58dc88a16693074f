"""The program's spans in the profiler's trace, and the accounts that
split the wave phases and the reads (ISSUE 24; docs/INTERNALS.md §13):

- a started, WAL-backed three-node cluster traced on the CPU leaves
  every span name of every thread role on ``/host:CPU`` with its
  ``node`` stat;
- the sub-phases of ``device_step`` and of ``host_pack`` add up to
  them, the leaves of ``ingress_drain`` and of ``host_egress`` stay
  inside them (ISSUE 35), the thread-CPU accounts stay inside their
  wall phases, the read accounts count every read and stay inside what
  the callers measured;
- ``scripts/idle_gaps.py`` on synthetic planes; ``api.profile``.
"""

import os
import sys
import threading
import time

import jax
import pytest

from ra_tpu import api, leaderboard, obs
from ra_tpu.log.log import Log
from ra_tpu.log.segment_writer import SegmentWriter
from ra_tpu.log.tables import TableRegistry
from ra_tpu.log.wal import Wal
from ra_tpu.models.kv import KvMachine, kv_get
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import USR, Command, ElectionTimeout
from ra_tpu.runtime import heap
from ra_tpu.runtime.coordinator import BatchCoordinator

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
import idle_gaps  # noqa: E402

NODES = ("sp0", "sp1", "sp2")
GROUPS = 4
READS = 60
WRITES = 150
BURST = (4, 10)  # callers at once, puts each: a put lands on a busy loop

SPANS = {
    "step": {"ra/step/classify", "ra/step/lock_wait", "ra/step/ingress_drain",
             "ra/step/ingress_drain/ingest_append",
             "ra/step/ingress_drain/route", "ra/step/host_pack",
             "ra/step/host_pack/scatter_dispatch",
             "ra/step/host_pack/mailbox_build",
             "ra/step/host_pack/step_dispatch",
             "ra/step/host_pack/step_dispatch/release", "ra/step/aer_fanout",
             "ra/step/idle"},
    "egress": {"ra/egress/sync", "ra/egress/lock_wait",
               "ra/egress/host_egress", "ra/egress/host_egress/follow",
               "ra/egress/host_egress/mirror", "ra/egress/rare",
               "ra/egress/aer_fanout"},
    "probe": {"ra/probe/gil_wait"},
    "send": {"ra/send/batch"},
    "detect": {"ra/detect/scan"},
    "wal": {"ra/wal/batch", "ra/wal/batch/write", "ra/wal/batch/notify",
            "ra/wal/batch/notify/lock_wait"},
    "segw": {"ra/segw/flush"},
    "caller": {"ra/api/process_command", "ra/api/consistent_query",
               "ra/kv/get"},
}


def await_(cond, timeout=60.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timeout waiting for {what}")


def host_spans(xplane_path):
    """{span name: [stats dict]} of the ``ra/`` events on /host:CPU."""
    data = jax.profiler.ProfileData.from_file(xplane_path)
    got = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ra/"):
                    got.setdefault(e.name, []).append(dict(e.stats))
    return got


def wave_totals(coords):
    """{phase: (count, total ns)} over the coordinators."""
    out = {}
    for name, _help in obs.WAVE_PHASES:
        hs = [obs.histograms().fetch(("wave", c.name, name)) for c in coords]
        out[name] = (sum(h.n for h in hs), sum(h.total for h in hs))
    return out


def counter_totals(coords):
    out = {}
    for c in coords:
        for k, v in c.counters.to_dict().items():
            out[k] = out.get(k, 0) + v
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced window of a started WAL-backed 3 x 4 ``ra_kv``
    cluster: WRITES puts, READS ``kv_get``s and as many local queries,
    a WAL rollover. The
    thread-CPU accounts read their clock on every turn here, so that
    they can be held to the wall phases turn for turn."""
    leaderboard.clear()
    base = tmp_path_factory.mktemp("spans")
    coords, storage = [], []
    shift = BatchCoordinator._CPU_SAMPLE_SHIFT
    BatchCoordinator._CPU_SAMPLE_SHIFT = 0
    try:
        for n in NODES:
            c = BatchCoordinator(n, capacity=GROUPS, num_peers=3)
            d = str(base / n)
            tables = TableRegistry()
            sw = SegmentWriter(os.path.join(d, "data"), tables, c.wal_notify)
            w = Wal(os.path.join(d, "wal"), tables, c.wal_notify,
                    segment_writer=sw)
            w.notify_many = c.wal_notify_many
            coords.append(c)
            storage.append((tables, w, sw, d))
        names = [f"k{g}" for g in range(GROUPS)]
        for c, (tables, w, _sw, d) in zip(coords, storage):
            c.add_groups([
                (n, f"spans_{n}", [(n, node) for node in NODES], KvMachine(),
                 Log(n, os.path.join(d, "data", n), tables, w))
                for n in names])
        for c in coords:
            c.warm_steps()
            c.start()
        lead = coords[0]
        for n in names:
            lead.deliver((n, lead.name), ElectionTimeout(), None)
        await_(lambda: all(lead.by_name[n].role == C.R_LEADER for n in names),
               what="leaders")
        await_(lambda: all(c._applied_np[:GROUPS].min() >= 1 for c in coords),
               what="election noops applied")
        for g, n in enumerate(names):  # something to read in every group
            api.process_command((n, lead.name), ("put", f"key{g}", b"v" * 64))

        before_w, before_c = wave_totals(coords), counter_totals(coords)
        trace_dir = str(base / "trace")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=obs.profile_options())
        try:
            for i in range(WRITES):
                g = i % GROUPS
                api.process_command((names[g], lead.name),
                                    ("put", f"key{g}", b"w" * 64))
            t0 = time.monotonic_ns()
            for i in range(READS):
                g = i % GROUPS
                assert kv_get(api, (names[g], lead.name), f"key{g}") == \
                    b"w" * 64
            read_ns = time.monotonic_ns() - t0
            # a read's queries and heartbeats are handled as they are
            # routed; a local query still rides its pass's ticket
            for i in range(READS):
                assert api.local_query((names[i % GROUPS], lead.name),
                                       lambda st: len(st))[0] == "ok"

            # puts from several callers at once: one lands while a step
            # is realised, and waits in its lane for the next turn
            def burst(g):
                for _ in range(BURST[1]):
                    api.process_command((names[g], lead.name),
                                        ("put", f"key{g}", b"w" * 64))

            callers = [threading.Thread(target=burst, args=(k % GROUPS,))
                       for k in range(BURST[0])]
            for t in callers:
                t.start()
            for t in callers:
                t.join()
            # a thread that keeps the interpreter lock for its whole turn,
            # for a few of the probe's periods
            spin_until = time.monotonic() + 0.25
            while time.monotonic() < spin_until:
                sum(range(1000))
            probe_node = heap._serving[0][1]
            storage[0][1].force_rollover()  # a job for the segment writer
            await_(lambda: storage[0][2].wait_idle(0.1), what="segments")
            time.sleep(0.3)  # a detector pass or two
        finally:
            jax.profiler.stop_trace()
        after_w, after_c = wave_totals(coords), counter_totals(coords)
        yield {
            "spans": host_spans(obs.xplane_path(trace_dir)),
            "xplane": obs.xplane_path(trace_dir),
            "wave": {k: (after_w[k][0] - before_w[k][0],
                         after_w[k][1] - before_w[k][1]) for k in after_w},
            "counters": {k: after_c[k] - before_c[k] for k in after_c},
            "read_ns": read_ns,
            "probe_node": probe_node,
        }
    finally:
        BatchCoordinator._CPU_SAMPLE_SHIFT = shift
        for c in coords:
            c.stop()
        for _t, w, sw, _d in storage:
            w.close()
            sw.close()
        leaderboard.clear()


@pytest.mark.parametrize("role", sorted(SPANS))
def test_every_span_of_a_thread_role_is_in_the_trace(traced, role):
    got = traced["spans"]
    missing = SPANS[role] - set(got)
    assert not missing, (missing, sorted(got))
    # (the probe books on the process's first started coordinator, which
    # is one that an earlier test of this worker leaked, if any did)
    ours = {traced["probe_node"]} if role == "probe" else set(NODES)
    for name in SPANS[role]:
        nodes = {stats.get("node") for stats in got[name]}
        assert nodes and nodes <= ours, (name, nodes)


def test_step_dispatch_and_batches_carry_their_stats(traced):
    got = traced["spans"]
    for stats in got["ra/step/host_pack/step_dispatch"]:
        assert stats["width"] >= 1
        assert stats["variant"] in ("sub_scat", "scat", "packed")
    assert all(s["msgs"] >= 1 for s in got["ra/send/batch"])
    assert all(s["items"] >= 1 for s in got["ra/wal/batch"])
    assert any(s["entries"] >= 1 for s in got["ra/wal/batch/write"])
    # one span per step or batch, never one per group, message or entry
    # (a pass whose active set came out empty packs and dispatches none)
    # (the histograms were read just outside the profiler session)
    steps = traced["wave"]["host_pack"][0]
    dispatched = len(got["ra/step/host_pack/step_dispatch"])
    assert 0.9 * steps <= dispatched <= steps
    assert len(got["ra/step/host_pack/mailbox_build"]) == dispatched
    assert dispatched <= len(got["ra/step/host_pack"]) <= 2 * steps


def test_device_step_sub_phases_add_up(traced):
    wave = traced["wave"]
    n, total = wave["device_step"]
    assert n >= 200
    parts = ("ticket_queue", "egress_sync", "egress_lock_wait")
    assert all(wave[p][0] == n for p in parts)
    assert abs(sum(wave[p][1] for p in parts) - total) <= 0.05 * total


def test_host_pack_and_ingress_sub_phases_stay_inside(traced):
    wave = traced["wave"]
    n, total = wave["host_pack"]
    parts = ("scatter_dispatch", "mailbox_build", "step_dispatch")
    assert all(wave[p][0] == n for p in parts)
    assert abs(sum(wave[p][1] for p in parts) - total) <= 0.05 * total
    assert wave["step_lock_wait"][0] == wave["ingress_drain"][0]
    assert wave["step_lock_wait"][1] <= wave["ingress_drain"][1]
    # one record per pass that had client commands, per step that
    # committed: all groups of the pass, inside the phase
    for part, whole in (("ingest_append", "ingress_drain"),
                        ("egress_apply", "host_egress")):
        assert 0 < wave[part][0] <= wave[whole][0]
        assert 0 < wave[part][1] <= wave[whole][1]
    # every put on its own pass (the burst's may share one)
    assert wave["ingest_append"][0] <= WRITES + BURST[0] * BURST[1]
    # ingest_append is one stretch of a pass and has its span (the
    # histograms were read just outside the profiler session);
    # egress_apply adds up applies that lie apart, and has none
    n = len(traced["spans"]["ra/step/ingress_drain/ingest_append"])
    assert 0.9 * wave["ingest_append"][0] <= n <= wave["ingest_append"][0]
    assert not any("egress_apply" in name for name in traced["spans"])


def test_ingress_and_egress_leaves_stay_inside_and_have_their_spans(traced):
    """ISSUE 35: the leaves of ``ingress_drain`` and of ``host_egress``,
    one record and one span a pass or step that had the work."""
    wave, got = traced["wave"], traced["spans"]
    n_in, ns_in = wave["ingress_drain"]
    assert abs(wave["ingress_classify"][0] - n_in) <= 3  # (read live)
    ingress = ("ingress_classify", "step_lock_wait", "ingress_route",
               "ingest_append")
    egress = ("egress_follow", "egress_mirror", "egress_apply", "egress_rare")
    for leaves, whole in ((ingress, "ingress_drain"), (egress, "host_egress")):
        for leaf in leaves:
            assert 0 < wave[leaf][0] <= wave[whole][0], (leaf, wave[leaf])
            assert 0 < wave[leaf][1] <= wave[whole][1], (leaf, wave[leaf])
        assert sum(wave[leaf][1] for leaf in leaves) <= wave[whole][1]
    # every pass that fans out dispatches: its fan-out is aer_fanout's
    assert wave["ingest_fanout"] == (0, 0)
    assert "ra/step/ingress_drain/fanout" not in got
    # (the histograms were read just outside the profiler session)
    for span, leaf in (("ra/step/ingress_drain/route", "ingress_route"),
                       ("ra/egress/host_egress/follow", "egress_follow"),
                       ("ra/egress/host_egress/mirror", "egress_mirror"),
                       ("ra/egress/rare", "egress_rare")):
        assert 0.9 * wave[leaf][0] - 1 <= len(got[span]) <= wave[leaf][0], \
            (span, len(got[span]), wave[leaf][0])
    assert all(s["msgs"] >= 1 for s in got["ra/step/ingress_drain/route"])
    assert all(s["msgs"] >= 1 for s in got["ra/egress/host_egress/follow"])
    # aer_fanout's span is the dispatching pass's, one a step at most
    assert len(got["ra/step/aer_fanout"]) <= wave["host_pack"][0]
    # the sender's queue: a sample a batch, in no phase
    assert wave["send_queue"][0] >= len(got["ra/send/batch"]) > 0
    # the probe: a span only where the wait passed a millisecond, and it
    # says how far back it reaches
    waits = got["ra/probe/gil_wait"]
    assert all(s["wait_ns"] > 1_000_000 for s in waits)
    assert len(waits) <= wave["gil_wait"][0] + 1 or \
        traced["probe_node"] not in NODES
    # ... and idle_gaps lays it from its due time to the wake
    _ops, laid, _runs = idle_gaps.read_trace(traced["xplane"])
    probe = [(hi - lo) for name, _node, lo, hi in laid
             if name == "ra/probe/gil_wait"]
    assert sorted(probe) == pytest.approx(
        sorted(s["wait_ns"] for s in waits), abs=100_000)


def test_wal_notify_accounts_one_round_per_batch(traced):
    cnt, got = traced["counters"], traced["spans"]
    rounds = cnt["wal_notify_batches"]
    assert rounds > 0
    # a round per WAL batch that had written events to deliver, each
    # with its wait as a span under the batch's notify
    assert rounds <= len(got["ra/wal/batch/notify"]) + 3
    assert 0.9 * rounds <= len(got["ra/wal/batch/notify/lock_wait"]) <= rounds
    # every put is written on a quorum before the next is sent (a
    # lagging third replica may cover two with one event); a round may
    # carry several events
    assert cnt["wal_notify_events"] >= 2 * WRITES
    assert cnt["wal_notify_events"] >= rounds
    assert cnt["wal_notify_wait_ns"] >= 0 and cnt["wal_notify_hold_ns"] > 0


def test_thread_cpu_never_exceeds_the_wall_phase(traced):
    wave, cnt = traced["wave"], traced["counters"]
    for phase in ("ingress_drain", "host_pack", "host_egress", "aer_fanout"):
        n, wall = wave[phase]
        cpu = cnt[f"cpu_ns_{phase}"]
        assert 0 < cpu
        # the two clocks are read one after the other at each boundary
        assert cpu <= wall + 2_000 * n, (phase, cpu, wall)


def test_read_accounts_count_every_read(traced):
    cnt = traced["counters"]
    assert cnt["read_registers"] == READS
    assert cnt["read_quorum_rounds"] == READS
    assert cnt["state_queries"] >= READS
    parts = (cnt["read_register_ns"], cnt["read_quorum_ns"],
             cnt["state_query_ns"])
    assert all(p > 0 for p in parts)
    # each stage ends before its caller sees the reply
    assert sum(parts) <= traced["read_ns"]
    assert sum(parts) >= 0.3 * traced["read_ns"]


def test_span_is_a_trace_annotation_and_futures_are_stamped():
    sp = obs.span("ra/test/x", node="n")
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp:
        pass
    t0 = time.monotonic_ns()
    assert t0 <= api.Future().t_born <= time.monotonic_ns()


def test_tracing_follows_the_profilers_session(tmp_path):
    """``obs.tracing()`` is the profiler's own state, and a span opened
    with ``begin`` and closed with ``end`` lands like one under
    ``with``."""
    assert obs.tracing() is False
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=obs.profile_options())
    try:
        assert obs.tracing() is True
        obs.end(obs.begin("ra/test/begin_end", node="n", width=3))
    finally:
        jax.profiler.stop_trace()
    assert obs.tracing() is False
    got = host_spans(obs.xplane_path(str(tmp_path)))
    assert [dict(s) for s in got["ra/test/begin_end"]] == [
        {"node": "n", "width": 3}]


def test_thread_cpu_is_read_on_one_turn_in_sixteen(monkeypatch):
    """The thread clock is a system call (16 us a read on the v5e's
    host): a coordinator reads it on one turn in 16 and books that
    turn's readings 16 times. The coordinator under test is driven from
    this thread and reads a clock that advances 1 us on each read (the
    real one ticks in 10 ms on some hosts, and a 16th of 160 turns can
    fall between two ticks); every other thread of the process, the
    module's traced cluster among them, keeps the real clock and is not
    counted."""
    leaderboard.clear()
    # (the module's traced cluster reads the clock on every turn)
    monkeypatch.setattr(BatchCoordinator, "_CPU_SAMPLE_SHIFT", 4)
    c = BatchCoordinator("sp_cpu", capacity=4, num_peers=3)
    try:
        sid = ("cpu", "sp_cpu")
        c.add_group("cpu", "spans_cpu", [sid], KvMachine())
        c.deliver(sid, ElectionTimeout(), None)
        while c.step_once():
            pass
        me = threading.get_ident()
        real = time.thread_time_ns
        reads = []

        def thread_clock():
            if threading.get_ident() != me:
                return real()
            reads.append(1)
            return 1000 * len(reads)

        before = c.counters.to_dict()
        turns0 = c._cpu_turn
        monkeypatch.setattr(time, "thread_time_ns", thread_clock)
        for _ in range(160):
            c.deliver(sid, Command(kind=USR, data=("put", "k", b"v")), None)
            while c.step_once():
                pass
        monkeypatch.undo()
        turns = c._cpu_turn - turns0
        assert turns >= 160
        # at most seven readings on a turn that is read
        assert turns // 16 <= len(reads) <= 7 * (turns // 16 + 1)
        after = c.counters.to_dict()
        for phase in ("ingress_drain", "host_pack", "host_egress",
                      "aer_fanout"):
            grown = after[f"cpu_ns_{phase}"] - before[f"cpu_ns_{phase}"]
            # whole readings of the fake clock, booked 16 times each
            assert grown > 0 and grown % 16000 == 0, (phase, grown)
    finally:
        c.stop()
        leaderboard.clear()


def test_single_voter_read_counts_in_register_only():
    leaderboard.clear()
    c = BatchCoordinator("sp_one", capacity=4, num_peers=3)
    c.start()
    try:
        sid = ("solo", "sp_one")
        c.add_group("solo", "spans_solo", [sid], KvMachine())
        c.deliver(sid, ElectionTimeout(), None)
        await_(lambda: c.by_name["solo"].role == C.R_LEADER, what="leader")
        api.process_command(sid, ("put", "k", b"v"))
        assert api.consistent_query(sid, lambda st: "k" in st)[1] is True
        cnt = c.counters.to_dict()
        assert cnt["read_registers"] == 1 and cnt["read_register_ns"] > 0
        assert cnt["read_quorum_rounds"] == 0 and cnt["read_quorum_ns"] == 0
    finally:
        c.stop()
        leaderboard.clear()


def test_profile_writes_an_xplane_with_a_cooperative_drivers_spans(tmp_path):
    """``api.profile`` beside a cooperative ``step_once`` driver: the
    same spans from the same code, realisation included."""
    import threading

    leaderboard.clear()
    c = BatchCoordinator("sp_coop", capacity=4, num_peers=3)
    try:
        sid = ("coop", "sp_coop")
        c.add_group("coop", "spans_coop", [sid], KvMachine())
        c.deliver(sid, ElectionTimeout(), None)
        stop_driver, stop_client = threading.Event(), threading.Event()

        def drive():
            while not stop_driver.is_set():
                if not c.step_once():
                    time.sleep(0.001)

        def client():
            while not stop_client.is_set():
                api.process_command(sid, ("put", "k", b"v"))

        driver, caller = (threading.Thread(target=f) for f in (drive, client))
        driver.start()
        try:
            await_(lambda: c.by_name["coop"].role == C.R_LEADER, what="leader")
            caller.start()
            path = api.profile(str(tmp_path / "trace"), 0.5)
        finally:
            stop_client.set()
            if caller.ident is not None:
                caller.join()
            stop_driver.set()
            driver.join()
        assert path.endswith(".xplane.pb") and os.path.getsize(path) > 0
        got = host_spans(path)
        assert {"ra/step/classify", "ra/step/lock_wait",
                "ra/step/ingress_drain", "ra/step/host_pack",
                "ra/egress/sync", "ra/egress/host_egress",
                "ra/egress/aer_fanout"} <= set(got)
        # (the module's traced cluster may still tick beside this one)
        assert "sp_coop" in {s["node"] for s in got["ra/egress/sync"]}
    finally:
        c.stop()
        leaderboard.clear()


def test_wal_hold_and_fsync_spans_on_the_python_path(tmp_path):
    """The group-commit hold, and the fsync as a span of its own where
    the write and the sync are two calls (the native path makes one)."""
    seen = []
    w = Wal(str(tmp_path / "nodeW" / "wal"), TableRegistry(),
            lambda uid, evt: seen.append(evt), native=False,
            group_commit_max_delay_s=0.02, group_commit_min_gain=1)
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=obs.profile_options())
    try:
        for i in range(1, 400):
            w.write("u", i, 1, b"x" * 32)
            if i % 40 == 0:
                time.sleep(0.005)
        await_(lambda: w.last_writer_seq("u") == 399, what="wal drained")
    finally:
        jax.profiler.stop_trace()
        w.close()
    got = host_spans(obs.xplane_path(trace_dir))
    assert {"ra/wal/hold", "ra/wal/batch", "ra/wal/batch/write",
            "ra/wal/batch/fsync", "ra/wal/batch/notify"} <= set(got)
    assert {s["node"] for s in got["ra/wal/hold"]} == {"nodeW"}


GAP_CASES = {
    # device busy [0,10) and [40,50): one idle gap [10,40)
    "a gap wholly inside one span": (
        [("ra/step/idle", "n1", 5, 45)],
        {("ra/step/idle", "n1"): 30}, 30, 0),
    "a gap cut by two spans": (
        [("ra/step/host_pack", "n1", 0, 22), ("ra/egress/sync", "n2", 30, 60)],
        {("ra/step/host_pack", "n1"): 12, ("ra/egress/sync", "n2"): 10},
        22, 8),
    "a gap no span covers": (
        [("ra/step/idle", "n1", 0, 10), ("ra/step/idle", "n1", 40, 50)],
        {}, 0, 30),
}


@pytest.mark.parametrize("case", sorted(GAP_CASES))
def test_idle_gaps_on_synthetic_planes(case):
    spans, rows, covered, uncovered = GAP_CASES[case]
    got = idle_gaps.idle_gaps([(0, 10), (40, 50)], spans, window=(0, 50))
    assert got["idle_s"] == pytest.approx(30e-9)
    assert got["covered_s"] == pytest.approx(covered * 1e-9)
    assert got["uncovered_s"] == pytest.approx(uncovered * 1e-9)
    assert {(n, d): round(s * 1e9) for n, d, s in got["rows"]} == rows


def test_idle_gaps_adds_a_thread_roles_spans_up():
    # host_pack and idle of one step thread, a caller beside them
    got = idle_gaps.idle_gaps(
        [(0, 10), (40, 50)],
        [("ra/step/host_pack", "n1", 8, 20), ("ra/step/idle", "n1", 22, 42),
         ("ra/step/host_pack/mailbox_build", "n1", 9, 12),
         ("ra/kv/get", "n1", 0, 50)], window=(0, 50))
    assert {(r, d): round(s * 1e9) for r, d, s in got["roles"]} == {
        ("step", "n1"): 28, ("kv", "n1"): 30}
    assert "| step | n1 |" in idle_gaps.render(got, 10)


def test_idle_gaps_adds_one_nodes_threads_as_a_union():
    # two threads of one node inside the same span name: the union; and
    # the window runs from the first to the last thing in the trace
    got = idle_gaps.idle_gaps(
        [(0, 10), (40, 50)],
        [("ra/step/idle", "n1", 10, 30), ("ra/step/idle", "n1", 20, 40),
         ("ra/step/idle", "n2", 10, 15)])
    assert {(n, d): round(s * 1e9) for n, d, s in got["rows"]} == {
        ("ra/step/idle", "n1"): 30, ("ra/step/idle", "n2"): 5}
    assert got["roles"] == got["rows"][:0] + [
        ("step", "n1", pytest.approx(30e-9)),
        ("step", "n2", pytest.approx(5e-9))]
    assert got["window_s"] == pytest.approx(50e-9)
    assert idle_gaps.idle_gaps([], []) is None
