"""The quorum-queue deployment (``ra_fifo_10k_x3.hot_queues``) at 8 and
64 groups on the CPU through the benchmark's own ``run_cell``, judged by
``benchmark/reference/ra_fifo.py``; and what it forced of the batch
backend (ISSUE 30): ``Monitor`` / ``Demonitor`` realised into the
coordinator's monitor table, ``process_down`` reaching the machine as a
``down`` command, ``state_enter`` re-arming a new leader, the effect
counters and the sub-phase ``effects_realise``.
"""

import copy
import os
import random
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark import run as R  # noqa: E402
from ra_tpu import api, leaderboard, obs  # noqa: E402
from ra_tpu.machine import SimpleMachine  # noqa: E402
from ra_tpu.models.fifo import FifoMachine  # noqa: E402
from ra_tpu.models.kv import KvMachine  # noqa: E402
from ra_tpu.ops import consensus as C  # noqa: E402
from ra_tpu.protocol import ElectionTimeout  # noqa: E402
from ra_tpu.runtime.coordinator import BatchCoordinator  # noqa: E402
from ra_tpu.system import SystemConfig  # noqa: E402

CELL = "ra_fifo_10k_x3.hot_queues"
SEED = 3_000_000_019  # above 2**31, as the driver's are
FX_COUNTERS = ("effects_send_msg", "effects_other", "release_cursors",
               "release_cursor_snapshots", "monitors_armed", "monitor_downs")


def await_(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(0.01)
    raise AssertionError(f"timeout waiting for {what}")


# -- the cell against the plain reference, through the same files ------------


_RUNS = {}


def _cell_run(groups):
    if groups not in _RUNS:
        _RUNS[groups] = _cell_run_once(groups)
    return _RUNS[groups]


def _cell_run_once(groups):
    lines = []
    seen = []
    gen_mod = harness.load_module("generators", "fifo_queues")
    sink_of = gen_mod.Generator._sink

    def _sink(self):
        inner = sink_of(self)

        def counting(to, msg, options):
            seen.append(msg[1])
            inner(to, msg, options)

        return counting

    mp = pytest.MonkeyPatch()
    # run_cell loads the generator's module anew: patch what it loads
    load = harness.load_module

    def load_module(kind, name):
        mod = load(kind, name)
        if (kind, name) == ("generators", "fifo_queues"):
            mod.Generator._sink = _sink
        return mod

    mp.setattr(harness, "load_module", load_module)
    try:
        run = R.run_cell(
            harness.load_benchmark(), CELL, SEED, 2.0, False, time.monotonic(),
            say=lambda line, **kw: lines.append((line, kw)),
            scale={"config": {"groups": groups},
                   "traffic": {"warmup_s": 0.5, "trace_s": 2,
                               "hot_queues": 4}})
    finally:
        mp.undo()
    run.lines = lines
    run.sink_saw = seen
    return run


@pytest.fixture(scope="module", params=[8, 64])
def fifo_run(request):
    return _cell_run(request.param)


@pytest.fixture(scope="module")
def fifo8():
    return _cell_run(8)


def _judge(run, history=None, observed=None):
    ref = harness.load_module("reference", run.config["reference"])
    return ref.judge(history or run.history, observed or run.observed,
                     run.config)


def test_cell_is_correct_with_both_kinds_acknowledged(fifo_run):
    run = fifo_run
    bench = harness.load_benchmark()
    out = R.result_line(bench, run, False)
    assert out["correct"] is True, run.violations
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"ops_s", "commit_p95_ms", "setup_s"}
    assert run.ops["write"].acked > 0 and run.ops["settle"].acked > 0
    h = run.history
    assert len(h["hot"]) == 4 and 0 in h["hot"]  # a sampled group is hot
    assert not h["unknown"] and not h["settle_unknown"] and not h["retired"]
    # every confirmed message delivered once and settled, queues short
    for g in h["hot"]:
        ids = [i for _c, i, _w, _s, _t in h["deliveries"][g]]
        assert ids == list(range(1, len(ids) + 1))
        assert sorted(i for _s, i in h["confirmed"][g]) == ids
    health = [kw for line, kw in run.lines if line == "health"][0]
    assert health["compilations_in_window"] == 0
    assert health["issued"]["redeliveries"] == 0
    # (a window of 8 unconfirmed, and the 8 before them not yet settled)
    assert 0 < health["issued"]["deepest_queue"] <= 16
    assert [kw for line, kw in run.lines if line == "teardown"][0][
        "threads_that_outlived_stop"] == []
    assert any("publish_to_delivery_ms" in kw
               for line, kw in run.lines if line == "generator")


def test_effect_accounts_match_what_the_sink_saw(fifo_run):
    run = fifo_run
    d = run.deltas
    # the counter is booked once a step: what the window's steps sent
    total = sum(len(v) for v in run.history["deliveries"].values())
    assert len(run.sink_saw) == total
    in_window = d.counter("coordinator", "effects_send_msg")
    assert 0 < in_window <= total
    assert abs(in_window - run.issued["deliveries"]) <= 64
    # one monitor a consumer, armed during set-up; none went down
    assert d.after["coordinator"]["monitors_armed"] == run.config["groups"]
    assert d.counter("coordinator", "monitors_armed") == 0
    assert d.after["coordinator"]["monitor_downs"] == 0
    # a publisher with a window never lets its queue drain, so the
    # machine releases no cursor inside the window, and the logs cut no
    # snapshot
    assert d.counter("coordinator", "release_cursors") <= 3 * 4
    assert d.counter("coordinator", "release_cursor_snapshots") == 0
    fx_h, apply_h = d.hist("wave", "effects_realise"), d.hist("wave", "egress_apply")
    assert 0 < fx_h.n <= d.scalar("steps")
    assert 0 < fx_h.total_ns < apply_h.total_ns
    out = R.result_line(harness.load_benchmark(), run, True)
    assert out["metrics"]["send_msgs_per_kop"]["value"] == pytest.approx(
        500, abs=25)
    assert out["metrics"]["effects_ms_per_kop"]["value"] > 0
    assert out["metrics"]["snapshots_per_kop"]["value"] == 0.0


def test_planted_lost_message_is_caught(fifo8):
    assert _judge(fifo8) == []
    history = copy.deepcopy(fifo8.history)
    g = history["hot"][1]
    lost = history["deliveries"][g].pop(3)
    history["settled"][g] = [r for r in history["settled"][g]
                             if r[0] != lost[1]]
    bad = _judge(fifo8, history=history)
    assert bad and "lost message" in bad[0]
    # ... and one that every replica still holds as ready
    observed = copy.deepcopy(fifo8.observed)
    for s in observed["states"][g]:
        s["ready"] = [5]
    assert any("still ready" in b for b in _judge(fifo8, observed=observed))


def test_planted_doubled_enqueue_is_caught(fifo8):
    history = copy.deepcopy(fifo8.history)
    observed = copy.deepcopy(fifo8.observed)
    g = history["hot"][2]
    consumer, last, writer, seq, t = history["deliveries"][g][-1]
    # the last body came again under the next message id, on every replica
    history["deliveries"][g].append((consumer, last + 1, writer, seq, t + 1))
    history["settled"][g].append((last + 1, t + 2))
    for s in observed["states"][g]:
        s["next_msg_id"] += 1
    bad = _judge(fifo8, history=history, observed=observed)
    assert any("doubled enqueue" in b for b in bad)
    # the state alone shows it too
    bad = _judge(fifo8, observed=observed)
    assert bad and "doubled" in bad[0]


def test_planted_out_of_order_delivery_is_caught(fifo8):
    history = copy.deepcopy(fifo8.history)
    rows = history["deliveries"][history["hot"][0]]
    rows[4], rows[5] = rows[5], rows[4]
    bad = _judge(fifo8, history=history)
    assert bad and "out of order" in bad[0]


def test_planted_delivery_after_settle_is_caught(fifo8):
    history = copy.deepcopy(fifo8.history)
    g = history["hot"][3]
    msg_id, t_done = history["settled"][g][2]
    first = next(r for r in history["deliveries"][g] if r[1] == msg_id)
    history["deliveries"][g].append(first[:4] + (t_done + 1_000_000,))
    bad = _judge(fifo8, history=history)
    assert any("after its settle was acknowledged" in b for b in bad)


def test_unknown_outcomes_are_held_to_a_range(fifo8):
    history = copy.deepcopy(fifo8.history)
    observed = copy.deepcopy(fifo8.observed)
    g = history["hot"][0]
    n = len(history["confirmed"][g])
    history["unknown"][g] = [n]  # one more enqueue, outcome unknown
    assert _judge(fifo8, history=history) == []  # it did not happen
    for s in observed["states"][g]:  # it did, after its shard had ended
        s["next_msg_id"] += 1
        s["checked_out"] = {history["consumer"][g]: [n + 1]}
    assert _judge(fifo8, history=history, observed=observed) == []
    assert _judge(fifo8, observed=observed)  # not without the unknown
    observed["states"][g][2]["next_msg_id"] -= 1  # the replicas disagree
    assert "replicas differ" in _judge(
        fifo8, history=history, observed=observed)[0]


# -- monitors, process_down and state_enter on the batch backend ---------------


def _batch_cluster(prefix, machine=FifoMachine, groups=1):
    leaderboard.clear()
    coords = [BatchCoordinator(f"{prefix}{i}", capacity=16, num_peers=3)
              for i in range(3)]
    got = []  # (consumer, message) in the order the leaders sent them
    for c in coords:
        c.send_msg_cb = lambda to, msg, _o: got.append((to, msg))
        c.start()
    names = [f"{prefix}q{g}" for g in range(groups)]
    for n in names:
        members = [(n, c.name) for c in coords]
        for c in coords:
            c.add_group(n, f"{prefix}cl_{n}", members, machine())
        coords[0].deliver((n, coords[0].name), ElectionTimeout(), None)
    await_(lambda: all(coords[0].by_name[n].role == C.R_LEADER for n in names),
           what="election")
    return coords, names, got


def _stop(coords):
    for c in coords:
        c.stop()
    leaderboard.clear()


def _transfer(old, sid, target) -> bool:
    """Ask ``old`` to hand ``sid``'s leadership to ``target``."""
    fut = api.Future()
    old.deliver(sid, ("transfer_leadership", target, fut), None)
    return fut.result(10) == ("ok", None)


def _states(coords, name):
    return [c.by_name[name].machine_state for c in coords]


def _fingerprint(st):
    return (st.next_msg_id, tuple(st.queue),
            tuple(sorted((c, tuple(sorted(f.items())))
                         for c, f in st.consumers.items())),
            tuple(sorted(st.prefetch.items())), tuple(st.service_queue))


def test_consumer_down_under_traffic_redelivers_in_order():
    coords, (q,), got = _batch_cluster("fd")
    try:
        sid = (q, coords[0].name)
        lead = coords[0]
        assert api.process_command(sid, ("checkout", "c1", 8))[0][0] == "ok"
        assert lead.monitors.watchers("process", "c1") == [(sid, "machine")]
        n = 40
        pub = threading.Thread(target=lambda: [
            api.process_command(sid, ("enqueue", f"m{i}"), timeout=30)
            for i in range(1, n + 1)])
        pub.start()
        # c1 never settles: its credit fills and stays full
        await_(lambda: len(got) >= 8, what="c1's credit filled")
        assert lead.process_down("c1", "gone") == 1
        assert lead.process_down("c1", "gone") == 0  # a monitor fires once
        await_(lambda: "c1" not in lead.by_name[q].machine_state.consumers,
               what="the down command applied")
        assert api.process_command(sid, ("checkout", "c2", 100))[0][0] == "ok"
        pub.join(60)
        await_(lambda: sum(1 for to, _m in got if to == "c2") >= n,
               what="everything at c2")
        c1 = [m for to, m in got if to == "c1"]
        c2 = [m for to, m in got if to == "c2"]
        # what c1 held comes again, in order, ahead of what followed;
        # nothing lost, nothing doubled
        assert c1 == [("delivery", i, f"m{i}") for i in range(1, 9)]
        assert c2 == [("delivery", i, f"m{i}") for i in range(1, n + 1)]
        for i in range(1, n + 1):
            api.process_command(sid, ("settle", "c2", i), timeout=30)
        await_(lambda: len({_fingerprint(s) for s in _states(coords, q)}) == 1,
               what="replicas equal")
        st = lead.by_name[q].machine_state
        assert not st.queue and st.consumers == {"c2": {}}
        assert st.next_msg_id == n + 1
        cnt = lead.counters.to_dict()
        assert cnt["monitor_downs"] == 1 and cnt["monitors_armed"] == 2
        assert cnt["effects_send_msg"] == len(got) == n + 8
        # the queue drained once, at the last settle: every replica
        # realises that release cursor, none cut a snapshot
        await_(lambda: [c.counters.get("release_cursors") for c in coords]
               == [1, 1, 1], what="the release cursor on every replica")
        assert all(c.counters.get("release_cursor_snapshots") <= 1
                   for c in coords)
        assert all(c.counters.get("effects_send_msg") == 0 for c in coords[1:])
    finally:
        _stop(coords)


def test_new_leader_rearms_monitors_through_state_enter():
    coords, (q,), got = _batch_cluster("fl")
    try:
        old, new = coords[0], coords[1]
        sid = (q, old.name)
        assert api.process_command(sid, ("checkout", "c1", 4))[0][0] == "ok"
        stop, go = threading.Event(), threading.Event()
        go.set()
        sent = []

        def publish():
            while not stop.is_set():
                go.wait()
                try:
                    r, _ = api.process_command(
                        sid, ("enqueue", len(sent)), timeout=30)
                    sent.append(r)
                except api.RaError:
                    pass  # deposed with the entry in its log: may commit

        pub = threading.Thread(target=publish)
        pub.start()
        try:
            await_(lambda: len(sent) >= 5, what="traffic")
            target = (q, new.name)
            g_old = old.by_name[q]
            # (the hand-off wants the target caught up: hold the
            # publisher for it, and let it go on at the new leader)
            go.clear()
            await_(lambda: _transfer(old, sid, target),
                   what="transfer accepted")
            await_(lambda: new.by_name[q].role == C.R_LEADER
                   and g_old.role != C.R_LEADER, what="leader moved")
            before = len(sent)
            go.set()
            await_(lambda: len(sent) >= before + 5,
                   what="traffic at the new leader")
        finally:
            stop.set()
            go.set()
            pub.join(60)
        # the old leader forgot its watch on leaving leadership; the
        # new one's state_enter armed it again
        assert old.monitors.watchers("process", "c1") == []
        assert new.monitors.watchers("process", "c1") == [(target, "machine")]
        assert new.counters.get("monitors_armed") == 1
        assert old.process_down("c1") == 0
        assert new.process_down("c1") == 1
        await_(lambda: all("c1" not in s.consumers for s in _states(coords, q)),
               what="down applied everywhere")
        # what c1 held is ready again, in order
        st = new.by_name[q].machine_state
        ids = [i for i, _m in st.queue]
        assert ids == sorted(ids) and len(ids) >= 4
        assert new.counters.get("monitor_downs") == 1
    finally:
        _stop(coords)


def _seeded_commands(seed, n=120):
    rng = random.Random(seed)
    cmds, next_id = [], 1
    for i in range(n):
        r = rng.random()
        c = rng.choice(("c1", "c2"))
        if r < 0.45:
            cmds.append(("enqueue", f"body-{i}"))
            next_id += 1
        elif r < 0.6:
            cmds.append(("checkout", c, rng.randint(1, 4)))
        elif r < 0.85:
            cmds.append(("settle", c, rng.randint(1, next_id)))
        elif r < 0.92:
            cmds.append(("return", c, rng.randint(1, next_id)))
        elif r < 0.96:
            cmds.append(("cancel", c))
        else:
            cmds.append(("DOWN", c))  # the consumer's process goes away
    return cmds


def test_batch_and_actor_backends_agree_on_one_command_list(tmp_path):
    cmds = _seeded_commands(30)
    # the batch backend
    coords, (q,), got = _batch_cluster("fp")
    try:
        sid = (q, coords[0].name)
        g = coords[0].by_name[q]
        for cmd in cmds:
            if cmd[0] == "DOWN":
                if coords[0].process_down(cmd[1]):
                    await_(lambda: cmd[1] not in g.machine_state.consumers,
                           what="down applied (batch)")
            else:
                api.process_command(sid, cmd, timeout=30)
        await_(lambda: len({_fingerprint(s) for s in _states(coords, q)}) == 1,
               what="replicas equal")
        batch_state = _fingerprint(g.machine_state)
        batch_sent = list(got)
    finally:
        _stop(coords)
    # the actor backend
    leaderboard.clear()
    nodes = {}
    for n in ("faA", "faB", "faC"):
        nodes[n] = api.start_node(
            n, SystemConfig(name="fifo_parity", data_dir=str(tmp_path)),
            election_timeout_s=0.1, tick_interval_s=0.1, detector_poll_s=0.05)
    try:
        ids = [("fa1", "faA"), ("fa2", "faB"), ("fa3", "faC")]
        api.start_cluster("fa_q", FifoMachine, ids)
        leader = api.wait_for_leader("fa_q")
        sent = []
        for c in ("c1", "c2"):
            api.register_client(
                leader[1], c,
                lambda _f, msgs, c=c: sent.extend((c, m) for m in msgs))
        proc = nodes[leader[1]].procs[leader[0]]
        for cmd in cmds:
            if cmd[0] == "DOWN":
                if nodes[leader[1]].monitors.watchers("process", cmd[1]):
                    nodes[leader[1]].on_proc_down(cmd[1])
                    await_(lambda: cmd[1] not in
                           proc.server.machine_state.consumers,
                           what="down applied (actor)")
            else:
                api.process_command(leader, cmd, timeout=30)
        assert api.wait_for_leader("fa_q") == leader
        await_(lambda: len(sent) >= len(batch_sent), timeout=5,
               what="the actor backend's deliveries")
        assert _fingerprint(proc.server.machine_state) == batch_state
        assert sent == batch_sent
        assert any(m[0] == "delivery" for _c, m in sent)
    finally:
        for n in nodes:
            api.stop_node(n)
        leaderboard.clear()


@pytest.mark.parametrize("machine", [
    lambda: SimpleMachine(lambda c, s: s + c, 0),
    lambda: KvMachine(snapshot_interval=4),
], ids=["bench_sum_like", "ra_kv"])
def test_other_machines_move_no_effect_account(machine):
    """A machine with no ``state_enter`` and no effect but log effects
    arms nothing, sends nothing, and a leader change calls nothing."""
    coords, (q,), got = _batch_cluster("fo", machine=machine)
    try:
        sid = (q, coords[0].name)
        kv = isinstance(coords[0].by_name[q].machine, KvMachine)
        for i in range(10):
            api.process_command(sid, ("put", f"k{i}", b"v") if kv else 1,
                                timeout=30)
        target = (q, coords[1].name)
        await_(lambda: coords[0].by_name[q].log.last_index_term()[0] > 10)
        await_(lambda: _transfer(coords[0], sid, target),
               what="transfer accepted")
        await_(lambda: coords[1].by_name[q].role == C.R_LEADER,
               what="leader moved")
        api.process_command(target, ("put", "k", b"v") if kv else 1, timeout=30)
        total = {k: sum(c.counters.get(k) for c in coords) for k in FX_COUNTERS}
        assert got == []
        cursors = total.pop("release_cursors")
        snaps = total.pop("release_cursor_snapshots")
        assert all(v == 0 for v in total.values()), total
        assert (cursors > 0) == kv and snaps <= cursors
        n_fx = sum(obs.histograms().fetch(("wave", c.name, "effects_realise")).n
                   for c in coords)
        assert (n_fx > 0) == kv
        assert all(not c.monitors._tab for c in coords)
    finally:
        _stop(coords)


def test_effects_are_spans_under_a_profiler_session(tmp_path):
    """One ``ra/egress/effects`` span a step that realised effects (its
    first effect -> the end of its applies), with the node and the
    number of effects, and only while a session runs."""
    import jax

    coords, (q,), got = _batch_cluster("fs")
    try:
        sid = (q, coords[0].name)
        api.process_command(sid, ("checkout", "c1", 8), timeout=30)
        jax.profiler.start_trace(str(tmp_path),
                                 profiler_options=obs.profile_options())
        try:
            for i in range(3):
                api.process_command(sid, ("enqueue", i), timeout=30)
        finally:
            jax.profiler.stop_trace()
        data = jax.profiler.ProfileData.from_file(obs.xplane_path(str(tmp_path)))
        spans = [dict(e.stats) for plane in data.planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for e in line.events
                 if e.name == "ra/egress/effects"]
        mine = [s for s in spans if s["node"] == coords[0].name]
        assert len(mine) == 3 and all(int(s["effects"]) == 1 for s in mine)
        assert len(got) == 3
    finally:
        _stop(coords)
