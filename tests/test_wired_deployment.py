"""The wired quorum-queue deployment (``ra_fifo_10k_x3_wired.hot_queues``,
ISSUE 33) at 8 and 64 groups on the CPU through the benchmark's own
``run_cell``, judged by ``benchmark/reference/ra_fifo.py``, beside its
in-process twin on the same seed; and what it forced of the batch
backend: three ``BatchCoordinator``s with a ``NodeRegistry`` and a
``TcpTransport`` each that elect, commit, apply and serve a consistent
query over loopback sockets, survive a closed connection and a one-way
partition, and keep a short kv history linearizable.
"""

import os
import socket
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark import run as R  # noqa: E402
from ra_tpu import api, leaderboard, linearize  # noqa: E402
from ra_tpu.kv_harness import DictKv  # noqa: E402
from ra_tpu.ops import consensus as C  # noqa: E402
from ra_tpu.protocol import USR, Command, ElectionTimeout  # noqa: E402
from ra_tpu.runtime.coordinator import BatchCoordinator  # noqa: E402
from ra_tpu.runtime.transport import NodeRegistry  # noqa: E402

CELL = "ra_fifo_10k_x3_wired.hot_queues"
TWIN = "ra_fifo_10k_x3.hot_queues"
SEED = 3_000_000_019  # above 2**31, as the driver's are
WIRE = ("wire_msgs_per_frame", "wire_ms_per_kop", "wire_bytes_per_op",
        "wire_dropped_per_kop")


def await_(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(0.01)
    raise AssertionError(f"timeout waiting for {what}")


# -- the cell against the plain reference, through the same files ------------


def _cell_run(cell, groups):
    lines = []
    run = R.run_cell(
        harness.load_benchmark(), cell, SEED, 2.0, False, time.monotonic(),
        say=lambda line, **kw: lines.append((line, kw)),
        scale={"config": {"groups": groups},
               "traffic": {"warmup_s": 0.5, "trace_s": 2, "hot_queues": 4}})
    run.lines = lines
    return run


@pytest.fixture(scope="module", params=[8, 64])
def wired_run(request):
    return _cell_run(CELL, request.param)


def _line(run, name):
    return [kw for line, kw in run.lines if line == name][0]


def test_wired_cell_is_correct_by_the_reference(wired_run):
    run = wired_run
    bench = harness.load_benchmark()
    out = R.result_line(bench, run, False)
    assert out["correct"] is True, run.violations
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"ops_s", "commit_p95_ms", "setup_s"}
    assert run.ops["write"].acked > 0 and run.ops["settle"].acked > 0
    assert run.config["reference"] == "ra_fifo"
    assert run.config["deployment"] == "wired_cluster"
    h = run.history
    assert len(h["hot"]) == 4
    assert not h["unknown"] and not h["settle_unknown"] and not h["retired"]
    for g in h["hot"]:
        ids = [i for _c, i, _w, _s, _t in h["deliveries"][g]]
        assert ids == list(range(1, len(ids) + 1))
        assert sorted(i for _s, i in h["confirmed"][g]) == ids
    cluster = _line(run, "cluster")
    assert cluster["transport"] == "tcp" and len(set(cluster["ports"])) == 3
    assert cluster["connections"] == 6  # directed, all dialled by the election
    health = _line(run, "health")
    assert health["compilations_in_window"] == 0
    assert health["term_bumps_since_window_start"] == 0
    assert health["lane_wedges"] == 0 and health["detector_errors"] == 0
    assert health["issued"]["redeliveries"] == 0
    teardown = _line(run, "teardown")
    assert teardown["threads_that_outlived_stop"] == []
    assert not [t for t in teardown["python_threads"] if "ra-tcp" in t]


def test_wired_line_holds_the_transport_metrics(wired_run):
    run = wired_run
    bench = harness.load_benchmark()
    layer = R.result_line(bench, run, True)["metrics"]
    declared = {m["name"]: m for m in
                harness.metrics_of(bench, "per_layer", CELL)}
    assert set(WIRE) <= set(layer) <= set(declared)
    for name in WIRE:
        assert layer[name]["unit"] == declared[name]["unit"]
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["layer"] == "transport"
    assert layer["wire_msgs_per_frame"]["value"] > 1
    assert layer["wire_dropped_per_kop"]["value"] == 0.0
    assert layer["wire_ms_per_kop"]["value"] > 0
    # an enqueue's 1 KB body goes to two followers, a settle is small
    assert 1024 < layer["wire_bytes_per_op"]["value"] < 8 * 1024
    d = run.deltas
    # every frame written was read (both ends are in this process), and
    # the unelected metrics of the twin are here too
    assert d.counter("coordinator", "wire_frames_out") > 0
    assert d.counter("coordinator", "wire_msgs_in") > 0
    assert {"send_msgs_per_kop", "effects_ms_per_kop", "unasked_elections",
            "fsyncs_per_kop", "host_ms_per_kop",
            "rejected_per_kop"} <= set(layer)
    assert layer["unasked_elections"]["value"] == 0
    assert layer["rejected_per_kop"]["value"] == 0


def test_the_in_process_twin_agrees_on_the_same_seed():
    """One seed, both deployments: the same hot queues, both judged
    correct, and in both every publisher's acknowledged sequence
    numbers are ``0 .. n-1`` without a gap, so over the shorter run's
    length the two acknowledge the same set."""
    wired, twin = _cell_run(CELL, 8), _cell_run(TWIN, 8)
    for run in (wired, twin):
        assert not run.violations, run.violations
        assert not run.history["unknown"]
    assert wired.history["hot"] == twin.history["hot"]
    for g in wired.history["hot"]:
        a = sorted(s for s, _i in wired.history["confirmed"][g])
        b = sorted(s for s, _i in twin.history["confirmed"][g])
        assert a == list(range(len(a))) and b == list(range(len(b)))
        n = min(len(a), len(b))
        assert n > 0 and set(a[:n]) == set(b[:n])
    # the twin's line has none of the transport's metrics: nothing of
    # it leaves the process
    bench = harness.load_benchmark()
    assert not set(WIRE) & set(R.result_line(bench, twin, True)["metrics"])
    assert twin.deltas.counter("coordinator", "wire_frames_out") == 0
    for name in WIRE:
        assert harness.load_module("metrics", name).read(twin) is None


def test_wired_cluster_refuses_a_program_without_the_wire(monkeypatch):
    """The parent's ``BatchCoordinator`` takes no ``tcp``: the deployment
    says so at once and builds nothing."""
    mod = harness.load_module("deployments", "wired_cluster")
    real = BatchCoordinator.__init__

    def old_init(self, node_name, capacity=1024, num_peers=3, nodes=None):
        real(self, node_name, capacity, num_peers, nodes)

    monkeypatch.setattr(BatchCoordinator, "__init__", old_init)
    said = []
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="no result"):
        mod.Cluster({"groups": 8, "replicas": 3, "nodes": 3}, DictKv,
                    [], lambda *a, **k: said.append(a))
    assert time.monotonic() - t0 < 1 and not said


# -- three coordinators, a registry and a transport each -----------------------


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wired_coordinator(**kw):
    """A coordinator on a free loopback port, with a registry of its
    own (a port taken between the look and the bind: look again)."""
    for _ in range(8):
        try:
            return BatchCoordinator(f"127.0.0.1:{free_port()}",
                                    nodes=NodeRegistry(), tcp=True, **kw)
        except OSError:
            continue
    raise AssertionError("no free port")


class Trio:
    def __init__(self, groups=1, machine=DictKv):
        leaderboard.clear()
        self.coords = [wired_coordinator(capacity=16, num_peers=3,
                                         detector_poll_s=0.05)
                       for _ in range(3)]
        self.names = [f"wq{g}" for g in range(groups)]
        for c in self.coords:
            c.start()
        for n in self.names:
            members = [(n, c.name) for c in self.coords]
            for c in self.coords:
                c.add_group(n, f"wired_{n}", members, machine())
            self.coords[0].deliver((n, self.coords[0].name),
                                   ElectionTimeout(), None)
        await_(lambda: all(self.coords[0].by_name[n].role == C.R_LEADER
                           for n in self.names), what="election over tcp")

    def stop(self):
        transports = [c.transport for c in self.coords]
        for c in self.coords:
            c.running = False
        for c in self.coords:
            c.stop()
        leaderboard.clear()
        return [t.name for tr in transports for t in tr.threads()]

    def call(self, name, make, timeout=10.0):
        """Deliver ``make(future)`` to ``name``'s leader, following
        redirects; the reply's value."""
        deadline = time.monotonic() + timeout
        at = 0
        while time.monotonic() < deadline:
            c = self.coords[at % 3]
            fut = api.Future()
            c.deliver((name, c.name), make(fut), None)
            reply = fut.result(max(0.1, deadline - time.monotonic()))
            if reply[0] == "ok":
                return reply[1]
            nodes = [x.name for x in self.coords]
            if reply[0] == "redirect" and reply[1] is not None:
                at = nodes.index(reply[1][1])
            else:
                at += 1
                time.sleep(0.02)
        raise TimeoutError(f"no ok from {name}")

    def command(self, name, data, timeout=10.0):
        return self.call(name, lambda fut: Command(
            kind=USR, data=data, reply_mode="await_consensus",
            from_ref=fut), timeout)

    def read(self, name, fn, timeout=10.0):
        return self.call(
            name, lambda fut: ("consistent_query", fn, fut), timeout)

    def states(self, name):
        return [c.by_name[name].machine_state for c in self.coords]


@pytest.fixture
def trio():
    t = Trio()
    yield t
    assert t.stop() == []  # no thread of a transport outlives stop()


def _wire(c):
    return {k[5:]: v for k, v in c.counters.to_dict().items()
            if k.startswith("wire_")}


def test_wired_trio_elects_commits_applies_and_reads(trio):
    (q,) = trio.names
    for c in trio.coords:
        # no peer is found locally: everything went through a socket
        assert c.registry.names() == [c.name]
    for i in range(20):
        assert trio.command(q, ("put", "k", i)) == ("ok", i)
    assert trio.read(q, lambda s: s.get("k")) == 19
    await_(lambda: all(s == {"k": 19} for s in trio.states(q)),
           what="followers apply")
    # (a frame may be in flight: every message written is read)
    await_(lambda: sum(_wire(c)["msgs_out"] for c in trio.coords)
           == sum(_wire(c)["msgs_in"] for c in trio.coords),
           what="every message written is read")
    lead, f1, f2 = (_wire(c) for c in trio.coords)
    assert lead["frames_out"] > 0 and lead["msgs_out"] >= lead["frames_out"]
    assert f1["frames_in"] > 0 and f2["frames_in"] > 0
    assert lead["bytes_out"] > 0 and lead["encode_ns"] > 0
    assert f1["decode_ns"] > 0
    assert all(w["dropped"] == 0 for w in (lead, f1, f2))
    assert all(c.transport.dropped == 0 for c in trio.coords)
    # liveness by pings on the pairs that talk (dialling is lazy: two
    # followers of one group have nothing to say to each other)
    a, b, c = trio.coords
    for x, y in ((a, b), (a, c), (b, a), (c, a)):
        assert x.transport.node_alive(y.name)


def test_a_closed_connection_reconnects_and_loses_nothing_acknowledged(trio):
    (q,) = trio.names
    lead = trio.coords[0]
    acked = []
    stop = threading.Event()

    def load():
        while not stop.is_set():
            try:
                trio.command(q, ("incr", "n", 1), timeout=20)
                acked.append(1)
            except TimeoutError:
                acked.append(0)  # unknown outcome

    th = threading.Thread(target=load, daemon=True)
    th.start()
    await_(lambda: len(acked) >= 20, what="load running")
    # the leader's connection to one follower breaks under load
    peer = lead.transport._peers[trio.coords[1].name]
    old = peer.sock
    old.shutdown(socket.SHUT_RDWR)
    await_(lambda: peer.sock is not None and peer.sock is not old,
           what="lazy reconnect on the next frame")
    n = len(acked)
    await_(lambda: len(acked) >= n + 20, what="load after the reconnect")
    stop.set()
    th.join(30)
    total = trio.read(q, lambda s: s.get("n"))
    assert sum(acked) <= total <= len(acked)  # nothing acknowledged lost
    await_(lambda: all(s == {"n": total} for s in trio.states(q)),
           what="replicas converge after the reconnect")
    assert lead.transport.node_alive(trio.coords[1].name)


def test_one_way_block_drops_and_counts_per_message(trio):
    (q,) = trio.names
    a, b, c = trio.coords
    trio.command(q, ("put", "k", 0))
    await_(lambda: trio.states(q)[1] == {"k": 0}, what="b applies")
    before, was = _wire(a), a.transport.dropped
    b_in = _wire(b)["msgs_in"]
    a.transport.block(a.name, b.name)  # a's sends to b; b's to a still flow
    for i in range(1, 11):
        assert trio.command(q, ("put", "k", i)) == ("ok", i)  # quorum a + c
    lost = a.transport.dropped - was
    assert lost >= 10  # at least the ten entries' AERs to b
    assert _wire(a)["dropped"] - before["dropped"] == lost  # per message
    assert trio.states(q)[1] == {"k": 0}  # b heard nothing of them
    assert not a.transport.node_alive(b.name)
    assert b.transport.node_alive(a.name)
    a.transport.unblock_all()
    trio.command(q, ("put", "k", 11))
    await_(lambda: trio.states(q)[1] == {"k": 11}, what="b catches up")
    assert _wire(b)["msgs_in"] > b_in


def test_a_short_kv_history_on_the_wired_trio_is_linearizable(trio):
    (q,) = trio.names
    rec = linearize.HistoryRecorder()

    def do_write(key, value):
        trio.command(q, ("put", key, value) if value is not None
                     else ("delete", key))

    def do_read(key):
        return trio.read(q, lambda s, k=key: s.get(k))

    clients = [threading.Thread(
        target=linearize._client_loop,
        args=(rec, cid, 33, ["k0", "k1", "k2"], 30, do_write, do_read),
        daemon=True) for cid in range(3)]
    for t in clients:
        t.start()
    for t in clients:
        t.join(120)
    assert not any(t.is_alive() for t in clients)
    history = rec.history()
    assert sum(len(v) for v in history.values()) >= 60
    res = linearize.check_history(history)
    assert res.ok, res.violations


def test_the_wire_writes_its_spans_under_a_profiler_session(trio, tmp_path):
    """``ra/tcp/send`` on the writer threads, one a socket write, and
    ``ra/tcp/recv`` on the reader threads, one a batch frame around
    decode and ingest; only while a session runs."""
    import jax

    from ra_tpu import obs

    (q,) = trio.names
    trio.command(q, ("put", "k", 0))
    nodes = {c.name for c in trio.coords}

    def frames_in():
        return sum(_wire(c)["frames_in"] for c in trio.coords)

    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=obs.profile_options())
    try:
        before = frames_in()
        for i in range(1, 6):
            trio.command(q, ("put", "k", i))
        after = frames_in()
    finally:
        jax.profiler.stop_trace()
    data = jax.profiler.ProfileData.from_file(obs.xplane_path(str(tmp_path)))
    spans = {"ra/tcp/send": [], "ra/tcp/recv": []}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        spans[e.name].append(dict(e.stats))
    send, recv = spans["ra/tcp/send"], spans["ra/tcp/recv"]
    assert send and recv
    assert {s["node"] for s in send} <= nodes
    assert {s["peer"] for s in send} <= nodes
    assert all(int(s["frames"]) >= 1 and int(s["bytes"]) > 20 for s in send)
    assert {s["node"] for s in recv} <= nodes
    assert all(int(s["msgs"]) >= 1 and int(s["bytes"]) > 20 for s in recv)
    # one span a batch frame (frames read while the session opened or
    # closed may fall on either side)
    assert after - before - 2 <= len(recv) <= after - before + 6


def test_the_sender_thread_sends_one_batch_a_destination_a_turn(trio):
    """What queued for one destination while the sender thread waited
    its turn leaves as one ``send_batch`` (one frame), in order: fifty
    hand-offs published in one breath are a few frames, not fifty."""
    a, b, c = trio.coords
    calls = []
    real = a.transport.send_batch

    def recording(node_name, msgs):
        calls.append((node_name, [m[1] for m in msgs]))
        return real(node_name, msgs)

    a.transport.send_batch = recording
    for i in range(50):
        dest = b if i % 2 else c
        a._send_batch(dest.name, [(("nobody", dest.name), ("n", i), None)])
    def mine_of(seen):
        # (the coordinators' own traffic passes the same transport)
        kept = [(n, [x for x in m if type(x) is tuple and x[:1] == ("n",)])
                for n, m in seen]
        return [(n, m) for n, m in kept if m]

    await_(lambda: sum(len(m) for _n, m in mine_of(list(calls))) >= 50,
           what="all sent")
    mine = mine_of(calls)
    assert len(mine) <= 10
    for dest, parity in ((b, 1), (c, 0)):
        sent = [x[1] for n, m in mine if n == dest.name for x in m]
        assert sent == [i for i in range(50) if i % 2 == parity]


def test_a_taken_port_raises_before_anything_is_registered():
    """The transport binds first of all: a caller that lost the race for
    a port gets ``OSError`` and a node name nothing has heard of, so it
    can look for another port (``wired_cluster`` does)."""
    from ra_tpu import counters, health

    with socket.socket() as held:
        held.bind(("127.0.0.1", 0))
        held.listen(1)
        name = f"127.0.0.1:{held.getsockname()[1]}"
        reg = NodeRegistry()
        with pytest.raises(OSError):
            BatchCoordinator(name, capacity=8, nodes=reg, tcp=True)
        assert reg.names() == []
        assert counters.fetch(("coordinator", name)) is None
        assert name not in health.scanners()
