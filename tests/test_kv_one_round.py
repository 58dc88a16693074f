"""A ``kv_get`` in one round (docs/INTERNALS.md §13).

A consistent query's function may name an entry of the group's own log
(``protocol.LogRead``); the replica that issues the answer reads it
there and then, so ``kv_get`` is one request and one reply. Covered on
both backends: one message and one future a read, every site that
answers a consistent query (single voter, lease, quorum round; on the
actor core also the lease read parked for its apply), a plain-valued
function answered as before, an index the log has cut (a miss, counted,
re-asked), a digest that does not match, a deposed leader's pending
query, and a concurrent history through ``kv_get`` checked for
linearizability on the batch backend.
"""

import threading

import pytest

from ra_tpu import api, leaderboard, linearize
from ra_tpu.models.kv import KvMachine, _digest, kv_get
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import ElectionTimeout, Entry, LogRead
from ra_tpu.runtime.transport import registry as node_registry
from ra_tpu.server import LEADER
from ra_tpu.system import SystemConfig

from harness import Net, make_server, three_node_net
from test_batch_parity import await_, mk_cluster, stop_all
from test_lease_runtime import FakeClock, lease_net

S1 = ("s1", "nodeA")


def kv():
    return KvMachine(snapshot_interval=4)


def put(sid, key, value):
    r, _ = api.process_command(sid, ("put", key, value), timeout=20)
    assert r[0] == "ok", r
    return r[1]


def name_index(idx):
    return lambda st: LogRead(idx, "note")


def assert_resolved(answer, idx, key, value):
    assert type(answer) is LogRead
    assert (answer.index, answer.note) == (idx, "note")
    assert isinstance(answer.entry, Entry) and answer.entry.index == idx
    assert answer.entry.cmd.data == ("put", key, value)


# ---------------------------------------------------------------------------
# the two backends, started: one fixture gives (leader sid, state handle)


class Batch:
    """Three started coordinators, one kv group, leader on node 0."""

    def __init__(self, pfx, **kw):
        self.coords = mk_cluster(pfx, machine=kv, **kw)
        self.leader = (f"{pfx}g0", f"{pfx}0")
        self.group = self.coords[0].by_name[self.leader[0]]
        self.lock = self.coords[0]._state_lock

    def counter(self, name):
        return self.coords[0].counters.get(name)

    @property
    def log(self):
        return self.group.log

    def state(self):
        return self.group.machine_state

    def set_state(self, st):
        with self.lock:
            self.group.machine_state = st

    def stop(self):
        stop_all(self.coords)


class Actor:
    """Three started nodes, one kv cluster."""

    NODES = ("orA", "orB", "orC")

    def __init__(self, tmp_path):
        leaderboard.clear()
        for n in self.NODES:
            cfg = SystemConfig(name="onernd", data_dir=str(tmp_path))
            cfg.min_snapshot_interval = 4
            api.start_node(n, cfg, election_timeout_s=0.1,
                           tick_interval_s=0.1, detector_poll_s=0.05)
        ids = [(f"or{i}", n) for i, n in enumerate(self.NODES)]
        api.start_cluster("onernd", kv, ids)
        self.leader = api.wait_for_leader("onernd")
        self.server = node_registry().get(
            self.leader[1]).procs[self.leader[0]].server

    def counter(self, name):
        return None  # RA_SERVER_FIELDS has no read-leg accounts

    @property
    def log(self):
        return self.server.log

    def state(self):
        return self.server.machine_state

    def set_state(self, st):
        self.server.machine_state = st

    def stop(self):
        for n in self.NODES:
            try:
                api.stop_node(n)
            except Exception:  # noqa: BLE001
                pass
        leaderboard.clear()


@pytest.fixture(params=["tpu_batch", "per_group_actor"])
def cluster(request, tmp_path):
    c = Batch("or1") if request.param == "tpu_batch" else Actor(tmp_path)
    try:
        yield c
    finally:
        c.stop()


class Sent:
    """What ``kv_get`` hands to ``api._try_send`` and how many futures
    it makes."""

    def __init__(self, monkeypatch):
        self.msgs = []
        self.futures = 0
        orig_send, orig_future, sent = api._try_send, api.Future, self

        def try_send(sid, msg):
            sent.msgs.append(msg)
            return orig_send(sid, msg)

        class CountedFuture(orig_future):
            __slots__ = ()

            def __init__(self):
                sent.futures += 1
                super().__init__()

        monkeypatch.setattr(api, "_try_send", try_send)
        monkeypatch.setattr(api, "Future", CountedFuture)

    def kinds(self):
        return [m[0] for m in self.msgs]


def test_a_present_key_is_one_request_and_one_reply(cluster, monkeypatch):
    put(cluster.leader, "k", {"v": 1})
    fetched = cluster.counter("state_queries")
    sent = Sent(monkeypatch)
    assert kv_get(api, cluster.leader, "k") == {"v": 1}
    assert sent.kinds() == ["consistent_query"]
    assert sent.futures == 1
    if fetched is not None:
        # the log read is booked on the leg's counters, by the helper
        assert cluster.counter("state_queries") == fetched + 1
        assert cluster.counter("read_log_misses") == 0


def test_an_absent_key_is_none_after_one_round(cluster, monkeypatch):
    put(cluster.leader, "k", 1)
    fetched = cluster.counter("state_queries")
    sent = Sent(monkeypatch)
    assert kv_get(api, cluster.leader, "nope") is None
    assert sent.kinds() == ["consistent_query"]
    assert sent.futures == 1
    if fetched is not None:
        assert cluster.counter("state_queries") == fetched  # no log read


class StaleOnce(dict):
    """The index map as a reader that resolved before an overwrite saw
    it: the first ``get`` of ``key`` names the old entry."""

    def __init__(self, truth, key, old):
        super().__init__(truth)
        self._stale = {key: old}

    def get(self, key, default=None):
        return self._stale.pop(key, None) or super().get(key, default)


def overwrite_and_cut(cluster):
    """k = "old" then k = "new", then enough puts for a release cursor
    past both: the log no longer holds the first entry."""
    a = put(cluster.leader, "k", "old")
    b = put(cluster.leader, "k", "new")
    for i in range(8):
        put(cluster.leader, f"fill{i}", i)
    await_(lambda: (cluster.log.snapshot_index_term() or (0, 0))[0] > b,
           what="a snapshot past both writes")
    await_(lambda: cluster.log.fetch(a) is None, what="the dead entry cut")
    assert cluster.log.fetch(b) is not None  # live: the state names it
    return a, b


def test_a_cut_index_is_a_miss_and_kv_get_asks_again(cluster, monkeypatch):
    a, b = overwrite_and_cut(cluster)
    missed = cluster.counter("read_log_misses")
    # the answer names what the log has cut: the entry comes back None
    out = api.consistent_query(cluster.leader, name_index(a), timeout=20)
    assert out[0] == "ok" and out[1] == LogRead(a, "note", None)
    if missed is not None:
        assert cluster.counter("read_log_misses") == missed + 1
    # kv_get whose first answer saw the key at the cut index re-asks
    # and returns the newer value
    truth = cluster.state()
    cluster.set_state(StaleOnce(truth, "k", (a, _digest("old"))))
    sent = Sent(monkeypatch)
    try:
        assert kv_get(api, cluster.leader, "k") == "new"
    finally:
        cluster.set_state(truth)
    assert sent.kinds() == ["consistent_query"] * 2
    assert sent.futures == 2
    if missed is not None:
        assert cluster.counter("read_log_misses") == missed + 2


def test_a_key_that_stays_cut_gives_up_after_three_rounds(cluster,
                                                          monkeypatch):
    a, _b = overwrite_and_cut(cluster)
    truth = cluster.state()
    cluster.set_state({**truth, "k": (a, _digest("old"))})
    sent = Sent(monkeypatch)
    try:
        assert kv_get(api, cluster.leader, "k") is None
    finally:
        cluster.set_state(truth)
    assert sent.kinds() == ["consistent_query"] * 3


def test_a_digest_mismatch_still_raises(cluster):
    idx = put(cluster.leader, "k", "value")
    truth = cluster.state()
    cluster.set_state({**truth, "k": (idx, _digest("another"))})
    try:
        with pytest.raises(IOError, match="digest mismatch"):
            kv_get(api, cluster.leader, "k")
    finally:
        cluster.set_state(truth)
    assert kv_get(api, cluster.leader, "k") == "value"


# ---------------------------------------------------------------------------
# the coordinator's three answering sites


def _batch_site(site):
    if site == "single_voter":
        leaderboard.clear()
        from ra_tpu.runtime.coordinator import BatchCoordinator

        c = BatchCoordinator("os0", capacity=16, num_peers=3)
        c.start()
        sid = ("osg0", "os0")
        c.add_group("osg0", "oscl0", [sid], kv())
        c.deliver(sid, ElectionTimeout(), None)
        await_(lambda: c.by_name["osg0"].role == C.R_LEADER, what="election")
        return {0: c}, sid, None
    pfx = "ol" if site == "lease" else "oq"
    coords = mk_cluster(pfx, machine=kv, lease=site == "lease")
    served = "read_lease_served" if site == "lease" else "read_quorum_rounds"
    return coords, (f"{pfx}g0", f"{pfx}0"), served


@pytest.mark.parametrize("site", ["single_voter", "lease", "quorum"])
def test_each_coordinator_site_resolves_a_log_read(site):
    coords, sid, served = _batch_site(site)
    try:
        cnt = coords[0].counters
        idx = put(sid, "k", "v1")
        put(sid, "other", 2)

        def ask(fn):
            # a lease is earned by acks: ask until this site answered
            for _ in range(200):
                before = cnt.get(served) if served else 0
                out = api.consistent_query(sid, fn, timeout=20)
                assert out[0] == "ok" and out[2] == sid, out
                if served is None or cnt.get(served) > before:
                    return out[1]
            raise AssertionError(f"{served} never rose")

        fetched = cnt.get("state_queries")
        assert_resolved(ask(name_index(idx)), idx, "k", "v1")
        assert cnt.get("state_queries") > fetched
        assert cnt.get("state_query_ns") > 0
        assert cnt.get("read_log_misses") == 0
        # a plain value is answered as before, and books no log read
        fetched = cnt.get("state_queries")
        assert ask(lambda st: sorted(st)) == ["k", "other"]
        assert ask(lambda st: st.get("nope")) is None
        assert cnt.get("state_queries") == fetched
        # an index the log never held: a miss, the query still answered
        assert ask(name_index(10_000)) == LogRead(10_000, "note", None)
        assert cnt.get("read_log_misses") >= 1
    finally:
        stop_all(coords)


def test_a_deposed_leaders_pending_query_still_gets_redirect():
    coords = mk_cluster("od", machine=kv)
    try:
        sid = ("odg0", "od0")
        idx = put(sid, "k", "v")
        # cut the leader off: its query's heartbeats reach no one
        for o in ("od1", "od2"):
            coords[0].transport.block("od0", o)
            coords[int(o[-1])].transport.block(o, "od0")
        fut = api.Future()
        coords[0].deliver(sid, ("consistent_query", name_index(idx), fut),
                          None)
        g = coords[0].by_name["odg0"]
        await_(lambda: len(g.pending_queries) == 1, what="query pending")
        coords[1].deliver(("odg0", "od1"), ElectionTimeout(), None)
        await_(lambda: any(coords[i].by_name["odg0"].role == C.R_LEADER
                           for i in (1, 2)), what="majority takes over")
        assert not fut.done()
        for c in coords.values():
            c.transport.unblock_all()
        out = fut.result(20)
        assert out[0] == "redirect", out
        assert g.role != C.R_LEADER and g.pending_queries == []
        # and the read, sent again, is answered by the new leader
        assert kv_get(api, sid, "k") == "v"
    finally:
        stop_all(coords)


# ---------------------------------------------------------------------------
# the actor core's sites, on the in-test net


def _replies(net, ref):
    return [r for f, r in net.replies if f == ref]


def _kv_net(lease):
    if not lease:
        return three_node_net(kv), None
    clk = FakeClock()
    net = lease_net(clk)
    for s in net.servers.values():
        s.machine = kv()
        s.machine_state = {}
    return net, clk


@pytest.mark.parametrize("site", ["quorum", "lease", "parked_lease_read",
                                  "single_voter"])
def test_each_server_site_resolves_a_log_read(site):
    if site == "single_voter":
        net = Net({S1: make_server(S1, [S1], kv())})
    else:
        net, clk = _kv_net(lease=site != "quorum")
    net.elect(S1)
    s1 = net.servers[S1]
    net.command(S1, ("put", "k", "v1"), from_ref="w")
    (wrote,) = _replies(net, "w")
    idx = wrote[1][1]  # ("ok", ("ok", index), leader)
    if site == "parked_lease_read":
        # a lease read admitted at a read index that is not applied
        # yet waits for the apply, and is answered from there
        nxt = s1.log.next_index()
        s1.pending_lease_reads.append((nxt, "q", name_index(idx)))
        s1.pending_lease_reads.append((nxt, "plain", lambda st: sorted(st)))
        assert not _replies(net, "q")
        net.command(S1, ("put", "other", 2))
    else:
        if site == "lease":
            assert s1._lease.valid(clk.monotonic())
        net.deliver(S1, ("consistent_query", name_index(idx), "q"))
        net.deliver(S1, ("consistent_query", lambda st: sorted(st), "plain"))
        net.run()
        if site == "lease":
            assert s1.counter.get("read_lease_served") == 2
    (answer,) = _replies(net, "q")
    assert answer[0] == "ok" and answer[2] == S1
    assert_resolved(answer[1], idx, "k", "v1")
    (plain,) = _replies(net, "plain")
    assert plain[0] == "ok" and plain[1][0] == "k"
    # an index the log does not hold: the entry comes back None
    net.deliver(S1, ("consistent_query", name_index(10_000), "miss"))
    net.run()
    assert _replies(net, "miss") == [("ok", LogRead(10_000, "note", None), S1)]
    assert s1.role == LEADER


# ---------------------------------------------------------------------------
# linearizability with every read through kv_get, batch backend


def test_a_kv_get_history_is_linearizable_on_the_batch_backend():
    coords = mk_cluster("oh", machine=kv)
    try:
        ids = [("ohg0", f"oh{i}") for i in range(3)]
        rec = linearize.HistoryRecorder()

        def do_write(key, value):
            cmd = ("put", key, value) if value is not None \
                else ("delete", key)
            api.process_command(ids[0], cmd, timeout=20)

        def do_read(key):
            return kv_get(api, ids[0], key, timeout=20)

        clients = [threading.Thread(
            target=linearize._client_loop,
            args=(rec, cid, 34, ["k0", "k1", "k2"], 40, do_write, do_read),
            daemon=True) for cid in range(4)]
        for t in clients:
            t.start()
        for t in clients:
            t.join(120)
        assert not any(t.is_alive() for t in clients)
        history = rec.history()
        reads = [o for ops in history.values() for o in ops
                 if o.kind == "read"]
        assert sum(len(v) for v in history.values()) >= 120
        # the release cursor (every 4th index) cut the log under the
        # reads: values came from live entries only
        assert coords[0].by_name["ohg0"].log.snapshot_index_term()
        assert any(o.value is not None for o in reads)
        res = linearize.check_history(history)
        assert res.ok, res.violations
        assert coords[0].counters.get("state_queries") > 0
    finally:
        stop_all(coords)
