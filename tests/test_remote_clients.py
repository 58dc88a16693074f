"""Clients on another node (docs/INTERNALS.md, "Clients on another
node"): three ``BatchCoordinator``s joined by loopback TCP, each with a
registry of its own, and a fourth node that holds no replica (a
``RaNode`` with ``tcp=True``) in the process registry, so that every
``api`` call reaches a leader as a call frame and its reply comes back
as a frame. What crosses (a query object, never a closure), redirects
across the wire, timeouts and late replies, one reply frame a caller
node a wave, and ``kv_get`` in process answering as it did."""

import pickle
import random
import threading
import time

import pytest

from ra_tpu import api, linearize
from ra_tpu.models.kv import KvMachine, KvResolve, _digest, kv_get
from ra_tpu.protocol import Entry, LogRead, USR, Command
from ra_tpu.runtime.node import RaNode
from ra_tpu.runtime.tcp import RemoteRef
from ra_tpu.system import SystemConfig
from ra_tpu.utils.wire import wire_loads

from test_kv_one_round import (  # noqa: F401 (the fixture)
    Sent, StaleOnce, cluster, overwrite_and_cut, put)
from test_wired_deployment import Trio, await_, free_port, wired_coordinator


class Wired:
    """A wired trio of kv groups and a client node."""

    def __init__(self, tmp_path, groups):
        self.trio = Trio(groups=groups, machine=KvMachine)
        self.client = None
        for _ in range(8):
            try:
                self.client = RaNode(
                    f"127.0.0.1:{free_port()}", tcp=True,
                    config=SystemConfig(name="remote_clients",
                                        data_dir=str(tmp_path)))
                break
            except OSError:
                continue
        assert self.client is not None, "no free port"
        self.coords = self.trio.coords
        self.names = self.trio.names

    def counts(self, node=None):
        t = (node or self.client).transport
        return t.client.to_dict()

    def stop(self):
        self.client.stop()
        left = [t.name for t in self.client.transport.threads()]
        return self.trio.stop() + left


@pytest.fixture
def wired(tmp_path):
    w = Wired(tmp_path, groups=4)
    yield w
    assert w.stop() == []  # no thread of a transport outlives stop()


def _mix(seed, n):
    """A seeded mix of puts and kv_gets over a few keys: (op, key,
    value)."""
    rng = random.Random(seed)
    return [("put", f"k{rng.randrange(4)}", rng.randbytes(16))
            if rng.random() < 0.5 else ("get", f"k{rng.randrange(5)}", None)
            for _ in range(n)]


class InProcess:
    """``api``'s query surface over the trio's in-process calls (a
    local future delivered to the coordinator), for ``kv_get``."""

    def __init__(self, trio):
        self.trio = trio
        self.sent = []

    def consistent_query(self, member, fn, timeout=5.0):
        self.sent.append(fn)
        return ("ok", self.trio.read(member[0], fn, timeout))


def test_a_seeded_mix_answers_alike_over_the_wire_and_in_process(wired):
    lead = wired.coords[0]
    remote_groups, local_groups = wired.names[:2], wired.names[2:]
    local = InProcess(wired.trio)
    replies = {"remote": [], "local": []}
    for r_g, l_g in zip(remote_groups, local_groups):
        for op, key, value in _mix(1_234, 40):
            if op == "put":
                got, leader = api.process_command(
                    (r_g, lead.name), ("put", key, value))
                assert leader == (r_g, lead.name)
                replies["remote"].append(got)
                replies["local"].append(
                    wired.trio.command(l_g, ("put", key, value)))
            else:
                replies["remote"].append(
                    kv_get(api, (r_g, lead.name), key))
                replies["local"].append(kv_get(local, (l_g, lead.name), key))
    assert replies["remote"] == replies["local"]
    assert any(type(r) is bytes for r in replies["remote"])  # reads hit
    assert any(r is None for r in replies["remote"])  # and missed
    assert all(type(fn) is KvResolve for fn in local.sent)
    for r_g, l_g in zip(remote_groups, local_groups):
        await_(lambda: all(s == wired.trio.states(l_g)[0]
                           for s in wired.trio.states(r_g)
                           + wired.trio.states(l_g)),
               what="equal maps on all three replicas")
    c = wired.counts()
    assert c["client_calls_out"] == c["client_replies_in"] \
        == sum(wired.counts(x)["client_calls_in"] for x in wired.coords)
    assert c["client_timeouts"] == c["client_late_replies"] == 0
    assert not wired.client.transport._calls


def test_a_history_through_the_client_node_is_linearizable(wired):
    sid = (wired.names[0], wired.coords[0].name)
    rec = linearize.HistoryRecorder()

    def do_write(key, value):
        api.process_command(sid, ("put", key, value) if value is not None
                            else ("delete", key))

    def do_read(key):
        return kv_get(api, sid, key)

    clients = [threading.Thread(
        target=linearize._client_loop,
        args=(rec, cid, 43, ["k0", "k1", "k2"], 30, do_write, do_read),
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
    assert wired.counts()["client_calls_out"] >= 90


def test_a_query_object_crosses_and_a_closure_never_leaves(wired):
    q = wire_loads(pickle.dumps(("consistent_query", KvResolve("k"), None)))
    assert type(q[1]) is KvResolve and q[1].key == "k"
    assert q[1]({"k": (7, b"d")}) == LogRead(7, b"d")
    assert q[1]({}) is None
    sid = (wired.names[0], wired.coords[0].name)
    before = wired.counts()
    for fn in (lambda s: s, len, KvResolve("k").__call__):
        t0 = time.monotonic()
        with pytest.raises(api.RaError, match="cannot cross"):
            api.consistent_query(sid, fn, timeout=5)
        assert time.monotonic() - t0 < 0.5  # at once, not a timeout
    assert wired.counts() == before  # nothing was sent
    assert not wired.client.transport._calls
    # members() crosses: its query is an object
    got, leader = api.members(sid)
    assert set(got) == {(wired.names[0], c.name) for c in wired.coords}
    assert leader == sid


def test_a_call_to_a_follower_is_redirected_across_the_wire(wired):
    lead, follower = wired.coords[0], wired.coords[1]
    name = wired.names[0]
    before = {n.name: wired.counts(n)["client_calls_in"]
              for n in (lead, follower)}
    reply, leader = api.process_command((name, follower.name),
                                        ("put", "k", b"v"))
    assert reply[0] == "ok" and leader == (name, lead.name)
    assert wired.counts()["client_redirects"] == 1
    assert wired.counts(follower)["client_calls_in"] \
        == before[follower.name] + 1
    assert wired.counts(lead)["client_calls_in"] == before[lead.name] + 1
    assert kv_get(api, (name, follower.name), "k") == b"v"
    assert wired.counts()["client_redirects"] == 2


def test_a_blocked_call_times_out_and_a_late_reply_is_dropped(wired):
    lead = wired.coords[0]
    sid = (wired.names[0], lead.name)
    me = wired.client.transport
    api.process_command(sid, ("put", "k", b"0"))
    # the client's frames to the leader are lost: the call times out
    me.block(wired.client.name, lead.name)
    t0 = time.monotonic()
    with pytest.raises(api.RaError, match="timed out"):
        api.process_command(sid, ("put", "k", b"1"), timeout=0.3)
    assert time.monotonic() - t0 < 2
    assert not me._calls
    assert wired.counts()["client_timeouts"] == 1
    me.unblock_all()
    # the leader holds its state lock past the caller's timeout: the
    # reply comes, late, and is counted and dropped
    with lead._state_lock:
        with pytest.raises(api.RaError, match="timed out"):
            api.process_command(sid, ("put", "k", b"2"), timeout=0.3)
        assert not me._calls
    await_(lambda: wired.counts()["client_late_replies"] == 1,
           what="the late reply")
    assert wired.counts()["client_timeouts"] == 2
    # the late put did commit: its outcome was unknown, never lost
    assert kv_get(api, sid, "k") == b"2"


def test_one_waves_replies_to_one_caller_node_leave_in_one_frame(wired):
    """A coordinator gathers what its wave answers callers on one node
    and sends it as one frame when the turn ends. The replying node here
    is a wired coordinator that is not started, so that no wave but this
    test's turns it."""
    me = wired.client.transport
    rep = wired_coordinator(capacity=16, num_peers=3)
    try:
        # calls pending on the client node, their frames lost on the way
        me.block(wired.client.name, rep.name)
        futs = [api.Future() for _ in range(5)]
        corrs = [me.send_call(("g", rep.name),
                              ("consistent_query", KvResolve("k"), None), 1,
                              f) for f in futs]
        me.unblock_all()
        before = wired.counts()
        token = rep._coop_drainer()  # this thread is the wave's
        try:
            for i, corr in enumerate(corrs):
                rep._reply(RemoteRef(rep.transport, wired.client.name, corr,
                                     0), ("ok", i, ("g", rep.name)))
            assert [len(v) for v in rep._client_out.values()] == [5]
            assert not futs[0].done()  # nothing left before the turn's end
            rep._flush_client_replies()
        finally:
            rep._coop_done(token)
        assert [f.result(5)[1] for f in futs] == [0, 1, 2, 3, 4]
        sent = rep.transport.client.to_dict()
        assert sent["client_frames_out"] == 1
        assert sent["client_replies_out"] == 5
        after = wired.counts()
        assert after["client_replies_in"] - before["client_replies_in"] == 5
        assert after["client_late_replies"] == 0
        assert not me._calls
    finally:
        rep.stop()
    assert not rep.transport.threads()


def test_a_command_crosses_without_its_reply_handle_and_keeps_no_ref(wired):
    """The leader's log and its followers never hold the remote handle:
    the entry as replicated carries neither a reference nor a stamp."""
    lead = wired.coords[0]
    name = wired.names[0]
    (reply, _leader) = api.process_command((name, lead.name),
                                           ("put", "k", b"x"))
    idx = reply[1]
    for c in wired.coords:
        await_(lambda c=c: c.by_name[name].log.fetch(idx) is not None,
               what="the entry on every replica")
        cmd = c.by_name[name].log.fetch(idx).cmd
        assert type(cmd) is Command and cmd.kind == USR
        assert cmd.from_ref is None and cmd.ts is None
    with pytest.raises(TypeError):
        pickle.dumps(RemoteRef(lead.transport, "n", 1, 0))


def test_a_reply_leaves_behind_what_belongs_to_its_process():
    """A reject's gate waiter is an event of the leader's process, and an
    entry a ``LogRead`` answer carries may still hold the leader's reply
    handle (the actor core keeps it in its log): neither crosses."""
    from ra_tpu.runtime.tcp import _wire_reply

    gate = threading.Event()
    assert _wire_reply(("reject", "overloaded", gate)) \
        == ("reject", "overloaded")
    held = Entry(5, 1, Command(USR, ("put", "k", b"v"),
                               from_ref=api.Future(), ts=123))
    out = _wire_reply(("ok", LogRead(5, b"d", held), ("g", "n")))
    assert out == ("ok", LogRead(5, b"d", Entry(5, 1, Command(
        USR, ("put", "k", b"v")))), ("g", "n"))
    assert wire_loads(pickle.dumps(out)) == out
    plain = ("ok", ("ok", 7), ("g", "n"))
    assert _wire_reply(plain) is plain


# -- kv_get in process: what it returns is unchanged ---------------------------


def test_kv_get_in_process_returns_as_before(cluster, monkeypatch):  # noqa: F811
    put(cluster.leader, "k", {"v": 1})
    sent = Sent(monkeypatch)
    assert kv_get(api, cluster.leader, "k") == {"v": 1}
    assert kv_get(api, cluster.leader, "nope") is None
    assert sent.kinds() == ["consistent_query"] * 2
    assert [type(m[1]) for m in sent.msgs] == [KvResolve] * 2


def test_kv_get_in_process_asks_again_after_a_miss(cluster,  # noqa: F811
                                                   monkeypatch):
    a, _b = overwrite_and_cut(cluster)
    truth = cluster.state()
    cluster.set_state(StaleOnce(truth, "k", (a, _digest("old"))))
    sent = Sent(monkeypatch)
    try:
        assert kv_get(api, cluster.leader, "k") == "new"
    finally:
        cluster.set_state(truth)
    assert [type(m[1]) for m in sent.msgs] == [KvResolve] * 2


def test_kv_get_reads_the_entry_a_query_object_names():
    """Against a stand-in for ``api``: one answer with the entry, a
    miss then the entry, an absent key."""
    value = b"value"
    entry = Entry(5, 1, Command(USR, ("put", "k", value)))

    class Api:
        def __init__(self, answers):
            self.answers, self.asked = list(answers), []

        def consistent_query(self, member, fn, timeout=5.0):
            self.asked.append(fn)
            return self.answers.pop(0)

    hit = ("ok", LogRead(5, _digest(value), entry), ("g", "n"))
    miss = ("ok", LogRead(5, _digest(value), None), ("g", "n"))
    for answers, want in (([hit], value), ([miss, hit], value),
                          ([("ok", None, ("g", "n"))], None),
                          ([miss, miss, miss], None)):
        stand_in = Api(answers)
        assert kv_get(stand_in, ("g", "n"), "k") == want
        assert not stand_in.answers
        assert all(type(fn) is KvResolve and fn.key == "k"
                   for fn in stand_in.asked)
