"""A consistent read's three legs at ingest (docs/INTERNALS.md §13).

On the batch backend the leader's ``consistent_query``, the follower's
``HeartbeatRpc`` and the leader's ``HeartbeatReply`` read host mirrors
only. The ingest pass that routes one handles it there and then, before
the pass packs and dispatches, while no ticket of the coordinator is
dispatched and unrealised (a caller's answer is set as the turn ends,
where every reply of the turn is); with one unrealised they ride the
pass's ticket as every other rare message does. Driven by ``step_once`` on a
one-group ``ra_kv`` cluster of three coordinators: the read's five
messages and when its answer is set, a heartbeat against a vote in the
same and in an earlier pass, and the guard.
"""

import time

import numpy as np
import pytest

from ra_tpu import api
from ra_tpu.models.kv import KvMachine, kv_get
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import (USR, Command, ElectionTimeout, HeartbeatReply,
                             RequestVoteResult, RequestVoteRpc)
from ra_tpu.runtime.coordinator import BatchCoordinator
from ra_tpu.runtime.transport import NodeRegistry

STEPS = ("consensus_step_packed", "consensus_step_packed_scat",
         "consensus_step_packed_sub_scat")


class Cluster:
    """Three never-started coordinators, one ``ra_kv`` group led by the
    first, turned by ``step_once`` from the test's thread."""

    def __init__(self):
        reg = NodeRegistry()
        self.coords = [BatchCoordinator(f"ri{i}", capacity=8, num_peers=3,
                                        nodes=reg) for i in range(3)]
        self.ids = [("rg", c.name) for c in self.coords]
        for c in self.coords:
            c.add_group("rg", "ricl", self.ids, KvMachine())
        self.coords[0].deliver(self.ids[0], ElectionTimeout(), None)
        self.drive(lambda: self.group(0).role == C.R_LEADER, "a leader")
        self.settle()

    def group(self, i):
        return self.coords[i].by_name["rg"]

    def step(self):
        return any([c.step_once() for c in self.coords])

    def settle(self):
        while self.step():
            pass

    def drive(self, cond, what):
        deadline = time.monotonic() + 30
        while not cond():
            assert time.monotonic() < deadline, what
            if not self.step():
                time.sleep(0.001)

    def put(self, key, value):
        fut = api.Future()
        self.coords[0].deliver(self.ids[0], Command(
            kind=USR, data=("put", key, value), from_ref=fut,
            reply_mode="await_consensus"), None)
        self.drive(fut.done, "the put's reply")
        self.settle()

    def query(self, fn):
        """A consistent query delivered to the leader, not yet routed."""
        fut = api.Future()
        self.coords[0].deliver(self.ids[0], ("consistent_query", fn, fut),
                               None)
        return fut

    def count(self, name):
        return [c.counters.get(name) for c in self.coords]

    def stop(self):
        for c in self.coords:
            c.stop()


@pytest.fixture
def cluster():
    c = Cluster()
    yield c
    c.stop()


def record_sends(coord):
    """What ``coord`` sends from now on, in order (delivered as before)."""
    sent = []

    def keep(to, msg):
        sent.append(msg)
        return False

    coord.transport.drop_fn = keep
    return sent


class DrivenApi:
    """The ``api`` that ``kv_get`` calls, its queries handed to the
    leader and the cluster turned until the answer is set. A write of
    another key goes with each query, so that the read's passes have a
    step to dispatch."""

    def __init__(self, cluster):
        self.cluster = cluster
        self.futures = []

    def consistent_query(self, member, fn, timeout):
        self.cluster.coords[0].deliver(self.cluster.ids[0], Command(
            kind=USR, data=("put", "w", len(self.futures)),
            reply_mode="noreply"), None)
        fut = self.cluster.query(fn)
        self.futures.append(fut)
        self.cluster.drive(fut.done, "the read's answer")
        return fut.value


def test_a_kv_get_is_five_rare_messages_at_ingest(cluster, monkeypatch):
    """One ``kv_get``: the query and two heartbeat replies on the
    leader, a heartbeat on each follower, all handled at ingest and none
    on a ticket. The pass that takes the reply that makes the quorum
    reads the answer (the log read) before it dispatches, and the
    caller's future is set as that turn ends, where a commit's reply
    is."""
    cluster.put("k", "v")
    early0, handled0 = cluster.count("rares_early"), cluster.count(
        "rares_handled")
    lead = cluster.coords[0]
    read0 = lead.counters.get("state_queries")
    shim = DrivenApi(cluster)
    at_dispatch, after_turn = [], []

    def seen():
        return (lead.counters.get("rares_early") - early0[0],
                lead.counters.get("state_queries") - read0,
                [f.done() for f in shim.futures])

    def wrapped(step):
        def run(state, packed):
            at_dispatch.append(seen())
            return step(state, packed)
        return run

    for name in STEPS:
        monkeypatch.setattr(C, name, wrapped(getattr(C, name)))
    turn = lead.step_once

    def lead_turn():
        out = turn()
        after_turn.append(seen())
        return out

    monkeypatch.setattr(lead, "step_once", lead_turn)
    assert kv_get(shim, cluster.ids[0], "k") == "v"
    cluster.settle()
    early = [a - b for a, b in zip(cluster.count("rares_early"), early0)]
    assert early == [3, 1, 1]
    assert cluster.count("rares_handled") == handled0
    (fut,) = shim.futures
    assert fut.value[0] == "ok"
    # the leader's query (1) and the first reply (2) give the quorum:
    # that pass read the answer before it dispatched ...
    assert any(n >= 2 and read == 1 for n, read, _d in at_dispatch), \
        at_dispatch
    # ... and its turn ended with the future set
    assert all(done == [True] for n, _r, done in after_turn if n >= 2), \
        after_turn
    assert any(n >= 2 for n, _r, _d in after_turn)


def vote_request(cluster, term):
    """A ``RequestVoteRpc`` from the third replica, as up to date as the
    second, delivered to the second."""
    li, lt = cluster.group(1).log.last_index_term()
    cluster.coords[1].deliver(
        cluster.ids[1], RequestVoteRpc(term, cluster.ids[2], li, lt),
        cluster.ids[2])


def test_a_heartbeat_and_a_vote_in_one_pass_ack_then_vote(cluster):
    """The old leader's heartbeat at T and a vote request at T + 1 reach
    a follower in one pass: it acks at T as it routes the heartbeat,
    then votes in the step, which is one order the network could have
    delivered them in."""
    term = cluster.group(0).term
    sent = record_sends(cluster.coords[1])
    fut = cluster.query(lambda st: sorted(st))
    assert cluster.coords[0].step_once()  # registered: heartbeats out
    assert cluster.group(0).pending_queries
    vote_request(cluster, term + 1)
    assert cluster.coords[1].step_once()  # the heartbeat, then the vote
    kinds = [m for m in sent
             if type(m) in (HeartbeatReply, RequestVoteResult)]
    assert len(kinds) == 2, sent
    ack, vote = kinds
    assert type(ack) is HeartbeatReply and ack.term == term
    assert ack.query_index >= 1
    assert type(vote) is RequestVoteResult
    assert vote.term == term + 1 and vote.vote_granted
    assert cluster.group(1).term == term + 1
    # the ack was given before the vote: it counts at T
    assert cluster.coords[0].step_once()
    assert fut.done() and fut.value[0] == "ok", fut.value


def test_a_vote_in_an_earlier_pass_redirects_the_old_leaders_query(
        cluster):
    """The follower voted at T + 1 in an earlier pass: its answer to the
    old leader's heartbeat carries T + 1, the old leader steps down, and
    its pending query is redirected, never answered."""
    term = cluster.group(0).term
    # the third replica hears nothing from the leader: the second alone
    # could confirm the read
    cluster.coords[0].transport.block("ri0", "ri2")
    vote_request(cluster, term + 1)
    assert cluster.coords[1].step_once()
    assert cluster.group(1).term == term + 1
    sent = record_sends(cluster.coords[1])
    fut = cluster.query(lambda st: sorted(st))
    assert cluster.coords[0].step_once()
    assert cluster.group(0).pending_queries
    assert cluster.coords[1].step_once()
    acks = [m for m in sent if type(m) is HeartbeatReply]
    assert acks == [HeartbeatReply(term=term + 1, query_index=-1)]
    assert cluster.coords[0].step_once()
    assert fut.done() and fut.value == ("redirect", None), fut.value
    g = cluster.group(0)
    assert g.role != C.R_LEADER and g.term == term + 1
    assert g.pending_queries == []


def hold(coord):
    """A ticket dispatched on ``coord`` and left unrealised: a forced
    step of the group, with the lanes empty."""
    with coord._step_lock:
        coord._hot.add(coord.by_name["rg"].gid)
        ticket = coord._drain_and_dispatch(coord._drain_classify())
    assert ticket is not None and ticket.stepped
    assert coord._unrealised == 1
    return ticket


def pass_behind(coord, held):
    """``coord``'s next pass while ``held`` is unrealised, then both
    realised in order. Returns what that pass left on its ticket."""
    with coord._step_lock:
        ticket = coord._drain_and_dispatch(coord._drain_classify())
        assert ticket is not None
        kinds = [type(m).__name__ if type(m) is not tuple else m[0]
                 for _g, m, _f in ticket.rare]
        coord._realise(held)
        coord._realise(ticket)
    assert coord._unrealised == 0
    return kinds


def test_with_a_ticket_unrealised_the_read_rides_the_ticket(cluster):
    """The guard: where an ingest finds a ticket of its coordinator
    unrealised, the host mirrors may be behind the device, and the
    read's three kinds ride the pass's ticket as before."""
    lead, f1, f2 = cluster.coords
    early0 = cluster.count("rares_early")
    handled0 = cluster.count("rares_handled")
    held = [hold(c) for c in cluster.coords]
    fut = cluster.query(lambda st: sorted(st))
    assert pass_behind(lead, held[0]) == ["consistent_query"]
    held[0] = hold(lead)
    assert pass_behind(f1, held[1]) == ["HeartbeatRpc"]
    assert pass_behind(f2, held[2]) == ["HeartbeatRpc"]
    assert pass_behind(lead, held[0]) == ["HeartbeatReply"] * 2
    assert fut.done() and fut.value[0] == "ok", fut.value
    assert cluster.count("rares_early") == early0
    handled = [a - b for a, b in zip(cluster.count("rares_handled"),
                                     handled0)]
    assert handled == [3, 1, 1]
    # and with nothing held, the next read is at ingest again
    fut = cluster.query(lambda st: sorted(st))
    cluster.drive(fut.done, "the second read")
    assert fut.value[0] == "ok"
    assert [a - b for a, b in zip(cluster.count("rares_early"),
                                  early0)] == [3, 1, 1]


def test_a_heartbeat_at_ingest_supersedes_a_queued_pre_vote(cluster):
    """A follower whose election timeout started a pre-vote round (its
    role queued for the next step's scatter) takes the leader's
    heartbeat in its next pass: it follows at the leader's term on the
    host and on the device, and the queued role does not land after."""
    f1 = cluster.coords[1]
    g = cluster.group(1)
    f1.transport.drop_fn = lambda to, msg: True  # its pre-votes go nowhere
    f1.deliver(cluster.ids[1], ElectionTimeout(), None)
    assert f1.step_once()
    assert g.role == C.R_PRE_VOTE and f1._pending_roles
    fut = cluster.query(lambda st: sorted(st))
    assert cluster.coords[0].step_once()  # the heartbeats go out
    assert f1.step_once()
    assert g.role == C.R_FOLLOWER and not f1._pending_roles
    assert int(np.asarray(f1.state.role)[g.gid]) == C.R_FOLLOWER
    f1.transport.drop_fn = None
    cluster.drive(fut.done, "the read's answer")
    assert fut.value[0] == "ok"
