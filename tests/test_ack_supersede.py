"""A leader's lane under a hot key: a follower's later success ack takes
the place of its queued earlier one (``BatchCoordinator._supersede_ack``;
docs/INTERNALS.md §15). The device takes one message a group a step, and
acks are cumulative, so the lane holds at most one success ack a follower
however many writes are in flight."""

import pytest

from ra_tpu import api, leaderboard
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import (
    AppendEntriesReply,
    Command,
    RequestVoteRpc,
    USR,
)
from ra_tpu.runtime.coordinator import BatchCoordinator

from test_batch_parity import adder, await_, mk_cluster, stop_all

MEMBERS = [("akg", f"ak{i}") for i in range(3)]
F1, F2 = MEMBERS[1], MEMBERS[2]


@pytest.fixture
def lane():
    """A leader's group on a coordinator that is never started: only
    the host routing runs."""
    leaderboard.clear()
    c = BatchCoordinator("ak0", capacity=8, num_peers=3)
    c.add_group("akg", "akcl", MEMBERS, adder())
    g = c.by_name["akg"]
    g.role, g.term = C.R_LEADER, 3

    def route(frm, msg):
        with c._state_lock:
            c._route_one(g, frm, msg, [], {}, {}, set(), {})
        return [(f, (m.last_index if type(m) is AppendEntriesReply
                     else type(m).__name__)) for f, m in g.inbox]

    yield g, route
    c.stop()
    leaderboard.clear()


def ack(idx, term=3, ok=True):
    return AppendEntriesReply(term, ok, idx + 1, idx, term)


def test_a_later_ack_takes_the_place_of_the_queued_one(lane):
    g, route = lane
    assert route(F1, ack(5)) == [(F1, 5)]
    assert route(F2, ack(5)) == [(F1, 5), (F2, 5)]
    # two more writes acknowledged: the lane does not grow, and each
    # follower's queued ack now says what its newest said
    assert route(F1, ack(6)) == [(F1, 6), (F2, 5)]
    assert route(F2, ack(7)) == [(F1, 6), (F2, 7)]
    assert route(F1, ack(7)) == [(F1, 7), (F2, 7)]
    # the host's own bookkeeping saw every one of them
    assert g.next_index[1] == 8 and g.next_index[2] == 8
    assert g.match_hint[1] == 7 and g.match_hint[2] == 7


def test_what_is_not_superseded(lane):
    g, route = lane
    route(F1, ack(5))
    # an older index (reordered on the way) does not replace a newer
    assert route(F1, ack(4)) == [(F1, 5), (F1, 4)]
    # a reject is queued, and no later ack reaches across it
    assert route(F1, ack(9, ok=False))[-1] == (F1, 9)
    assert route(F1, ack(10)) == [(F1, 5), (F1, 4), (F1, 9), (F1, 10)]
    # another term's ack is left alone
    g.inbox.clear()
    route(F2, ack(5, term=2))
    assert route(F2, ack(6)) == [(F2, 5), (F2, 6)]
    # nor does an ack pass the sender's other messages
    g.inbox.clear()
    route(F1, ack(5))
    route(F1, RequestVoteRpc(3, F1, 5, 3))
    assert route(F1, ack(6)) == [(F1, 5), (F1, "RequestVoteRpc"), (F1, 6)]


def test_a_follower_does_not_touch_its_inbox(lane):
    g, route = lane
    g.role = C.R_FOLLOWER
    route(F1, ack(5))
    assert route(F1, ack(6)) == [(F1, 5), (F1, 6)]


def test_a_burst_on_one_group_commits_in_order_on_every_replica():
    coords = mk_cluster("ab")
    try:
        sid = ("abg0", "ab0")
        N = 300
        futs = [api.Future() for _ in range(N)]
        coords[0].deliver_many([
            (sid, Command(kind=USR, data=i + 1, reply_mode="await_consensus",
                          from_ref=f), None) for i, f in enumerate(futs)])
        total = 0
        for i, f in enumerate(futs):
            out = f.result(30)
            total += i + 1
            assert out[0] == "ok" and out[1] == total, (i, out)
        for c in coords.values():
            g = c.by_name["abg0"]
            await_(lambda g=g: g.machine_state == total,
                   what=f"{c.name} applied the burst")
        lead = coords[0].by_name["abg0"]
        await_(lambda: lead.next_index[1] == lead.next_index[2]
               == lead.log.last_index_term()[0] + 1, what="both followers acked")
    finally:
        stop_all(coords)
