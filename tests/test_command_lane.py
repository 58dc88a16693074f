"""Command-lane flow control and liveness regression tier.

Pins the round-5 active-set command wedge (VERDICT r5 items 1 and 4)
as DETERMINISTIC interleavings: coordinators are never start()ed — the
tests drive ``step_once`` by hand, so every message delivery and device
step happens in a fixed order. The wedge's root cause was a leader
deposed between append and commit silently dropping its pending client
futures (popped on apply as a non-leader, or truncated away), hanging
every waiting client for its full timeout; under the active-set stepping
path the takeover races that cause depositions are far more frequent,
which is why the linearizability test flaked ~1/3 on ``"auto"`` and
never on ``"never"``.

Also covers the rest of the flow-control layer: the client admission
window (reject-with-backoff / counted drops), the per-peer pipeline
window with stale-peer re-send, and the command-lane watchdog that turns
any residual wedge into a detected, bounded event.
"""

import time

import pytest

from ra_tpu import api
from ra_tpu.kv_harness import DictKv
from ra_tpu.machine import SimpleMachine
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import Command, ElectionTimeout, USR
from ra_tpu.runtime.coordinator import BatchCoordinator

MODES = ["auto", "always", "never"]


def adder():
    return SimpleMachine(lambda c, s: s + c, 0)


def step_all(coords, rounds=1):
    for _ in range(rounds):
        for c in coords:
            c.step_once()


def step_until(coords, cond, rounds=200, what="condition"):
    for _ in range(rounds):
        if cond():
            return
        for c in coords:
            c.step_once()
    if not cond():
        raise AssertionError(f"never reached: {what}")


def mk_cluster(prefix, mode, n=3, **kw):
    """Unstarted coordinators (manual stepping): one group across n
    nodes. Returns (coords, ids)."""
    names = [f"{prefix}{i}" for i in range(n)]
    coords = [
        BatchCoordinator(nm, capacity=8, num_peers=n, active_set=mode,
                         election_timeout_s=0.05, **kw)
        for nm in names
    ]
    ids = [("g", nm) for nm in names]
    for c in coords:
        c.add_group("g", "cl", ids, adder())
    return coords, ids


def elect(coords, ids, i=0):
    coords[i].deliver(ids[i], ElectionTimeout(), None)
    step_until(
        coords, lambda: coords[i].by_name["g"].role == C.R_LEADER,
        what=f"{ids[i]} leads",
    )
    # settle the term noop so later appends start from a committed floor
    g = coords[i].by_name["g"]
    step_until(coords, lambda: g.last_applied >= g.noop_index,
               what="noop committed")


# -- the round-5 wedge, pinned --------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_deposed_leader_redirects_pending_commands(mode):
    """THE previously-wedging interleaving: a leader accepts a command
    (appended, pending_replies registered), is deposed by a higher-term
    election BEFORE the command commits, and the client's future must
    resolve with a redirect — not hang until its timeout (the round-5
    bug: the future was silently popped on apply, or never popped at
    all, and the linearizability test's 10 s command timeout fired)."""
    coords, ids = mk_cluster(f"dw_{mode[:2]}", mode)
    try:
        elect(coords, ids, 0)
        # cut the leader's OUTBOUND links first: the command is
        # appended but replicated to nobody, so it can never commit
        for o in (1, 2):
            coords[0].transport.block(coords[0].name, coords[o].name)
        fut = api.Future()
        coords[0].deliver(
            ids[0],
            Command(kind=USR, data=7, reply_mode="await_consensus", from_ref=fut),
            None,
        )
        coords[0].step_once()  # append + AER send; no follower steps
        g0 = coords[0].by_name["g"]
        assert g0.pending_replies, "command was not accepted as pending"
        assert not fut.done()
        # depose: the other members elect among themselves at a higher
        # term; the moment sr0 consumes the higher-term vote request its
        # device steps LEADER -> FOLLOWER and the pending future must
        # redirect immediately
        coords[1].deliver(ids[1], ElectionTimeout(), None)
        step_until(
            [coords[1], coords[2]],
            lambda: coords[1].by_name["g"].role == C.R_LEADER
            or coords[2].by_name["g"].role == C.R_LEADER,
            what="majority re-elects",
        )
        step_until(coords, fut.done, what="pending future resolved")
        out = fut.value
        # "maybe": the entry survives in the deposed leader's log and
        # MAY still commit under the new leader — the client learns the
        # outcome is unknown NOW instead of hanging out its timeout
        assert out[0] == "maybe", out
        assert coords[0].by_name["g"].role != C.R_LEADER
        assert not g0.pending_replies
        assert coords[0].counters.get("pending_redirected") >= 1
    finally:
        for c in coords:
            c.transport.unblock_all()
            c.stop()


@pytest.mark.parametrize("mode", MODES)
def test_truncated_pending_command_redirects(mode):
    """Variant: the deposed leader's uncommitted suffix is OVERWRITTEN
    by the new leader's log. The truncated entries are provably dead, so
    their futures must redirect at truncation time (belt-and-braces
    below the role-transition sweep)."""
    coords, ids = mk_cluster(f"tr_{mode[:2]}", mode)
    try:
        elect(coords, ids, 0)
        # isolate the leader both ways: its entry replicates to nobody,
        # and it sees nothing of the election that deposes it — the
        # FIRST higher-term message it consumes is the overwriting AER
        for o in (1, 2):
            coords[0].transport.block(coords[0].name, coords[o].name)
            coords[o].transport.block(coords[o].name, coords[0].name)
        fut = api.Future()
        coords[0].deliver(
            ids[0],
            Command(kind=USR, data=9, reply_mode="await_consensus", from_ref=fut),
            None,
        )
        coords[0].step_once()
        g0 = coords[0].by_name["g"]
        doomed_idx = min(g0.pending_replies)
        # the majority elects and commits its own entries over the same
        # indexes, then replicates them to the old leader
        coords[1].deliver(ids[1], ElectionTimeout(), None)
        step_until(
            coords,
            lambda: coords[1].by_name["g"].role == C.R_LEADER
            or coords[2].by_name["g"].role == C.R_LEADER,
            what="majority re-elects",
        )
        new_leader = (
            coords[1] if coords[1].by_name["g"].role == C.R_LEADER else coords[2]
        )
        fut2 = api.Future()
        new_leader.deliver(
            ("g", new_leader.name),
            Command(kind=USR, data=11, reply_mode="await_consensus", from_ref=fut2),
            None,
        )
        step_until(coords, fut2.done, what="new leader commits")
        assert fut2.value[0] == "ok"
        # heal the new leader -> old leader direction only: the
        # overwriting AER is the first higher-term message sr0 consumes.
        # next_index for sr0 advanced optimistically into the blocked
        # link, so rewind it to the divergence point by hand (the
        # detector's resync probe does this in production, but manual
        # stepping runs without the detector thread)
        for o in (1, 2):
            coords[o].transport.unblock_all()
        gN = new_leader.by_name["g"]
        slot0 = gN.slot_of(ids[0])
        gN.next_index[slot0] = doomed_idx
        gN.commit_sent[slot0] = -1
        new_leader._send_aers({gN.gid})
        step_until(coords, fut.done, what="old pending future resolved")
        assert fut.value[0] == "redirect", fut.value
        # the doomed entry is gone from the old leader's log (overwritten)
        assert g0.log.fetch_term(doomed_idx) != 1 or doomed_idx not in g0.pending_replies
        assert coords[0].counters.get("pending_redirected") >= 1
    finally:
        for c in coords:
            c.transport.unblock_all()
            c.stop()


# -- admission window -------------------------------------------------------


def test_admission_rejects_past_backlog():
    """Commands past the appended-but-unapplied backlog cap are rejected
    with ("reject", "overloaded") — bounded queueing, not unbounded
    latency. Followers are never stepped, so nothing commits and the
    backlog cannot drain."""
    coords, ids = mk_cluster("adm", "auto", max_command_backlog=4)
    try:
        elect(coords, ids, 0)
        g = coords[0].by_name["g"]
        base_backlog = g.log.next_index() - 1 - g.last_applied
        futs = [api.Future() for _ in range(10)]
        for f in futs:
            coords[0].deliver(
                ids[0],
                Command(kind=USR, data=1, reply_mode="await_consensus", from_ref=f),
                None,
            )
        coords[0].step_once()  # followers never step: no commits
        rejected = [
            f for f in futs
            if f.done() and f.value[:2] == ("reject", "overloaded")
        ]
        accepted = 4 - base_backlog
        assert len(rejected) == 10 - accepted, [f.value for f in futs if f.done()]
        assert coords[0].counters.get("commands_rejected") == len(rejected)
        assert g.log.next_index() - 1 - g.last_applied <= 4
    finally:
        for c in coords:
            c.stop()


def test_admission_drops_ackfree_commands_counted():
    """noreply commands past the window are dropped (no ack was owed)
    and surface through the overload counter."""
    coords, ids = mk_cluster("admn", "auto", max_command_backlog=4)
    try:
        elect(coords, ids, 0)
        for _ in range(10):
            coords[0].deliver(
                ids[0], Command(kind=USR, data=1, reply_mode="noreply"), None
            )
        coords[0].step_once()
        assert coords[0].counters.get("commands_dropped_overload") >= 6
    finally:
        for c in coords:
            c.stop()


def test_process_command_retries_after_reject():
    """api.process_command treats ("reject", "overloaded") as
    reject-with-backoff: it retries the same leader and succeeds once
    the backlog drains (here: once the followers start stepping)."""
    import threading

    coords, ids = mk_cluster("admr", "auto", max_command_backlog=2)
    try:
        elect(coords, ids, 0)
        # saturate the window (followers frozen)
        for _ in range(4):
            coords[0].deliver(
                ids[0], Command(kind=USR, data=1, reply_mode="noreply"), None
            )
        coords[0].step_once()
        # a client write now gets rejected at first, then admitted once
        # the cluster steps again and the backlog applies
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                step_all(coords)
                time.sleep(0.002)

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        try:
            reply, _ = api.process_command(ids[0], 5, timeout=10)
            assert reply is not None or reply is None  # completed at all
        finally:
            stop.set()
            t.join(timeout=5)
    finally:
        for c in coords:
            c.stop()


# -- pipeline window --------------------------------------------------------


def test_pipeline_window_bounds_inflight_and_stale_resend():
    """A peer that stops acking stalls at match + window (next_index no
    longer advances past it); once it has been silent for a tick the
    leader rewinds next_index to match + 1 (stale-peer re-send,
    reference: Next - Match <= ?MAX_PIPELINE_COUNT)."""
    coords, ids = mk_cluster(
        "pw", "auto", max_pipeline_count=8, tick_interval_s=0.05,
        aer_batch_size=8,
    )
    try:
        elect(coords, ids, 0)
        g = coords[0].by_name["g"]
        # freeze the followers' links: acks stop flowing
        for o in (1, 2):
            coords[0].transport.block(coords[0].name, coords[o].name)
        mh = list(g.match_hint)
        for k in range(40):
            coords[0].deliver(
                ids[0], Command(kind=USR, data=1, reply_mode="noreply"), None
            )
            coords[0].step_once()
        for s in range(len(g.members)):
            if s == g.self_slot:
                continue
            # optimistic next_index is bounded by confirmed match +
            # window + one AER batch (the batch in flight when the
            # window filled)
            assert g.next_index[s] <= mh[s] + 8 + 8, (s, g.next_index, mh)
        # silence exceeds a tick: the next send attempt rewinds
        time.sleep(0.08)
        coords[0].deliver(
            ids[0], Command(kind=USR, data=1, reply_mode="noreply"), None
        )
        coords[0].step_once()
        assert coords[0].counters.get("stale_peer_resends") >= 1
        # the rewind re-sent one batch from match + 1, so the optimistic
        # next_index is back inside match + one AER batch
        assert all(
            g.next_index[s] <= g.match_hint[s] + 1 + 8
            for s in range(len(g.members)) if s != g.self_slot
        ), (g.next_index, g.match_hint)
    finally:
        for c in coords:
            c.transport.unblock_all()
            c.stop()


# -- watchdog ---------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_watchdog_bounds_wedged_lane(mode):
    """A leader partitioned from its followers accepts a command that
    can never commit. The command-lane watchdog must detect the wedge
    (counter + log), attempt recovery, and then BOUND the failure by
    redirecting the stuck client — the class of bug that previously
    meant a silent 10 s client hang."""
    names = [f"wd_{mode[:2]}{i}" for i in range(3)]
    coords = [
        BatchCoordinator(nm, capacity=8, num_peers=3, active_set=mode,
                         election_timeout_s=0.05, detector_poll_s=0.02,
                         tick_interval_s=0.05, command_deadline_s=0.3)
        for nm in names
    ]
    ids = [("g", nm) for nm in names]
    try:
        for c in coords:
            c.add_group("g", "cl", ids, DictKv())
            c.start()
        coords[0].deliver(ids[0], ElectionTimeout(), None)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if coords[0].by_name["g"].role == C.R_LEADER:
                break
            time.sleep(0.01)
        assert coords[0].by_name["g"].role == C.R_LEADER
        # partition the leader away BEFORE the command: accepted, then
        # wedged (no acks can ever arrive)
        for o in (1, 2):
            coords[0].transport.block(names[0], names[o])
            coords[o].transport.block(names[o], names[0])
        fut = api.Future()
        coords[0].deliver(
            ids[0],
            Command(kind=USR, data=("put", "k", 1),
                    reply_mode="await_consensus", from_ref=fut),
            None,
        )
        # bounded: the watchdog answers well before a client-scale
        # (10 s) timeout — two strikes at 0.3 s deadline + tick slack.
        # Verdict "maybe": the entry is still in the wedged leader's
        # log and could commit if the partition healed
        out = fut.result(timeout=5)
        assert out[0] == "maybe", out
        assert coords[0].counters.get("lane_wedges") >= 1
        assert coords[0].counters.get("lane_recoveries") >= 1
    finally:
        for c in coords:
            c.transport.unblock_all()
            c.stop()


@pytest.mark.parametrize("wave_s,quiet_at,struck_at,deadline_ms", [
    (0.001, (0.2,), 0.35, 300),       # light load: the configured deadline
    (0.025, (0.2, 0.39), 0.45, 400),  # sixteen waves of 25 ms
    (0.1, (0.35, 0.59), 0.65, 600),   # sixteen would be 1.6 s: capped at two
    (3.0, (0.59,), 0.65, 600),        # waves that grow cannot unbound it
])
def test_watchdog_deadline_follows_the_wave_up_to_two_deadlines(
        wave_s, quiet_at, struck_at, deadline_ms):
    """Where the coordinator's own wave is long (a saturated fleet's
    lasts a second), a lane still for ``command_deadline_s`` is slow,
    not wedged: the deadline stretches to sixteen waves, and never past
    two configured deadlines. The gauges say what it was and how long
    a lane has been seen standing still."""
    c = BatchCoordinator("wd_wave", capacity=8, num_peers=3,
                         command_deadline_s=0.3)
    try:
        c.add_group("g", "cl", [("g", "wd_wave")], DictKv())
        g = c.by_name["g"]
        g.pending_replies[7] = api.Future()
        c._pending_np[g.gid] = 1  # as the append that puts one in marks it
        c._wave_s = wave_s
        watch = {}
        c._lane_watchdog(watch, 0.0)  # first seen still
        for now in quiet_at:
            c._lane_watchdog(watch, now)
            assert c.counters.get("lane_wedges") == 0, now
        assert c.counters.get("lane_deadline_ms") == deadline_ms
        # the longest it has seen the lane still, at or under the deadline
        assert c.counters.get("lane_stall_max_ms") == round(quiet_at[-1] * 1e3)
        c._lane_watchdog(watch, struck_at)
        assert c.counters.get("lane_wedges") == 1
        assert c.counters.get("lane_stall_max_ms") == round(struck_at * 1e3)
    finally:
        c.stop()


def test_started_loops_measure_their_wave_for_the_watchdog():
    """The live path: a started cluster that commits commands moves
    ``_wave_s`` on every node that stepped (written by the realising
    thread, read by the detector's), its watchdog publishes the deadline
    it used, and at this light load that is the configured one."""
    names = [f"wv{i}" for i in range(3)]
    coords = [
        BatchCoordinator(nm, capacity=8, num_peers=3,
                         election_timeout_s=0.05, detector_poll_s=0.02,
                         tick_interval_s=0.05, command_deadline_s=2.0)
        for nm in names
    ]
    ids = [("g", nm) for nm in names]
    try:
        for c in coords:
            c.add_group("g", "cl", ids, DictKv())
            c.start()
        coords[0].deliver(ids[0], ElectionTimeout(), None)
        deadline = time.monotonic() + 20
        while coords[0].by_name["g"].role != C.R_LEADER:
            assert time.monotonic() < deadline, "no leader"
            time.sleep(0.01)
        for k in range(5):
            fut = api.Future()
            coords[0].deliver(
                ids[0],
                Command(kind=USR, data=("put", "k", k),
                        reply_mode="await_consensus", from_ref=fut),
                None,
            )
            assert fut.result(timeout=10)[0] == "ok"
        time.sleep(0.15)  # a few watchdog ticks
        for c in coords:
            assert 0.0 < c._wave_s < 1.0, (c.name, c._wave_s)
            assert c.counters.get("lane_deadline_ms") == 2000
        assert coords[0].counters.get("lane_wedges") == 0
        assert coords[0].counters.get("lane_stall_max_ms") < 2000
    finally:
        for c in coords:
            c.stop()


# -- election-duel damping --------------------------------------------------


def test_vote_grant_resets_suspicion_clock():
    """Granting a (pre-)vote refreshes last_contact: the granter holds
    off its own campaign for a full election round instead of dueling
    the candidate it just endorsed (Raft §3.4 election-timer reset)."""
    coords, ids = mk_cluster("vg", "auto")
    try:
        g1 = coords[1].by_name["g"]
        g1.last_contact = time.monotonic() - 100.0  # long-stale
        before = g1.last_contact
        coords[0].deliver(ids[0], ElectionTimeout(), None)
        step_until(
            coords, lambda: coords[0].by_name["g"].role == C.R_LEADER,
            what="leader elected",
        )
        assert g1.last_contact > before + 50.0
    finally:
        for c in coords:
            c.stop()


def test_admission_never_sheds_internal_commands():
    """Machine-internal commands (timer fires, Append effects — marked
    Command.internal) fire exactly once with no retry path: a full
    admission window must never shed them, only client traffic."""
    coords, ids = mk_cluster("admi", "auto", max_command_backlog=4)
    try:
        elect(coords, ids, 0)
        g = coords[0].by_name["g"]
        # saturate the window with client noreply traffic
        for _ in range(10):
            coords[0].deliver(
                ids[0], Command(kind=USR, data=1, reply_mode="noreply"), None
            )
        coords[0].step_once()
        assert g.log.next_index() - 1 - g.last_applied >= 4
        li_before = g.log.last_index_term()[0]
        # an internal command (the shape a machine timer fire delivers)
        # must still append past the full window
        coords[0].deliver(
            ids[0],
            Command(kind=USR, data=("timeout", "t1"), reply_mode="noreply",
                    internal=True),
            None,
        )
        coords[0].step_once()
        assert g.log.last_index_term()[0] == li_before + 1
    finally:
        for c in coords:
            c.stop()
