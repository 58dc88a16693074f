"""The detector's walks as masks (ISSUE 31) against the per-row rule.

``_detect_pass`` and ``_lane_watchdog`` read per-group facts from flat
arrays (``coordinator.GroupMirrors``) and run their per-row Python only
for the rows a mask leaves. The rule a row is judged by did not change,
so the walk that was there before is kept here as plain functions
(``row_is_suspicious``, ``reference_sweep``, ``reference_probes``,
``reference_watchdog``) and the pass is held to it over seeded random
fleets: the rows the pass walks are a superset of the rows the rule
calls suspicious, and its deliveries, ``armed``, ``cooldown`` and
``lane_watch`` come out equal. One departure, ISSUE 31's: the pass
sheds the ``armed`` / ``lane_watch`` keys of every row its mask does
not pass, where the old walk left the key of a row it skipped (a
leader, a non-voter, a free row) standing; the comparison is made on
the rows the rule judges, and the pass's keys are checked to lie inside
its mask.

The second half holds the mirrors to the scalars on a started
three-node cluster that elects, serves, loses and regains a leader."""

import random
import re
import time
from pathlib import Path

import numpy as np
import pytest

from ra_tpu import api
from ra_tpu.kv_harness import DictKv
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import Command, ElectionTimeout, USR
from ra_tpu.runtime import coordinator as coordinator_mod
from ra_tpu.runtime.coordinator import BatchCoordinator, GroupHost, GroupMirrors
from ra_tpu.runtime.transport import NodeRegistry

NOW = 10_000.0
ET = 0.15  # election_timeout_s
TICK = 1.0  # tick_interval_s
CW = max(5 * TICK, 6 * ET)  # the sweep's contact window
NODES = ("det0", "det1", "det2")


class FixedClock:
    def __init__(self, now=NOW):
        self.now = now

    def monotonic(self):
        return self.now

    def time(self):
        return self.now


class Stub:
    """Another node, as ``InProcTransport.node_alive`` sees one."""

    def __init__(self, running=True):
        self.running = running


class Recording(list):
    """``coordinator.groups`` that notes the rows it is asked for."""

    def __init__(self, rows):
        super().__init__(rows)
        self.seen = []

    def __getitem__(self, i):
        self.seen.append(i)
        return super().__getitem__(i)


# -- the rule, as the walk over every row had it --------------------------------


def row_is_suspicious(c, g, now):
    """The three leaderless shapes of ``_detect_pass``'s sweep, for a
    registered voter row that does not lead."""
    leader = g.sid_of(g.leader_slot)
    if g.role in (C.R_PRE_VOTE, C.R_CANDIDATE):
        return now - g.last_contact > 2 * c.election_timeout_s
    if leader is not None and leader[1] != c.name:
        return (
            not c.transport.node_alive(leader[1])
            and now - g.last_contact > c.election_timeout_s
        ) or now - g.last_contact > CW
    return g.term > 0 and now - g.last_contact > CW


def judged(g):
    return (g is not None and g.role != C.R_LEADER
            and g.voter_status.get(g.self_slot) == "voter")


def reference_sweep(c, now, cooldown, armed, deliver):
    suspects = []
    for i in range(c.n_groups):
        g = c.groups[i]
        if not judged(g):
            continue
        if not row_is_suspicious(c, g, now):
            armed.pop(i, None)
            continue
        suspects.append(i)
        if now >= cooldown.get(i, 0.0):
            dl = armed.get(i)
            if dl is None:
                armed[i] = now + c.election_timeout_s * (0.1 + random.random())
            elif now >= dl:
                armed.pop(i, None)
                cooldown[i] = (
                    now + 2 * c.election_timeout_s
                    + random.random() * 2 * c.election_timeout_s
                )
                deliver((g.name, c.name), ElectionTimeout(now), None)
    return suspects


def reference_probes(c, now0, deliver):
    ms = int(c.clock.time() * 1000)
    for i in range(c.n_groups):
        g = c.groups[i]
        if g is None:
            continue
        if g.has_tick:
            deliver((g.name, c.name), ("machine_tick", ms), None)
        if g.role == C.R_LEADER:
            stale = [
                s for s, m in enumerate(g.members)
                if m is not None and s != g.self_slot
                and now0 - float(g.last_ack[s]) > 2 * c.tick_interval_s
            ]
            if stale:
                deliver((g.name, c.name), ("resync", stale), None)


def reference_watchdog(c, lane_watch, now0, deliver):
    deadline = min(
        max(c.command_deadline_s, c._WEDGE_WAVES * c._wave_s),
        c._WEDGE_STRETCH_MAX * c.command_deadline_s)
    for i in range(c.n_groups):
        g = c.groups[i]
        if g is None:
            continue
        pending = g.pending_replies
        if not pending:
            lane_watch.pop(i, None)
            continue
        oldest = min(pending)
        st = lane_watch.get(i)
        if st is None or st[0] != g.last_applied or st[1] != oldest:
            lane_watch[i] = (g.last_applied, oldest, now0, 0)
            continue
        if now0 - st[2] <= deadline:
            continue
        strikes = st[3] + 1
        lane_watch[i] = (g.last_applied, oldest, now0, strikes)
        deliver((g.name, c.name),
                ("lane_recover",) if strikes == 1 else ("lane_fail",), None)


# -- fleets ---------------------------------------------------------------------


def build(n, dead="none", capacity=None):
    """An unstarted coordinator ``det0`` with ``n`` registered groups of
    three members, this node's slot rotating, on a registry of its own;
    ``dead`` says what became of ``det2``."""
    reg = NodeRegistry()
    c = BatchCoordinator(
        NODES[0], capacity=capacity or n, num_peers=3, nodes=reg,
        election_timeout_s=ET, tick_interval_s=TICK, clock=FixedClock())
    reg.register(NODES[1], Stub())
    if dead == "stopped":
        reg.register(NODES[2], Stub(running=False))
    elif dead != "unregistered":
        reg.register(NODES[2], Stub())
    if dead == "blocked":
        c.transport.block(NODES[0], NODES[2])
    specs = []
    for i in range(n):
        name = f"g{i}"
        members = [(name, NODES[(k + i) % 3]) for k in range(3)]
        specs.append((name, "cl", members, DictKv()))
    c.add_groups(specs)
    sent = []
    c.deliver = lambda to, msg, from_sid: sent.append((to, msg)) or True
    c.deliver_many = lambda msgs: sent.extend((to, m) for to, m, _frm in msgs)
    return c, sent


AGES = (0.0, 0.5 * ET, 0.99 * ET, 1.01 * ET, 1.99 * ET, 2.01 * ET,
        0.99 * CW, 1.01 * CW, 3 * CW)


def randomise(c, rng):
    """Every kind of row the sweep can meet; returns the freed rows."""
    freed = []
    for i in range(c.n_groups):
        g = c.groups[i]
        g.role = rng.choice((C.R_FOLLOWER, C.R_FOLLOWER, C.R_FOLLOWER,
                             C.R_PRE_VOTE, C.R_CANDIDATE, C.R_LEADER))
        g.term = rng.choice((0, 0, 1, 5))
        g.last_contact = NOW - rng.choice(AGES)
        g.voter_status[g.self_slot] = rng.choice(
            ("voter", "voter", "voter", "voter", ("nonvoter", 3), None))
        others = [s for s in range(3) if s != g.self_slot]
        if rng.random() < 0.15:  # a tombstoned slot
            s = rng.choice(others)
            g.members[s] = None
            g.voter_status[s] = None
            c._sync_peer_row(g)
        g.leader_slot = rng.choice((-1, g.self_slot, others[0], others[1]))
        for s in others:
            g.last_ack[s] = rng.choice((0.0, NOW - 0.5 * TICK, NOW - 1.9 * TICK,
                                        NOW - 2.1 * TICK, NOW - 50.0))
        if rng.random() < 0.05:  # a row no group holds
            c.groups[i] = None
            c._role_np[i] = GroupMirrors.FREE
            freed.append(i)
    return freed


def seed_tables(c, rng):
    armed, cooldown = {}, {}
    for i in range(c.n_groups):
        if rng.random() < 0.5:
            armed[i] = NOW + rng.choice((-1.0, -0.01, -0.01, 0.05, 1.0))
        if rng.random() < 0.2:
            cooldown[i] = NOW + rng.choice((-1.0, 0.2))
    return armed, cooldown


def new_sweep(c, sent, armed, cooldown, lane_watch=None, tick=False):
    """One ``_detect_pass``; returns (rows asked for, rows counted)."""
    c._detect_last_tick = NOW - (2 * TICK if tick else 0.0)
    c.groups = Recording(c.groups)
    before = c.counters.get("detector_rows_walked")
    del sent[:]
    c._detect_pass(cooldown, armed, {} if lane_watch is None else lane_watch)
    seen, c.groups = c.groups.seen, list(c.groups)
    return seen, c.counters.get("detector_rows_walked") - before


CASES = [(seed, n, dead)
         for seed, n in ((1, 160), (2, 128), (3, 200), (4, 333))
         for dead in ("none", "stopped", "unregistered", "blocked")]


@pytest.mark.parametrize("seed,n,dead", CASES)
def test_the_sweep_walks_a_superset_and_decides_as_the_row_rule(seed, n, dead):
    c, sent = build(n, dead)
    try:
        rng = random.Random(seed)
        randomise(c, rng)
        armed, cooldown = seed_tables(c, rng)
        ref_armed, ref_cooldown, ref_sent = dict(armed), dict(cooldown), []
        random.seed(seed)
        suspects = reference_sweep(
            c, NOW, ref_cooldown, ref_armed,
            lambda to, msg, frm: ref_sent.append((to, msg)))
        assert suspects and ref_sent, "a fleet that tests nothing"
        random.seed(seed)
        seen, counted = new_sweep(c, sent, armed, cooldown)
        assert set(suspects) <= set(seen)
        assert counted == len(seen) == len(set(seen)) < n
        assert sent == ref_sent  # the same elections, in the same order
        assert cooldown == ref_cooldown
        rows = [i for i in range(n) if judged(c.groups[i])]
        assert ({i: armed[i] for i in rows if i in armed}
                == {i: ref_armed[i] for i in rows if i in ref_armed})
        assert set(armed) <= set(seen)  # and no key outside the mask
        # (whether a dead node widened the mask: the follower rows
        # between one election timeout and the contact window)
        between = [i for i in range(n) if c.groups[i] is not None
                   and c.groups[i].role == C.R_FOLLOWER
                   and ET < NOW - c.groups[i].last_contact <= CW]
        assert between
        assert (dead != "none") == bool(set(between) & set(seen))
    finally:
        c.stop()


@pytest.mark.parametrize("seed,n", [(11, 64), (12, 257)])
def test_the_ticks_probes_and_machine_ticks_are_the_row_walks(seed, n):
    c, sent = build(n)
    try:
        rng = random.Random(seed)
        randomise(c, rng)
        for i in rng.sample(range(n), 5):  # machines with a tick
            if c.groups[i] is not None:
                c.groups[i].has_tick = True
                c._tick_gids.append(i)
        ref_sent = []
        reference_probes(c, NOW, lambda to, msg, frm: ref_sent.append((to, msg)))
        assert any(m[0] == "resync" for _to, m in ref_sent)
        assert any(m[0] == "machine_tick" for _to, m in ref_sent)
        seen, _counted = new_sweep(c, sent, {}, {}, tick=True)
        probes = [x for x in sent if isinstance(x[1], tuple)]
        assert sorted(probes) == sorted(ref_sent)
        led = [i for i in range(n) if c.groups[i] is not None
               and c.groups[i].role == C.R_LEADER]
        assert len(set(seen)) < len(led) + n // 2  # not every led row
    finally:
        c.stop()


def _command(fut):
    return Command(kind=USR, data=("put", "k", 1),
                   reply_mode="await_consensus", from_ref=fut)


def test_the_watchdog_walks_the_rows_with_client_futures_and_no_others():
    """Futures go in through ``_handle_commands`` (the one place that
    fills ``pending_replies``), some are answered, some lanes stand
    still past the deadline: the watchdog's table and strikes are the
    row walk's at every tick."""
    n = 96
    c, sent = build(n)
    try:
        rng = random.Random(5)
        for i in range(n):
            c.groups[i].role = C.R_LEADER if i % 3 == 0 else C.R_FOLLOWER
        busy = rng.sample(range(0, n, 3), 12)
        for i in busy:
            c._handle_commands(c.groups[i], [_command(api.Future())], {}, {}, set())
        assert all(c.groups[i].pending_replies for i in busy)
        assert np.flatnonzero(c._pending_np[:n]).tolist() == sorted(busy)
        watch, ref_watch, ref_sent = {7: (0, 1, 0.0, 0)}, {7: (0, 1, 0.0, 0)}, []
        ref_deliver = lambda to, msg, frm: ref_sent.append((to, msg))  # noqa: E731
        for k, now0 in enumerate((0.0, 1.0, 4.0, 6.0, 7.0, 12.0, 13.0)):
            if k == 2:  # four lanes answered, one deposed with its futures in
                for i in busy[:4]:
                    c.groups[i].pending_replies.clear()
                c.groups[busy[4]].role = C.R_FOLLOWER
            if k == 4:  # one of them takes a command again
                c._handle_commands(
                    c.groups[busy[0]], [_command(api.Future())], {}, {}, set())
            before = c.counters.get("detector_rows_walked")
            c._lane_watchdog(watch, now0)
            reference_watchdog(c, ref_watch, now0, ref_deliver)
            assert watch == ref_watch, now0
            assert sent == ref_sent, now0
            walked = c.counters.get("detector_rows_walked") - before
            assert walked <= len(busy) < n
            for i in range(n):  # a row with futures always passes
                if c.groups[i].pending_replies:
                    assert c._pending_np[i] == 1
        assert c.counters.get("lane_wedges") == len(ref_sent) > 0
        assert sorted(watch) == sorted(busy[4:] + busy[:1])
        assert int(c._pending_np[:n].sum()) == len(watch)  # the rest unmarked
    finally:
        c.stop()


def test_a_poll_of_a_healthy_elected_fleet_of_10240_walks_no_row():
    n = 10_240
    c, sent = build(n)
    try:
        for i in range(n):
            g = c.groups[i]
            g.term = 1
            if g.self_slot == 0:
                g.role, g.leader_slot = C.R_LEADER, 0
            else:
                g.leader_slot = 0
        # contact and acks as traffic and the probes keep them: younger
        # than an election timeout, younger than two ticks
        rng = np.random.default_rng(7)
        c._contact_np[:n] = NOW - rng.uniform(0.0, 0.9 * ET, n)
        c._last_ack_np[:n] = NOW - rng.uniform(0.0, 1.9 * TICK, (n, 3))
        armed, cooldown = {}, {}
        seen, counted = new_sweep(c, sent, armed, cooldown)
        assert seen == [] and counted == 0 and sent == []
        assert armed == {} and cooldown == {}
        # and a tick of it: no probe, no lane to watch
        seen, counted = new_sweep(c, sent, armed, cooldown, tick=True)
        assert seen == [] and counted == 0 and sent == []
        # the idle queues' contact lapses by three ticks: followers
        # wait (the contact window is five), a third of the leaders probe
        c._contact_np[:n] = NOW - 3 * TICK
        c._last_ack_np[:n:9] = NOW - 3 * TICK
        seen, counted = new_sweep(c, sent, armed, cooldown, tick=True)
        led = [i for i in range(0, n, 9) if c.groups[i].role == C.R_LEADER]
        assert sorted(seen) == led and counted == len(led)
        assert len(sent) == len(led) and all(m[0] == "resync" for _t, m in sent)
        assert armed == {}
    finally:
        c.stop()


def test_a_freed_and_re_registered_row_starts_with_fresh_mirrors():
    c, sent = build(8)
    try:
        gone = c.groups[5]
        gone.role = C.R_LEADER
        gone.last_contact = NOW - 100.0
        gone.last_ack[:] = NOW - 50.0
        c._handle_commands(gone, [_command(api.Future())], {}, {}, set())
        assert c._pending_np[5] == 1
        # the row is freed (no group, the role no mask passes, no mark):
        # whatever its other arrays still hold, no walk takes it, and
        # its keys go with it
        c.groups[5] = None
        c._role_np[5] = GroupMirrors.FREE
        c._pending_np[5] = 0
        armed, watch = {5: NOW - 1.0}, {5: (0, 1, 0.0, 0)}
        seen, counted = new_sweep(c, sent, armed, {}, lane_watch=watch, tick=True)
        assert seen == [] and counted == 0 and sent == []
        assert armed == {} and watch == {}
        # a new occupant of the row
        c.clock.now = NOW + 7.0
        name = "g5b"
        g = GroupHost(5, name, "cl", [(name, nd) for nd in NODES], 0,
                      gone.log, DictKv(), c._mirrors, clock=c.clock)
        c.groups[5] = g
        assert g.last_contact == c._contact_np[5] == NOW + 7.0
        assert g.role == c._role_np[5] == C.R_FOLLOWER
        assert c._last_ack_np[5].tolist() == [0.0, 0.0, 0.0]
        assert g.last_ack.base is c._last_ack_np  # the row itself, no copy
        assert c._pending_np[5] == 0
    finally:
        c.stop()


# -- the mirrors on a started cluster ----------------------------------------------


def _mirrors_agree(coords, want_marked=()):
    for c in coords:
        with c._state_lock:
            for g in c.groups[: c.n_groups]:
                assert c._role_np[g.gid] == g.role == g._role, (c.name, g.name)
                assert c._contact_np[g.gid] == g.last_contact
                assert np.shares_memory(g.last_ack, c._last_ack_np[g.gid])
                peers = [m is not None and s != g.self_slot
                         for s, m in enumerate(g.members)]
                assert c._peer_np[g.gid, : len(peers)].tolist() == peers
                if g.pending_replies:
                    assert c._pending_np[g.gid] == 1, (c.name, g.name)
    for c, gid in want_marked:
        assert c._pending_np[gid] == 1


def _leader_of(coords, name, timeout=20.0, among=None):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for c in among or coords:
            if c.running and c.by_name[name].role == C.R_LEADER:
                return c
        time.sleep(0.01)
    raise AssertionError(f"no leader for {name}")


def test_every_write_of_role_and_contact_reaches_the_mirrors():
    """A started three-node cluster elects, serves, loses its leader to a
    partition (which leaves a command pending on the deposed side),
    elects another through the detector's sweep, heals and serves again:
    after each stage every group's mirrors on every node read what its
    scalars do, and a row with client futures is marked."""
    reg = NodeRegistry()
    names = ["mir0", "mir1", "mir2"]
    coords = [
        BatchCoordinator(nm, capacity=8, num_peers=3, nodes=reg,
                         election_timeout_s=0.05, detector_poll_s=0.02,
                         tick_interval_s=0.05, command_deadline_s=30.0)
        for nm in names
    ]
    groups = [f"m{k}" for k in range(4)]
    try:
        for c in coords:
            c.add_groups([(gn, "cl", [(gn, nm) for nm in names], DictKv())
                          for gn in groups])
            c.start()
        _mirrors_agree(coords)
        for k, gn in enumerate(groups):
            coords[k % 3].deliver((gn, names[k % 3]), ElectionTimeout(), None)
        leaders = {gn: _leader_of(coords, gn) for gn in groups}
        for gn in groups:
            fut = api.Future()
            leaders[gn].deliver((gn, leaders[gn].name), _command(fut), None)
            assert fut.result(timeout=10)[0] == "ok"
        _mirrors_agree(coords)
        # the leader of m0 cut off from both peers; a command it accepts
        # cannot commit and stays in pending_replies
        old = leaders["m0"]
        rest = [c for c in coords if c is not old]
        for o in rest:
            old.transport.block(old.name, o.name)
            o.transport.block(o.name, old.name)
        stuck = api.Future()
        old.deliver(("m0", old.name), _command(stuck), None)
        new = _leader_of(coords, "m0", among=rest)  # by the sweep alone
        assert new is not old
        _mirrors_agree(coords, want_marked=[(old, old.by_name["m0"].gid)])
        fut = api.Future()
        new.deliver(("m0", new.name), _command(fut), None)
        assert fut.result(timeout=10)[0] == "ok"
        for c in coords:
            c.transport.unblock_all()
        # healed: the deposed side steps down and answers its client
        assert stuck.result(timeout=20)[0] in ("redirect", "maybe")
        deadline = time.monotonic() + 20
        while old.by_name["m0"].role == C.R_LEADER:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        # a tick or two: the watchdog unmarks the drained row
        while sum(int(c._pending_np.sum()) for c in coords):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        _mirrors_agree(coords)
        for c in coords:
            assert c.detector_errors == 0
            assert c.counters.get("detector_passes") > 0
            assert c.counters.get("detector_cpu_ns") >= 0
    finally:
        for c in coords:
            c.transport.unblock_all()
            c.stop()


def test_the_mirrors_have_one_writer_each():
    """No store into the detector's arrays outside their writers: the
    ``GroupHost`` properties (role, contact), ``_sync_peer_row``,
    ``_handle_commands`` and the watchdog (pending); and no scalar
    ``last_contact`` or dict ``last_ack`` left to go stale."""
    src = Path(coordinator_mod.__file__).read_text()
    stores = re.findall(
        r"^.*(?:_contact_np|_role_np|_last_ack_np|_peer_np|_pending_np|"
        r"mirrors\.\w+)\b[^\n=]*\]\s*=[^=].*$", src, re.M)
    stores = [line.strip() for line in stores]
    assert sorted(stores) == sorted([
        "mirrors.pending[gid] = 0",
        "self._mirrors.role[self.gid] = role",
        "self._mirrors.contact[self.gid] = t",
        "self._pending_np[gid] = 1",
    ]), stores
    assert "last_ack = {}" not in src and "last_ack.get(" not in src
    assert '"last_contact"' not in src  # no slot: the array is the value
    host = GroupHost.__slots__
    assert "role" not in host and "last_contact" not in host
    assert isinstance(GroupHost.role, property)
    assert isinstance(GroupHost.last_contact, property)


def test_the_watchdog_reads_a_copy_of_the_marks_the_step_thread_writes():
    """numpy's ``nonzero`` lets go of the interpreter lock between
    counting and filling and raises if the count changed under it: the
    watchdog takes its rows from a private copy of ``_pending_np``, which
    the step thread marks while it runs (found on the chip, PR 31: two
    ``detector_errors`` in a kv run)."""
    import threading

    n = 10_240
    c, _sent = build(n)
    stop = threading.Event()

    def mark():  # as appends on the step thread do, all the time
        k = 0
        while not stop.is_set():
            c._pending_np[k % n] ^= 1
            k += 7

    t = threading.Thread(target=mark, daemon=True)
    t.start()
    try:
        watch, passes = {}, 0
        deadline = time.monotonic() + 1.5
        while time.monotonic() < deadline:
            c._lane_watchdog(watch, float(passes))
            passes += 1
        assert passes > 100
    finally:
        stop.set()
        t.join()
        c.stop()
