"""The wave's account, to the leaf (ISSUE 35; docs/INTERNALS.md §13):
every part of ``ingress_drain`` and ``host_egress`` has its sub-phase,
the sender's queue its wait, and the interpreter lock's wait is sampled
by the process's own probe thread (``ra_tpu/runtime/gil_probe.py``).

One histogram has one writer: the step thread writes the leaves of
``ingress_drain``, the realising thread those of ``host_egress`` and
``aer_fanout``, the sender ``send_queue``, the probe ``gil_wait``."""

import threading
import time
import types

import numpy as np
import pytest

from ra_tpu import api, obs
from ra_tpu.machine import SimpleMachine
from ra_tpu.ops import consensus as C
from ra_tpu.protocol import USR, Command, ElectionTimeout
from ra_tpu.runtime import gil_probe, heap
from ra_tpu.runtime.coordinator import BatchCoordinator
from ra_tpu.runtime.transport import NodeRegistry

INGRESS_LEAVES = ("ingress_classify", "step_lock_wait", "ingress_route",
                  "ingest_append", "ingest_fanout")
EGRESS_LEAVES = ("egress_follow", "egress_mirror", "egress_apply",
                 "egress_rare")
COUNTERS = ("routed_msgs", "follower_aers", "follower_entries",
            "rares_handled")


def wave(c, phase):
    return obs.histograms().fetch(("wave", c.name, phase))


def totals(coords, phases):
    """{phase: (samples, total ns)} over the coordinators."""
    return {ph: (sum(wave(c, ph).n for c in coords),
                 sum(wave(c, ph).total for c in coords)) for ph in phases}


def median_ns(before, after):
    """The median of what a histogram recorded between two ``arr``s."""
    counts = after - before
    total = int(counts.sum())
    assert total > 0
    b = int(np.searchsorted(np.cumsum(counts), (total + 1) // 2))
    lo, hi = obs.bucket_bounds(b)
    return (lo + hi) / 2


def await_(cond, timeout=30.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timeout waiting for {what}")


def probe_threads():
    return [t for t in threading.enumerate()
            if t.name == gil_probe.THREAD_NAME]


def spin(seconds):
    """A thread that holds the interpreter lock, in pure Python."""
    stop = time.monotonic() + seconds

    def run():
        x = 0
        while time.monotonic() < stop:
            for i in range(1000):
                x += i

    t = threading.Thread(target=run, name="spinner")
    t.start()
    return t


@pytest.fixture
def coop():
    """Three cooperative coordinators (never started: ``step_once``
    from this thread), one group led by the first."""
    reg = NodeRegistry()
    coords = [BatchCoordinator(f"wa{i}", capacity=8, num_peers=3, nodes=reg)
              for i in range(3)]
    ids = [("cg", c.name) for c in coords]
    for c in coords:
        c.add_group("cg", "wacl", ids, SimpleMachine(lambda cm, s: s + cm, 0))

    def step():
        return any([c.step_once() for c in coords])

    def drive(cond, what):
        deadline = time.monotonic() + 30
        while not cond():
            assert time.monotonic() < deadline, what
            if not step():
                time.sleep(0.001)

    coords[0].deliver(ids[0], ElectionTimeout(), None)
    drive(lambda: coords[0].by_name["cg"].role == C.R_LEADER, "leader")
    while step():
        pass
    yield coords, ids, step, drive
    for c in coords:
        c.stop()


@pytest.fixture
def no_leaked_serving():
    """A process in which no coordinator is started (one that an earlier
    test of this worker never stopped is taken out of the registry, as
    tests/test_heap_policy.py does), and every one made here stopped."""
    for counters, _node, _size in list(heap._serving):
        heap.leave(types.SimpleNamespace(counters=counters))
    await_(lambda: not probe_threads(), what="a leaked probe to end")
    made = []
    yield made
    for c in made:
        c.stop()
    assert heap._serving == []


def started(made, name):
    c = BatchCoordinator(name, capacity=8, num_peers=3,
                         detector_poll_s=0.05, tick_interval_s=0.2)
    made.append(c)
    c.start()
    return c


# -- A: the leaves of ingress_drain and host_egress ----------------------------


def test_every_leaf_has_samples_and_the_leaves_add_up(coop):
    """Client commands, consistent queries and the followers' writes
    pass: every sub-phase the deterministic driver can reach has samples,
    each leaf lies inside its phase, and the leaves add up to it (what
    is left is the code between two leaves' clock reads)."""
    coords, ids, step, drive = coop
    lead = coords[0]
    phases = ("ingress_drain", "host_egress") + INGRESS_LEAVES + EGRESS_LEAVES
    before = totals(coords, phases)
    cnt0 = {k: sum(c.counters.get(k) for c in coords) for k in COUNTERS}
    sent0 = {ph: totals(coords, (ph,))[ph][0]
             for ph in ("send_queue", "gil_wait")}
    n = 30
    for k in range(n):
        fut = api.Future()
        lead.deliver(ids[0], Command(kind=USR, data=1, from_ref=fut,
                                     reply_mode="await_consensus"), None)
        drive(fut.done, "a command's reply")
        q = api.Future()
        lead.deliver(ids[0], ("consistent_query", lambda s: s, q), None)
        drive(q.done, "a query's reply")
        assert q.value[:2] == ("ok", k + 1)
    while step():
        pass
    after = totals(coords, phases)
    got = {ph: (after[ph][0] - before[ph][0], after[ph][1] - before[ph][1])
           for ph in phases}
    # (an ingest-only pass is the started loop's: the test below)
    for ph in set(phases) - {"ingest_fanout"}:
        assert got[ph][0] > 0 and got[ph][1] > 0, (ph, got)
    assert got["ingest_fanout"] == (0, 0)
    for whole, leaves in (("ingress_drain", INGRESS_LEAVES),
                          ("host_egress", EGRESS_LEAVES)):
        n_whole, ns_whole = got[whole]
        for leaf in leaves:
            assert got[leaf][0] <= n_whole, (leaf, got)
            assert got[leaf][1] <= ns_whole, (leaf, got)
        covered = sum(got[leaf][1] for leaf in leaves)
        assert 0.8 * ns_whole <= covered <= ns_whole, (whole, got)
    # recorded where and only where ingress_drain is
    assert got["ingress_classify"][0] == got["ingress_drain"][0] \
        == got["step_lock_wait"][0]
    # the counters that turn a total into a time a message: every
    # command is one AppendEntries with one entry on each of two
    # followers; a query is a rare message on the leader and a heartbeat
    # on each follower, answered on the leader again
    cnt = {k: sum(c.counters.get(k) for c in coords) - cnt0[k]
           for k in COUNTERS}
    assert cnt["follower_aers"] == cnt["follower_entries"] == 2 * n
    assert cnt["rares_handled"] >= 5 * n
    assert cnt["routed_msgs"] >= cnt["rares_handled"] + 4 * n
    assert lead.counters.get("follower_aers") == 0
    # a send from a loop that was never started goes inline and waits for
    # no sender; no coordinator here was started, so no probe booked here
    for ph, n0 in sent0.items():
        assert totals(coords, (ph,))[ph][0] == n0


def test_a_rare_only_ticket_is_host_egress_too(coop):
    """A consistent query on a quiet leader finds nothing to step: its
    pass hands the rare message over on a ticket with no egress, and its
    time is ``host_egress`` and ``egress_rare`` (or no phase held it)."""
    coords, ids, step, drive = coop
    lead = coords[0]
    before = totals([lead], ("host_egress", "egress_rare", "device_step"))
    q = api.Future()
    lead.deliver(ids[0], ("consistent_query", lambda s: s, q), None)
    assert lead.step_once()
    after = totals([lead], ("host_egress", "egress_rare", "device_step"))
    assert after["device_step"][0] == before["device_step"][0]
    for ph in ("host_egress", "egress_rare"):
        assert after[ph][0] == before[ph][0] + 1
    assert (after["egress_rare"][1] - before["egress_rare"][1]
            <= after["host_egress"][1] - before["host_egress"][1])
    drive(q.done, "the query's reply")


def test_an_ingest_only_pass_books_ingest_fanout_not_aer_fanout(coop):
    """What the started loop does while a ticket is in flight: the pass
    appends, sends its AppendEntries at once and dispatches nothing. The
    fan-out's time is inside that pass's ``ingress_drain`` and is booked
    as ``ingest_fanout``; ``aer_fanout`` keeps its one writer, the
    thread that realises tickets."""
    coords, ids, step, drive = coop
    lead = coords[0]
    phases = ("ingest_fanout", "aer_fanout", "ingress_drain", "ingest_append")
    before = totals([lead], phases)
    lead.deliver(ids[0], Command(kind=USR, data=5, reply_mode="noreply"),
                 None)
    pre = lead._drain_classify()
    with lead._step_lock:
        assert lead._drain_and_dispatch(pre, dispatch=False) is None
    after = totals([lead], phases)
    got = {ph: (after[ph][0] - before[ph][0], after[ph][1] - before[ph][1])
           for ph in phases}
    assert got["aer_fanout"] == (0, 0)
    assert got["ingest_fanout"][0] == got["ingress_drain"][0] == 1
    assert 0 < got["ingest_fanout"][1] <= got["ingress_drain"][1]
    assert got["ingest_append"][0] == 1
    # the AppendEntries did leave: the followers write and the group
    # commits with no further fan-out for that entry's append
    drive(lambda: all(c.by_name["cg"].machine_state == 5 for c in coords),
          "the command applied everywhere")


def test_no_new_span_is_built_without_a_profiler_session(coop, monkeypatch):
    coords, ids, step, drive = coop
    built = []
    monkeypatch.setattr(obs, "begin",
                        lambda name, **kw: built.append(name))
    monkeypatch.setattr(obs, "span",
                        lambda name, **kw: built.append(name))
    assert obs.tracing() is False
    fut = api.Future()
    coords[0].deliver(ids[0], Command(kind=USR, data=1, from_ref=fut,
                                      reply_mode="await_consensus"), None)
    drive(fut.done, "a command's reply")
    assert built == []


# -- the sender's queue ---------------------------------------------------------


def test_send_queue_has_one_sample_a_batch_the_sender_drained(
        no_leaked_serving):
    made = no_leaked_serving
    coords = [BatchCoordinator(f"sq{i}", capacity=8, num_peers=3,
                               detector_poll_s=0.05, tick_interval_s=0.2)
              for i in range(3)]
    made.extend(coords)
    ids = [("sg", c.name) for c in coords]
    for c in coords:
        c.add_group("sg", "sqcl", ids, SimpleMachine(lambda cm, s: s + cm, 0))
        c.start()
    coords[0].deliver(ids[0], ElectionTimeout(), None)
    await_(lambda: coords[0].by_name["sg"].role == C.R_LEADER, what="leader")
    for k in range(10):
        assert api.process_command(ids[0], 1, timeout=10.0)[0] == k + 1

    def settled():
        return all(wave(c, "send_queue").n
                   == c.counters.get("egress_thread_batches") > 0
                   for c in coords)

    await_(settled, what="every drained batch sampled once")
    for c in coords:
        h = wave(c, "send_queue")
        assert 0 < h.total and h.max_v < 5_000_000_000


# -- B: the probe -----------------------------------------------------------------


def test_the_probe_lives_from_the_first_start_to_the_last_stop(
        no_leaked_serving):
    made = no_leaked_serving
    assert probe_threads() == []
    a = started(made, "gp_a")
    b = started(made, "gp_b")
    (probe,) = probe_threads()
    assert probe.daemon
    a.stop()
    assert probe_threads() == [probe]  # b still serves
    b.stop()
    # within one period of the last stop() (and a turn at the lock)
    await_(lambda: not probe.is_alive(), timeout=5.0, what="the probe's end")
    assert probe_threads() == []
    # a later start() has a probe of its own
    c = started(made, "gp_c")
    (again,) = probe_threads()
    assert again is not probe
    c.stop()
    await_(lambda: not again.is_alive(), timeout=5.0, what="the probe's end")


def test_the_probes_histogram_is_the_first_started_coordinators(
        no_leaked_serving):
    made = no_leaked_serving
    a = started(made, "gh_a")
    b = started(made, "gh_b")
    ha, hb = wave(a, "gil_wait"), wave(b, "gil_wait")
    await_(lambda: ha.n >= 5, what="samples on the first started")
    assert hb.n == 0
    a.stop()
    n_a = ha.n
    await_(lambda: hb.n >= 5, what="samples on the next one")
    assert ha.n <= n_a + 1  # (a sample in flight when a left)
    # some 50 a second, never more
    n0, t0 = hb.n, time.monotonic()
    time.sleep(0.5)
    assert hb.n - n0 <= 50 * (time.monotonic() - t0) + 1


def test_the_probe_reads_a_spinning_threads_turn(no_leaked_serving):
    """Beside one thread of pure Python the probe waits a switch
    interval (5 ms) for the interpreter lock: its median is at least 2 ms
    above its median with nothing running (a comparison, so that a loaded
    host does not decide it)."""
    made = no_leaked_serving
    a = started(made, "gs_a")
    h = wave(a, "gil_wait")
    quiet0 = h.arr
    time.sleep(0.8)
    quiet1 = h.arr
    t = spin(1.0)
    time.sleep(0.1)
    busy0 = h.arr
    time.sleep(0.8)
    busy1 = h.arr
    t.join()
    quiet, busy = median_ns(quiet0, quiet1), median_ns(busy0, busy1)
    assert busy >= quiet + 2_000_000, (quiet, busy)
