"""The ``ra_bench`` fleet deployment (``ra_bench_10k_x3.saturated``) at
8 and 16 groups on the CPU, through the benchmark's own ``run_cell``:
three started, WAL-backed coordinators, one command in flight per
group, judged by ``reference/ra_bench.py``. Holds the accounts that only
a busy fleet works (ISSUE 26) to what the run really did: the WAL
writers' state-lock rounds, the two per-pass sub-phases, and the seven
per-layer readers that read them.

The cell is read from ``BENCHMARK.json``, as the driver reads it; the
roofline share is listed there as ``step_roofline`` (the contract's name
for a kernel's share), a reader over ``step_roofline_pct.py``.
"""

import copy
import dataclasses
import os
import sys
import time
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark import run as R  # noqa: E402
from ra_tpu import obs  # noqa: E402
from ra_tpu.runtime.coordinator import BatchCoordinator  # noqa: E402

CELL = "ra_bench_10k_x3.saturated"
SEED = 3_000_000_019  # above 2**31, as the driver's are
WINDOW_S = 2.0
NODES = ("bench0", "bench1", "bench2")
READERS = ("step_roofline", "full_width_steps_pct", "wal_entries_per_fsync",
           "wal_notify_wait_ms_per_kop", "ingest_append_ms_per_kop",
           "egress_apply_ms_per_kop", "gc_pause_ms_per_kop",
           "wal_cpu_us_per_entry", "wal_runs_in_place_pct")


def _bench():
    return harness.load_benchmark()


def _hist_n(phase):
    hs = [obs.histograms().fetch(("wave", n, phase)) for n in NODES]
    return sum(h.n for h in hs if h is not None)


def _watched_run(groups):
    """One run of the cell at ``groups`` groups, with the coordinators'
    entry points wrapped to count, beside the program's own accounts,
    the passes that had client commands, the steps that applied
    something, and the written events each WAL handed over."""
    seen = {"ingest": Counter(), "apply": Counter(), "delivered": Counter(),
            "counted": {}, "mismatch": []}
    applying = {}
    ingest, egress = BatchCoordinator._ingest, BatchCoordinator._process_egress
    apply_group = BatchCoordinator._apply_group
    notify_many = BatchCoordinator.wal_notify_many

    def _ingest(self, n_items, cmd_q, *args, **kw):
        if cmd_q:
            seen["ingest"][self.name] += 1
        return ingest(self, n_items, cmd_q, *args, **kw)

    def _apply_group(self, g, commit_index):
        applying[self.name] = True
        return apply_group(self, g, commit_index)

    def _process_egress(self, *args, **kw):
        applying[self.name] = False
        try:
            return egress(self, *args, **kw)
        finally:
            if applying[self.name]:
                seen["apply"][self.name] += 1

    def wal_notify_many(self, rows):
        # (one WAL writer per coordinator: its rounds come one by one)
        written = len(rows)
        before = self.counters.get("wal_notify_events")
        notify_many(self, rows)
        after = self.counters.get("wal_notify_events")
        if after - before != written:
            seen["mismatch"].append((self.name, written, after - before))
        seen["delivered"][self.name] += written
        seen["counted"][self.name] = after

    mp = pytest.MonkeyPatch()
    mp.setattr(BatchCoordinator, "_ingest", _ingest)
    mp.setattr(BatchCoordinator, "_apply_group", _apply_group)
    mp.setattr(BatchCoordinator, "_process_egress", _process_egress)
    mp.setattr(BatchCoordinator, "wal_notify_many", wal_notify_many)
    n0 = {ph: _hist_n(ph) for ph in ("ingest_append", "egress_apply")}
    lines = []
    try:
        run = R.run_cell(
            _bench(), CELL, SEED, WINDOW_S, False,
            time.monotonic(), say=lambda line, **kw: lines.append((line, kw)),
            scale={"config": {"groups": groups},
                   "traffic": {"warmup_s": 0.5, "trace_s": 2}})
    finally:
        mp.undo()
    run.lines = lines
    run.seen = seen
    run.recorded = {ph: _hist_n(ph) - n0[ph] for ph in n0}
    return run


@pytest.fixture(scope="module")
def fleet8():
    return _watched_run(8)


@pytest.fixture(scope="module")
def fleet16():
    return _watched_run(16)


def _line(run, name):
    return [kw for line, kw in run.lines if line == name]


@pytest.mark.parametrize("groups", [8, 16])
def test_the_cell_is_correct_against_the_plain_reference(groups, request):
    run = request.getfixturevalue(f"fleet{groups}")
    bench = _bench()
    out = R.result_line(bench, run, False)
    assert out["correct"] is True, run.violations
    assert out["failed"] == 0 and out["attempted"] > groups
    assert set(out["metrics"]) == {"ops_s", "commit_p95_ms", "setup_s"}
    h = run.history
    assert h["groups"] == groups and min(h["count"]) > 0
    assert not h["unknown"] and not h["retired"]
    # every acknowledged command once on all three replicas
    for g in range(groups):
        assert set(run.observed["states"][g]) == {(h["count"][g], h["sum"][g])}
    health = _line(run, "health")[0]
    assert health["compilations_in_window"] == 0
    assert health["lane_wedges"] == 0
    assert _line(run, "teardown")[0]["threads_that_outlived_stop"] == []
    # the cell's traced line holds every per-layer metric it is given
    # that reads counters or spans (the trace's two need a chip)
    layer = R.result_line(bench, run, True)["metrics"]
    declared = {m["name"] for m in
                harness.metrics_of(bench, "per_layer", CELL)}
    assert set(READERS) <= declared
    assert declared - set(layer) == {"step_device_us", "step_roofline"}


@pytest.mark.parametrize("planted", ["lost", "doubled"])
def test_a_planted_fault_turns_correct_false(fleet8, planted):
    ref = harness.load_module("reference", fleet8.config["reference"])
    assert ref.judge(fleet8.history, fleet8.observed, fleet8.config) == []
    observed = copy.deepcopy(fleet8.observed)
    count, total = observed["states"][3][1]
    observed["states"][3][1] = (
        (count - 1, total - 1) if planted == "lost"
        else (count + 1, total + 12345))
    bad = ref.judge(fleet8.history, observed, fleet8.config)
    assert bad and planted in bad[0]


def test_the_small_fleet_works_the_full_width_path(fleet8):
    d = fleet8.deltas
    steps, sub = d.scalar("steps"), d.scalar("sub_steps")
    # the active-set program takes a step only when at most a quarter of
    # the capacity is active (two groups of 8): with every group in
    # flight nearly every step goes through _build_mailbox and the
    # full-width program
    assert steps - sub > sub >= 0
    reader = harness.load_module("metrics", "full_width_steps_pct")
    assert reader.read(fleet8) == 100.0 * (steps - sub) / steps > 50.0


def test_wal_notify_accounts_match_what_the_wals_delivered(fleet8):
    seen = fleet8.seen
    assert seen["mismatch"] == []
    assert set(seen["delivered"]) == set(NODES)
    for node in NODES:
        assert seen["counted"][node] == seen["delivered"][node] > 0
    d = fleet8.deltas
    rounds = d.counter("coordinator", "wal_notify_batches")
    events = d.counter("coordinator", "wal_notify_events")
    assert 0 < rounds <= events
    # every command of the window was written on a quorum at least
    assert events >= 2 * fleet8.acked
    wait = d.counter("coordinator", "wal_notify_wait_ns")
    hold = d.counter("coordinator", "wal_notify_hold_ns")
    assert wait >= 0 and hold > 0
    # three WAL writers, each inside at most one round at a time (a
    # round that straddles the window's edge counts whole: 10 ms each)
    assert wait + hold <= (fleet8.window_s * 1e9 + 10e6) * len(NODES)


def test_sub_phases_stay_inside_their_phases_one_record_a_working_pass(fleet8):
    d = fleet8.deltas
    for part, whole in (("ingest_append", "ingress_drain"),
                        ("egress_apply", "host_egress")):
        p, w = d.hist("wave", part), d.hist("wave", whole)
        assert 0 < p.n <= w.n
        assert 0 < p.total_ns <= w.total_ns
    # over the whole run: one record per pass that had client commands,
    # one per step that applied anything, whatever the number of groups
    assert fleet8.recorded["ingest_append"] == sum(fleet8.seen["ingest"].values())
    assert fleet8.recorded["egress_apply"] == sum(fleet8.seen["apply"].values())
    assert d.hist("wave", "egress_apply").n <= d.scalar("steps")


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_a_float_here_and_nothing_from_an_empty_run(
        fleet8, name):
    reader = harness.load_module("metrics", name)
    run = fleet8
    if name == "step_roofline":
        # the CPU's trace has no device plane: hand the reader PR 23's
        # chip reading (33 full-width steps at 0.926 ms of device time,
        # 10,240 x 3) and hold it to that PR's figure, 0.84 %
        from benchmark import roofline

        run = dataclasses.replace(
            fleet8, step_bytes=roofline.step_bytes(10240, 3),
            trace={"full_step_count": 33, "full_step_seconds": 33 * 0.926e-3},
            device={"kind": "TPU v5 lite"})
        assert abs(reader.read(run) - 0.84) < 0.01
        assert reader.read(fleet8) is None  # no trace, no reading
    value = reader.read(run)
    assert isinstance(value, float) and value >= 0.0
    empty = harness.Run(cell=fleet8.cell, config=fleet8.config,
                        traffic=fleet8.traffic, seed=0)
    assert reader.read(empty) is None
    # a program without the new accounts (the parent's) reads as nothing
    # where the metric needs them, never as an error
    if name in ("wal_notify_wait_ms_per_kop", "ingest_append_ms_per_kop",
                "egress_apply_ms_per_kop", "gc_pause_ms_per_kop",
                "wal_cpu_us_per_entry", "wal_runs_in_place_pct"):
        def without(snap):
            return {**snap,
                    "coordinator": {k: v for k, v in snap["coordinator"].items()
                                    if not k.startswith(("wal_notify_", "gc_"))},
                    "wal": {k: v for k, v in snap["wal"].items()
                            if k not in ("writer_cpu_ns", "runs", "runs_in_place")},
                    "wave": {k: v for k, v in snap["wave"].items()
                             if k not in ("ingest_append", "egress_apply")}}

        old = dataclasses.replace(fleet8, deltas=harness.Deltas(
            without(fleet8.deltas.before), without(fleet8.deltas.after)))
        assert reader.read(old) is None
