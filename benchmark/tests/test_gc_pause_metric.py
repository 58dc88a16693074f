"""``gc_pause_ms_per_kop`` (ISSUE 27): both cells at 8 groups hold it in
their per-layer line, it is the window's ``gc_pause_ns`` over 1,000
acknowledged, a forced collection inside a window shows in it, and a
program without the counter (the parent commit's) reads as nothing."""

import dataclasses
import gc
import threading
import time

import pytest

from benchmark import harness
from benchmark import run as R

NAME = "gc_pause_ms_per_kop"
SMALL = {"config": {"groups": 8, "records": 128},
         "traffic": {"warmup_s": 0.5, "clients": 4, "trace_s": 2}}
SEED = 3_000_000_019


@pytest.mark.parametrize("cell", ["kv_run", "fleet_run"])
def test_both_cells_report_it_from_the_counter(bench, cell, request):
    run = request.getfixturevalue(cell)
    declared = {m["name"]: m for m in
                harness.metrics_of(bench, "per_layer", run.cell["name"])}
    assert declared[NAME]["moves"] == "ops_s" and "workloads" not in declared[NAME]
    got = R.result_line(bench, run, True)["metrics"][NAME]
    assert got["unit"] == declared[NAME]["unit"] == "ms/kop"
    d = run.deltas
    assert got["value"] == \
        d.counter("coordinator", "gc_pause_ns") / 1e6 / (run.acked / 1000.0)
    # a pause has a length, and no pause no length
    n = d.counter("coordinator", "gc_collections")
    assert (got["value"] > 0) == (n > 0)
    assert 0 <= d.counter("coordinator", "gc_full_collections") <= n
    # the process stood still for less than the window
    assert d.counter("coordinator", "gc_pause_ns") < run.window_s * 1e9


def test_a_forced_collection_in_the_window_is_in_it(bench):
    """The fleet at 8 groups with a thread that collects ten times a
    second: every one is counted once (on one coordinator: the snapshot
    adds the three up), and the run stays correct under it."""
    stop, forced = threading.Event(), []

    def collect():
        while not stop.wait(0.1):
            gc.collect()
            forced.append(time.monotonic())

    t = threading.Thread(target=collect, daemon=True)
    t.start()
    try:
        run = R.run_cell(bench, "ra_bench_10k_x3.saturated", SEED, 2.0, False,
                         time.monotonic(), say=lambda line, **kw: None,
                         scale=SMALL)
    finally:
        stop.set()
        t.join()
    assert not run.violations
    inside = [x for x in forced
              if run.deltas.before["t"] <= x <= run.deltas.after["t"]]
    full = run.deltas.counter("coordinator", "gc_full_collections")
    assert len(inside) >= 10 and len(inside) - 1 <= full <= len(inside) + 1
    assert harness.load_module("metrics", NAME).read(run) > 0


def test_a_program_without_the_counter_reads_as_nothing(fleet_run):
    reader = harness.load_module("metrics", NAME)

    def without(snap):
        return {**snap, "coordinator": {
            k: v for k, v in snap["coordinator"].items()
            if not k.startswith("gc_")}}

    old = dataclasses.replace(fleet_run, deltas=harness.Deltas(
        without(fleet_run.deltas.before), without(fleet_run.deltas.after)))
    assert reader.read(old) is None
    empty = harness.Run(cell=fleet_run.cell, config=fleet_run.config,
                        traffic=fleet_run.traffic, seed=0)
    assert reader.read(empty) is None
