"""The wired quorum-queue cell ``ra_fifo_10k_x3_wired.hot_queues``
(ISSUE 33) at 8 groups, 4 of them hot, traced, with both result lines;
its four per-layer readers against planted counters; the same readers
on a program that lacks what they read (the parent commit's) and on a
deployment that never leaves its process (the in-process twin)."""

import copy
import dataclasses
import time

import pytest

from benchmark import harness
from benchmark import run as R

CELL = "ra_fifo_10k_x3_wired.hot_queues"
SEED = 3_000_000_019
SMALL = {"config": {"groups": 8},
         "traffic": {"warmup_s": 0.5, "trace_s": 2, "hot_queues": 4}}
NEW = ("wire_msgs_per_frame", "wire_ms_per_kop", "wire_bytes_per_op",
       "wire_dropped_per_kop")
WIRE_COUNTERS = ("wire_frames_out", "wire_msgs_out", "wire_bytes_out",
                 "wire_frames_in", "wire_msgs_in", "wire_bytes_in",
                 "wire_encode_ns", "wire_decode_ns", "wire_dropped")


@pytest.fixture(scope="module")
def wired_traced_run(bench):
    lines = []
    run = R.run_cell(bench, CELL, SEED, 2.0, True, time.monotonic(),
                     say=lambda line, **kw: lines.append((line, kw)),
                     scale=SMALL)
    run.lines = lines
    return run


def test_the_configuration_is_the_twins_on_the_wire(bench):
    twin = harness.load_json("configs", "ra_fifo_10k_x3")
    wired = harness.load_json("configs", "ra_fifo_10k_x3_wired")
    differs = ("source", "deployment", "layout", "guarantees", "assumed")
    assert {k: v for k, v in twin.items() if k not in differs} \
        == {k: v for k, v in wired.items() if k not in differs}
    assert wired["deployment"] == "wired_cluster" and wired["reduced"] == []
    # the twin's three guarantees word for word, and the wire's
    assert {k: v for k, v in wired["guarantees"].items() if k != "wire"} \
        == twin["guarantees"]
    assert "authenticated" in wired["guarantees"]["wire"]
    assert set(twin["assumed"]) < set(wired["assumed"])
    cell = harness.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ra_fifo_10k_x3_wired", "hot_queues", 1)
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)]
    assert e2e == ["ops_s", "commit_p95_ms", "setup_s"]
    # every per-layer metric the twin's line can hold, and the four new
    layer = {m["name"] for m in harness.metrics_of(bench, "per_layer", CELL)}
    twins = {m["name"] for m in harness.metrics_of(
        bench, "per_layer", "ra_fifo_10k_x3.hot_queues")}
    assert layer == twins | set(NEW)


def test_wired_cell_end_to_end_with_both_lines(bench, wired_traced_run):
    run = wired_traced_run
    e2e = R.result_line(bench, run, False)
    assert e2e["correct"] is True, run.violations
    assert e2e["failed"] == 0 and e2e["attempted"] > 0
    assert set(e2e["metrics"]) == {"ops_s", "commit_p95_ms", "setup_s"}
    layer = R.result_line(bench, run, True)
    declared = {m["name"]: m for m in
                harness.metrics_of(bench, "per_layer", CELL)}
    assert set(NEW) <= set(layer["metrics"]) <= set(declared)
    for name, got in layer["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
    m = layer["metrics"]
    assert m["wire_msgs_per_frame"]["value"] > 1
    assert m["wire_dropped_per_kop"]["value"] == 0.0
    assert 1024 < m["wire_bytes_per_op"]["value"] < 8 * 1024
    assert 0 < m["wire_ms_per_kop"]["value"]
    assert {"send_msgs_per_kop", "effects_ms_per_kop", "snapshots_per_kop",
            "host_ms_per_kop", "fsyncs_per_kop"} <= set(m)
    cluster = [kw for line, kw in run.lines if line == "cluster"][0]
    assert cluster["transport"] == "tcp" and cluster["connections"] == 6
    health = [kw for line, kw in run.lines if line == "health"][0]
    assert health["compilations_in_window"] == 0
    assert health["term_bumps_since_window_start"] == 0
    assert [kw for line, kw in run.lines if line == "teardown"][0][
        "threads_that_outlived_stop"] == []
    # every frame written in the window was read in it, give or take
    # those in flight at its two ends
    d = run.deltas
    assert abs(d.counter("coordinator", "wire_frames_out")
               - d.counter("coordinator", "wire_frames_in")) <= 12


def _planted(run, **counters):
    before = copy.deepcopy(run.deltas.before)
    after = copy.deepcopy(run.deltas.after)
    for k, v in counters.items():
        before["coordinator"][k] = 7
        after["coordinator"][k] = 7 + v
    return dataclasses.replace(run, deltas=harness.Deltas(before, after))


def test_readers_against_planted_counters(wired_traced_run):
    run = wired_traced_run
    kops = run.acked / 1000.0
    planted = _planted(run, wire_frames_out=50, wire_msgs_out=1800,
                       wire_bytes_out=900_000, wire_encode_ns=30_000_000,
                       wire_decode_ns=50_000_000, wire_dropped=3)
    read = {n: harness.load_module("metrics", n).read for n in NEW}
    assert read["wire_msgs_per_frame"](planted) == pytest.approx(36.0)
    assert read["wire_ms_per_kop"](planted) == pytest.approx(80.0 / kops)
    assert read["wire_bytes_per_op"](planted) == \
        pytest.approx(900_000 / run.acked)
    assert read["wire_dropped_per_kop"](planted) == pytest.approx(3 / kops)


@pytest.mark.parametrize("program", ["parent", "in_process_twin"])
def test_nothing_to_read_reads_as_nothing(wired_traced_run, program):
    """The parent's coordinator has no ``wire_*`` field; the twin's has
    them at 0, because nothing of it leaves its process. Either way the
    readers return nothing and the line leaves the metrics out."""
    run = wired_traced_run

    def strip(snap):
        co = snap["coordinator"]
        if program == "parent":
            co = {k: v for k, v in co.items() if k not in WIRE_COUNTERS}
        else:
            co = {**co, **{k: 0 for k in WIRE_COUNTERS}}
        return {**snap, "coordinator": co}

    old = dataclasses.replace(run, deltas=harness.Deltas(
        strip(run.deltas.before), strip(run.deltas.after)))
    empty = harness.Run(cell=run.cell, config=run.config,
                        traffic=run.traffic, seed=0)
    for name in NEW:
        reader = harness.load_module("metrics", name)
        assert reader.read(old) is None, name
        assert reader.read(empty) is None, name
    line = R.result_line(harness.load_benchmark(), old, True)
    assert not set(NEW) & set(line["metrics"])
    assert "send_msgs_per_kop" in line["metrics"]


def test_the_deployment_refuses_a_program_that_cannot_take_the_wire(
        monkeypatch):
    from ra_tpu.runtime.coordinator import BatchCoordinator

    mod = harness.load_module("deployments", "wired_cluster")
    real = BatchCoordinator.__init__

    def parents_init(self, node_name, capacity=1024, num_peers=3, nodes=None):
        real(self, node_name, capacity, num_peers, nodes)

    monkeypatch.setattr(BatchCoordinator, "__init__", parents_init)
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="no result"):
        mod.Cluster(harness.load_json("configs", "ra_fifo_10k_x3_wired"),
                    lambda: None, [], lambda *a, **k: None)
    assert time.monotonic() - t0 < 1  # at once: nothing was built
