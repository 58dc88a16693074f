"""The quorum-queue cell ``ra_fifo_10k_x3.hot_queues`` (ISSUE 30) at 8
groups, 4 of them hot, end to end with both result lines; its three
per-layer readers against planted counters; and the same readers on a
program that lacks what they read (the parent commit's)."""

import copy
import dataclasses
import time

import pytest

from benchmark import harness
from benchmark import run as R

CELL = "ra_fifo_10k_x3.hot_queues"
SEED = 3_000_000_019
SMALL = {"config": {"groups": 8},
         "traffic": {"warmup_s": 0.5, "trace_s": 2, "hot_queues": 4}}
NEW = ("send_msgs_per_kop", "effects_ms_per_kop", "snapshots_per_kop")


@pytest.fixture(scope="module")
def fifo_traced_run(bench):
    lines = []
    run = R.run_cell(bench, CELL, SEED, 2.0, True, time.monotonic(),
                     say=lambda line, **kw: lines.append((line, kw)),
                     scale=SMALL)
    run.lines = lines
    return run


def test_fifo_cell_end_to_end_with_both_lines(bench, fifo_traced_run):
    run = fifo_traced_run
    e2e = R.result_line(bench, run, False)
    assert e2e["correct"] is True, run.violations
    assert e2e["failed"] == 0 and e2e["attempted"] > 0
    assert set(e2e["metrics"]) == {"ops_s", "commit_p95_ms", "setup_s"}
    assert run.ops["write"].acked > 0 and run.ops["settle"].acked > 0
    layer = R.result_line(bench, run, True)
    declared = {m["name"]: m for m in
                harness.metrics_of(bench, "per_layer", CELL)}
    assert set(NEW) <= set(layer["metrics"]) <= set(declared)
    # every per-layer metric without a list that a CPU run can read is
    # there: the four commit stages too (a sampled group is hot)
    assert {"append_durable_p50_ms", "apply_reply_p50_ms",
            "submit_append_p50_ms", "durable_commit_p50_ms",
            "egress_apply_ms_per_kop", "fsyncs_per_kop"} <= set(layer["metrics"])
    for name, got in layer["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
    assert "read_quorum_pct" not in layer["metrics"]
    assert "step_roofline" not in layer["metrics"]
    m = layer["metrics"]
    assert m["send_msgs_per_kop"]["value"] == pytest.approx(500, abs=25)
    assert 0 < m["effects_ms_per_kop"]["value"] \
        < m["egress_apply_ms_per_kop"]["value"]
    assert m["snapshots_per_kop"]["value"] == 0.0
    health = [kw for line, kw in run.lines if line == "health"][0]
    assert health["compilations_in_window"] == 0
    assert health["issued"]["deliveries"] > 0
    assert [kw for line, kw in run.lines if line == "teardown"][0][
        "threads_that_outlived_stop"] == []
    assert run.window_s < 2.5  # cut to the traffic's trace_s


def _planted(run, **counters):
    """``run`` with the window's coordinator counters replaced."""
    before = copy.deepcopy(run.deltas.before)
    after = copy.deepcopy(run.deltas.after)
    for k, v in counters.items():
        before["coordinator"][k] = 7
        after["coordinator"][k] = 7 + v
    return dataclasses.replace(run, deltas=harness.Deltas(before, after))


def test_readers_against_planted_counters(fifo_traced_run):
    run = fifo_traced_run
    kops = run.acked / 1000.0
    planted = _planted(run, effects_send_msg=1234, release_cursor_snapshots=5)
    read = {n: harness.load_module("metrics", n).read for n in NEW}
    assert read["send_msgs_per_kop"](planted) == pytest.approx(1234 / kops)
    assert read["snapshots_per_kop"](planted) == pytest.approx(5 / kops)
    h = run.deltas.hist("wave", "effects_realise")
    assert read["effects_ms_per_kop"](run) == \
        pytest.approx(h.total_ns / 1e6 / kops)
    # the sub-phase is a subset of the step's applies
    assert h.total_ns <= run.deltas.hist("wave", "egress_apply").total_ns


def test_a_program_without_the_accounts_reads_as_nothing(fifo_traced_run):
    run = fifo_traced_run

    def without(snap):
        return {**snap,
                "coordinator": {k: v for k, v in snap["coordinator"].items()
                                if k not in ("effects_send_msg",
                                             "release_cursor_snapshots")},
                "wave": {k: v for k, v in snap["wave"].items()
                         if k != "effects_realise"}}

    old = dataclasses.replace(run, deltas=harness.Deltas(
        without(run.deltas.before), without(run.deltas.after)))
    empty = harness.Run(cell=run.cell, config=run.config,
                        traffic=run.traffic, seed=0)
    for name in NEW:
        reader = harness.load_module("metrics", name)
        assert reader.read(old) is None, name
        assert reader.read(empty) is None, name
    # the line of such a program leaves them out and keeps the rest
    line = R.result_line(harness.load_benchmark(), old, True)
    assert not set(NEW) & set(line["metrics"])
    assert "egress_apply_ms_per_kop" in line["metrics"]


def test_ycsb_b_is_the_kv_cell_with_another_mix(bench):
    a = harness.load_json("traffic", "ycsb_a")
    b = harness.load_json("traffic", "ycsb_b")
    assert (b["read_proportion"], b["update_proportion"]) == (0.95, 0.05)
    differs = ("why", "read_proportion", "update_proportion", "trace_s",
               "assumed")
    assert {k: v for k, v in a.items() if k not in differs} \
        == {k: v for k, v in b.items() if k not in differs}
    # few writes, so a longer traced window: the commit stages are sampled
    assert (a["trace_s"], b["trace_s"]) == (5, 10) and "trace_s" in b["assumed"]
    cell = harness.find_cell(bench, "ra_kv_1k_x3.ycsb_b")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ra_kv_1k_x3", "ycsb_b", 1)
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                 cell["name"])]
    assert e2e == ["ops_s", "commit_p95_ms", "setup_s"]


def test_ycsb_b_cell_end_to_end(bench):
    lines = []
    run = R.run_cell(bench, "ra_kv_1k_x3.ycsb_b", SEED, 2.0, False,
                     time.monotonic(),
                     say=lambda line, **kw: lines.append((line, kw)),
                     scale={"config": {"groups": 8, "records": 128},
                            "traffic": {"warmup_s": 0.5, "clients": 4,
                                        "trace_s": 2}})
    out = R.result_line(bench, run, False)
    assert out["correct"] is True, run.violations
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"ops_s", "commit_p95_ms", "setup_s"}
    reads, writes = run.ops["read"].acked, run.ops["write"].acked
    assert writes > 0 and reads > 5 * writes
    layer = R.result_line(bench, run, True)["metrics"]
    assert "read_quorum_pct" not in layer  # the list names ycsb_a alone
    assert {"fsyncs_per_kop", "host_ms_per_kop"} <= set(layer)
