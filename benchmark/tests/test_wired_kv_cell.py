"""The kv cell on the wire ``ra_kv_1k_x3_wired.ycsb_a`` at 8 groups:
judged by the same reference as its in-process twin, every call in the
window counted where the leaders take it in, a planted stale read
caught; the deployment on a program whose ``api`` cannot reach another
node; the three client-path readers on this cell, against planted
counters, and on the in-process twin."""

import copy
import dataclasses
import time

import pytest

from benchmark import harness
from benchmark import run as R

CELL = "ra_kv_1k_x3_wired.ycsb_a"
SEED = 3_000_000_019
SMALL = {"config": {"groups": 8, "records": 128},
         "traffic": {"warmup_s": 0.5, "clients": 4, "trace_s": 2}}
NEW = ("client_msgs_per_frame", "client_hop_p50_ms", "reply_hop_p50_ms")


@pytest.fixture(scope="module")
def wired_kv_run(bench):
    lines = []
    run = R.run_cell(bench, CELL, SEED, 2.0, True, time.monotonic(),
                     say=lambda line, **kw: lines.append((line, kw)),
                     scale=SMALL)
    run.lines = lines
    return run


def _line(run, name):
    return [kw for line, kw in run.lines if line == name][0]


def test_the_configuration_is_the_twins_with_its_callers_on_a_fourth_node(
        bench):
    twin = harness.load_json("configs", "ra_kv_1k_x3")
    wired = harness.load_json("configs", "ra_kv_1k_x3_wired")
    differs = ("source", "deployment", "layout", "guarantees", "assumed")
    assert {k: v for k, v in twin.items() if k not in differs} \
        == {k: v for k, v in wired.items() if k not in differs}
    assert wired["deployment"] == "wired_client_cluster"
    assert wired["reduced"] == []
    assert {k: v for k, v in wired["guarantees"].items()
            if k not in ("wire", "client")} == twin["guarantees"]
    assert set(twin["assumed"]) < set(wired["assumed"])
    cell = harness.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ra_kv_1k_x3_wired", "ycsb_a", 1)
    e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)]
    assert e2e == ["ops_s", "commit_p95_ms", "setup_s"]
    declared = {m["name"]: m for m in harness.metrics_of(
        bench, "per_layer", CELL)}
    for name in NEW:
        assert declared[name]["workloads"] == [CELL]


def test_the_cell_is_correct_and_every_call_crossed(bench, wired_kv_run):
    run = wired_kv_run
    out = R.result_line(bench, run, False)
    assert out["correct"] is True, run.violations
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"ops_s", "commit_p95_ms", "setup_s"}
    assert run.ops["read"].acked > 0 and run.ops["write"].acked > 0
    assert _line(run, "client_node")["replicas_held"] == 0
    assert _line(run, "cluster")["transport"] == "tcp"
    assert _line(run, "health")["term_bumps_since_window_start"] == 0
    teardown = _line(run, "teardown")
    assert teardown["threads_that_outlived_stop"] == []
    assert not [t for t in teardown["python_threads"] if "ra-" in t]
    d = run.deltas
    calls_in = d.counter("client", "client_calls_in")
    clients = run.traffic["clients"]
    # an operation that ended in the window was a call the leaders took
    # in through a socket: the counters are read at the snapshots, a
    # little outside the window's edges, so the calls in flight at
    # either edge count on one side only (each edge holds at most one a
    # client); more calls only for redirects and reads asked again
    more = (d.counter("client", "client_redirects")
            + d.counter("coordinator", "read_log_misses"))
    assert out["attempted"] - 2 * clients <= calls_in \
        <= out["attempted"] + 2 * clients + more
    assert d.counter("client", "client_timeouts") == 0
    assert d.counter("client", "client_late_replies") == 0
    assert abs(d.counter("client", "client_calls_out") - calls_in) \
        <= 2 * clients


def test_a_planted_stale_read_is_incorrect(wired_kv_run):
    run = wired_kv_run
    ref = harness.load_module("reference", "ra_kv")
    assert ref.judge(run.history, run.observed, run.config) == []
    history = copy.deepcopy(run.history)
    p, g = history["puts"], history["gets"]
    rows = {}
    for r in range(len(p["key"])):
        if p["ok"][r]:
            rows.setdefault(p["key"][r], []).append(r)
    first, last = next(sorted(v, key=lambda r: p["index"][r])[::len(v) - 1]
                       for v in rows.values() if len(v) > 1)
    g["key"].append(p["key"][first])
    g["t_send"].append(p["t_done"][last] + 1)
    g["t_done"].append(p["t_done"][last] + 2)
    g["writer"].append(p["writer"][first])
    g["seq"].append(p["seq"][first])
    g["ok"].append(True)
    bad = ref.judge(history, run.observed, run.config)
    assert bad and "stale read" in bad[0]
    stale = dataclasses.replace(run, history=history, violations=bad)
    assert R.result_line(harness.load_benchmark(), stale,
                         False)["correct"] is False


def test_the_deployment_refuses_a_program_without_the_client_path(
        monkeypatch):
    from ra_tpu.runtime import tcp

    mod = harness.load_module("deployments", "wired_client_cluster")
    monkeypatch.delattr(tcp.TcpTransport, "send_call")
    said = []
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="no result"):
        mod.Cluster(harness.load_json("configs", "ra_kv_1k_x3_wired"),
                    lambda: None, [], lambda *a, **k: said.append(a))
    assert time.monotonic() - t0 < 1 and not said  # nothing was built


def test_the_three_readers_read_this_cell(bench, wired_kv_run):
    run = wired_kv_run
    layer = R.result_line(bench, run, True)["metrics"]
    declared = {m["name"]: m for m in
                harness.metrics_of(bench, "per_layer", CELL)}
    assert set(NEW) <= set(layer) <= set(declared)
    for name in NEW:
        assert layer[name]["unit"] == declared[name]["unit"]
    # a call is a frame of its own; a wave's replies to the client node
    # are one frame
    assert 1.0 <= layer["client_msgs_per_frame"]["value"] <= 2.0
    assert layer["client_hop_p50_ms"]["value"] > 0
    assert layer["reply_hop_p50_ms"]["value"] > 0
    # every per-layer metric of the wired fifo cell's line that this
    # cell's traffic can move is here too
    assert {"apply_reply_p50_ms", "submit_append_p50_ms",
            "durable_commit_p50_ms", "host_ms_per_kop", "ops_per_step",
            "fsyncs_per_kop", "send_queue_p50_ms"} <= set(layer)


def _planted(run, **counters):
    before = copy.deepcopy(run.deltas.before)
    after = copy.deepcopy(run.deltas.after)
    for k, v in counters.items():
        before["client"][k] = 7
        after["client"][k] = 7 + v
    return dataclasses.replace(run, deltas=harness.Deltas(before, after))


def test_readers_against_planted_counters(wired_kv_run):
    planted = _planted(wired_kv_run, client_calls_out=300,
                       client_replies_out=300, client_frames_out=400)
    read = harness.load_module("metrics", "client_msgs_per_frame").read
    assert read(planted) == pytest.approx(1.5)
    assert read(_planted(wired_kv_run, client_frames_out=0)) is None


@pytest.mark.parametrize("program", ["parent", "in_process_twin"])
def test_nothing_to_read_reads_as_nothing(bench, wired_kv_run, kv_run,
                                          program):
    """The parent's deployment and the in-process twin's snapshot have
    no client family: the readers return nothing, the line leaves them
    out."""
    if program == "parent":
        def strip(snap):
            return {k: v for k, v in snap.items()
                    if k not in ("client", "client_hops")}
        run = dataclasses.replace(wired_kv_run, deltas=harness.Deltas(
            strip(wired_kv_run.deltas.before),
            strip(wired_kv_run.deltas.after)))
    else:
        run = kv_run
    for name in NEW:
        assert harness.load_module("metrics", name).read(run) is None, name
    line = R.result_line(bench, run, True)
    assert not set(NEW) & set(line["metrics"])
    empty = harness.Run(cell=run.cell, config=run.config,
                        traffic=run.traffic, seed=0)
    assert all(harness.load_module("metrics", n).read(empty) is None
               for n in NEW)
