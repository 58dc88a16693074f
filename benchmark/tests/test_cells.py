"""Each cell end to end at 8 groups, and ``correct`` turning false on a
planted lost write, a planted doubled command and a planted stale read."""

import copy

from benchmark import harness
from benchmark import run as R


def _line(run, name):
    return [kw for line, kw in run.lines if line == name]


def _check_line(bench, run, trace):
    out = R.result_line(bench, run, trace)
    assert out["correct"] is True, run.violations
    assert out["failed"] == 0 and out["attempted"] > 0
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in
                harness.metrics_of(bench, section, run.cell["name"])}
    assert set(out["metrics"]) <= set(declared)
    for name, got in out["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
        assert isinstance(got["value"], float)
    return out


def test_kv_cell_end_to_end(bench, kv_run):
    out = _check_line(bench, kv_run, trace=False)
    assert set(out["metrics"]) == {"ops_s", "commit_p50_ms", "commit_p95_ms",
                                   "read_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert kv_run.ops["read"].acked > 0 and kv_run.ops["write"].acked > 0
    assert _line(kv_run, "health")[0]["compilations_in_window"] == 0
    assert _line(kv_run, "teardown")[0]["threads_that_outlived_stop"] == []
    # the load phase wrote every record, outside the window
    assert len(kv_run.observed["maps"][0]) == kv_run.config["records"]


def test_fleet_cell_end_to_end(all_cells, fleet_run):
    out = _check_line(all_cells, fleet_run, trace=False)
    assert set(out["metrics"]) == {"ops_s", "commit_p95_ms", "setup_s"}
    h = fleet_run.history
    assert sum(h["count"]) >= fleet_run.acked > 0
    assert not h["unknown"] and not h["retired"]


def test_per_layer_line_from_counters_and_spans(all_cells, fleet_traced_run):
    bench = all_cells
    """On the CPU the trace holds no device plane: the trace's readers
    return nothing and are left out; the counters' and spans' are there."""
    out = _check_line(bench, fleet_traced_run, trace=True)
    assert {"host_ms_per_kop", "ops_per_step", "fsyncs_per_kop",
            "append_durable_p50_ms", "apply_reply_p50_ms",
            "device_step_p50_ms", "rejected_per_kop",
            "unasked_elections"} <= set(out["metrics"])
    assert "read_quorum_pct" not in out["metrics"]  # the kv cell's alone
    assert fleet_traced_run.trace is None
    assert "step_device_us" not in out["metrics"]
    assert fleet_traced_run.window_s < 2.5  # cut to the traffic's trace_s


def test_kv_per_layer_read_path(bench, kv_run):
    out = R.result_line(bench, kv_run, True)
    assert out["metrics"]["read_quorum_pct"]["value"] == 100.0  # lease off
    assert out["metrics"]["fsyncs_per_kop"]["value"] > 0


def _judge(run, history=None, observed=None):
    ref = harness.load_module("reference", run.config["reference"])
    return ref.judge(history or run.history, observed or run.observed,
                     run.config)


def test_planted_lost_write_is_caught(kv_run):
    assert _judge(kv_run) == []
    observed = copy.deepcopy(kv_run.observed)
    p = kv_run.history["puts"]
    # the newest acknowledged put of some key never reached node 2
    row = max(range(len(p["key"])), key=lambda r: p["index"][r])
    key = kv_run.history["keys"][p["key"][row]]
    older = [p["index"][r] for r in range(len(p["key"]))
             if p["key"][r] == p["key"][row] and r != row]
    observed["maps"][2][key] = max(older)
    bad = _judge(kv_run, observed=observed)
    assert bad and "lost write" in bad[0]
    del observed["maps"][2][key]
    assert _judge(kv_run, observed=observed)


def test_planted_stale_read_is_caught(kv_run):
    history = copy.deepcopy(kv_run.history)
    p, g = history["puts"], history["gets"]
    # a key put twice: a read sent after the second acknowledgement
    # that returns the first put's value
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
    bad = _judge(kv_run, history=history)
    assert bad and "stale read" in bad[0]
    # the same read sent before any later put was acknowledged is allowed
    g["t_send"][-1] = p["t_done"][first]
    assert _judge(kv_run, history=history) == []


def test_planted_doubled_and_lost_command_are_caught(fleet_run):
    assert _judge(fleet_run) == []
    observed = copy.deepcopy(fleet_run.observed)
    count, total = observed["states"][3][1]
    observed["states"][3][1] = (count + 1, total + 12345)
    bad = _judge(fleet_run, observed=observed)
    assert bad and "doubled" in bad[0]
    observed["states"][3][1] = (count - 1, total - 1)
    bad = _judge(fleet_run, observed=observed)
    assert bad and "lost" in bad[0]


def test_unknown_outcome_is_held_to_a_range(fleet_run):
    history = copy.deepcopy(fleet_run.history)
    observed = copy.deepcopy(fleet_run.observed)
    count, total = observed["states"][5][0]
    history["unknown"][5] = [77]
    assert _judge(fleet_run, history=history, observed=observed) == []
    observed["states"][5] = [(count + 1, total + 77)] * 3
    assert _judge(fleet_run, history=history, observed=observed) == []
    observed["states"][5][2] = (count, total)  # the replicas disagree
    assert _judge(fleet_run, history=history, observed=observed)
