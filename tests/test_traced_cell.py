"""``scripts/traced_cell.py`` and ``idle_gaps.dispatch_edges`` (ISSUE 35):
a step dispatch laid against the run of the program it started, and the
wrapper that keeps a cell's trace until both tables are read off it."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import idle_gaps  # noqa: E402
import traced_cell  # noqa: E402

D = idle_gaps.DISPATCH_SPAN


def test_a_dispatch_takes_the_first_run_that_begins_inside_or_after_it():
    ms = 1_000_000
    spans = [
        # n1: the program starts 1 ms into the call and ends 2 ms before
        # the call returns (the thread stood at the lock, device done)
        (D, "n1", 0, 10 * ms),
        # n2 calls while n1's is in flight; its program runs after n1's
        # and is still running when the call returns
        (D, "n2", 2 * ms, 12 * ms),
        (D, "n1", 30 * ms, 33 * ms),
        ("ra/step/host_pack", "n1", 0, 10 * ms),  # no dispatch: not counted
        (D, "n2", 90 * ms, 91 * ms),  # no run after it in the trace
    ]
    runs = [(1 * ms, 8 * ms), (9 * ms, 14 * ms), (31 * ms, 32 * ms)]
    got = idle_gaps.dispatch_edges(runs, spans)
    assert (got["dispatches"], got["step_runs"]) == (4, 3)
    rows = {r["node"]: r for r in got["rows"]}
    assert rows["n1"] == {"node": "n1", "steps": 2, "to_start_ms": 1.0,
                          "after_end_ms": 1.5, "ended_before_return": 1.0}
    assert rows["n2"] == {"node": "n2", "steps": 1, "to_start_ms": 7.0,
                          "after_end_ms": -2.0, "ended_before_return": 0.0}
    assert idle_gaps.dispatch_edges([], []) == {
        "dispatches": 0, "step_runs": 0, "rows": []}


def test_a_run_that_began_before_the_call_is_not_its_own():
    got = idle_gaps.dispatch_edges([(0, 5), (20, 30)], [(D, "n1", 10, 40)])
    assert got["rows"] == [{"node": "n1", "steps": 1, "to_start_ms": 1e-5,
                            "after_end_ms": 1e-5,
                            "ended_before_return": 1.0}]


def test_wrapped_around_a_cell_it_prints_both_tables_before_the_trace_goes(
        monkeypatch, capsys):
    """The wrapper's ``main`` with the chip's gate and the cell's size
    taken down to the CPU's. There is no device plane in a CPU's trace,
    so ``run.py`` ends with "no result", as it must: the two lines were
    printed before that, off the trace that ``run_cell`` then removed,
    with the program's spans in them and the probe's laid back to its
    due time."""
    import jax
    from benchmark import run as R
    from benchmark import trace_reduce

    small = {"config": {"groups": 8, "records": 128},
             "traffic": {"warmup_s": 0.5, "clients": 4, "trace_s": 2}}
    run_cell = R.run_cell
    monkeypatch.setattr(R, "require_tpu", lambda chips: jax.devices()[:chips])
    small_cell = lambda *a, **kw: run_cell(*a, scale=small, **kw)  # noqa: E731
    monkeypatch.setattr(R, "run_cell", small_cell)
    monkeypatch.setattr("ra_tpu.utils.lib.enable_compile_cache",
                        lambda: "/nonexistent")
    reduce_file = trace_reduce.reduce_file
    with pytest.raises(SystemExit, match="no operation on the device"):
        traced_cell.main(["--workload", "ra_kv_1k_x3.ycsb_a", "--seed",
                          "3000000035", "--seconds", "2"])
    # handed back
    assert trace_reduce.reduce_file is reduce_file
    assert R.run_cell is small_cell
    lines = {x["line"]: x for x in map(json.loads, (
        y for y in capsys.readouterr().out.splitlines() if y.startswith("{")))}
    assert lines["start"]["trace"] == 1
    gaps, edges = lines["idle_gaps"], lines["dispatch_edges"]
    names = {name for name, _node, _s in gaps["rows"]}
    assert {"ra/step/host_pack/step_dispatch", "ra/step/ingress_drain/route",
            "ra/egress/host_egress/follow", "ra/egress/host_egress/mirror",
            "ra/send/batch"} <= names
    assert 1.0 < gaps["window_s"] < 10.0
    assert edges["dispatches"] > 100 and edges["step_runs"] == 0
    assert edges["rows"] == []
    # what JAX itself wrote inside the dispatches (on the CPU: the jitted
    # call, its argument handling, the client's execute)
    inside = {row[0]: row for row in lines["host_events"]["rows"]}
    assert lines["host_events"]["inside"] == D
    jitted = [r for name, r in inside.items() if name.startswith("PjitFunction")]
    assert jitted and sum(r[1] for r in jitted) >= 0.9 * edges["dispatches"]
    assert all(r[1] > 0 and r[2] >= 0 and r[4] >= 0 for r in inside.values())
    assert all(r[2] > 0 for r in jitted)
    # and the program's one span inside a dispatch, the old state's
    # release (a collection may fall inside one too)
    assert {D + "/release"} <= {
        name for name in inside if name.startswith("ra/")} <= {
        D + "/release", "ra/gc/pause"}
    assert inside[D + "/release"][1] == edges["dispatches"]
    # (the release follows the jitted call)
    assert inside[D + "/release"][4] > min(r[4] for r in jitted)
    # the fourth line: the window's deltas of every wave histogram
    from ra_tpu import obs

    account = lines["wave_account"]
    assert set(account["phases"]) == {ph for ph, _help in obs.WAVE_PHASES}
    assert account["acked"] > 0 and 1.0 < account["window_s"] < 10.0
    ph = account["phases"]
    # (a snapshot reads histogram after histogram while the three step
    # threads run, so a few passes lie between two readings at either
    # edge of the window)
    drains = ph["ingress_drain"]["n"]
    assert drains > 0
    assert abs(ph["ingress_classify"]["n"] - drains) <= max(6, 0.02 * drains)
    leaves = sum(ph[p]["s"] for p in (
        "egress_follow", "egress_mirror", "egress_apply", "egress_rare"))
    assert 0.8 * ph["host_egress"]["s"] <= leaves <= ph["host_egress"]["s"]
    assert account["counters"]["routed_msgs"] > 0
    steps = account["counters"]["pipeline_steps"]
    assert 0 <= account["counters"]["sync_found_ready"] <= steps
    assert steps > 0

