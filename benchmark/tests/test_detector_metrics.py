"""``detect_cpu_ms_per_s`` and ``detect_rows_per_pass`` (ISSUE 31): both
cells at 8 groups hold them in their per-layer line, they are the
window's detector counters with the three nodes' added, and a program
without the counters (the parent commit's) reads as nothing."""

import dataclasses

import pytest

from benchmark import harness
from benchmark import run as R

CPU = "detect_cpu_ms_per_s"
ROWS = "detect_rows_per_pass"


@pytest.mark.parametrize("cell", ["kv_run", "fleet_run"])
def test_both_cells_report_them_from_the_counters(bench, cell, request):
    run = request.getfixturevalue(cell)
    declared = {m["name"]: m for m in
                harness.metrics_of(bench, "per_layer", run.cell["name"])}
    line = R.result_line(bench, run, True)["metrics"]
    d = run.deltas
    for name, unit in ((CPU, "ms/s"), (ROWS, "rows/pass")):
        assert declared[name]["moves"] == "ops_s"
        assert declared[name]["layer"] == "failure detection"
        assert "workloads" not in declared[name]
        assert line[name]["unit"] == declared[name]["unit"] == unit
    passes = d.counter("coordinator", "detector_passes")
    # three detector threads at detector_poll_s = 0.1 s
    assert 3 * 5 * d.seconds <= passes <= 3 * 10 * d.seconds + 3
    assert line[CPU]["value"] == \
        d.counter("coordinator", "detector_cpu_ns") / 1e6 / d.seconds
    assert line[ROWS]["value"] == \
        d.counter("coordinator", "detector_rows_walked") / passes
    # on a core for less than the three threads' window; a healthy
    # window's polls walk nothing, its ticks at most the groups there are
    assert 0 <= line[CPU]["value"] < 3000
    assert 0 <= line[ROWS]["value"] <= 2 * 8 / 5
    assert d.scalar("detector_errors") == 0


def test_the_three_nodes_are_added_up(fleet_run):
    """A window in which each node's detector booked its own share: the
    readers see the sums, as ``Cluster.snapshot()`` makes them."""
    per_node = [
        {"detector_cpu_ns": 30_000_000, "detector_passes": 100, "detector_rows_walked": 700},
        {"detector_cpu_ns": 10_000_000, "detector_passes": 60, "detector_rows_walked": 20},
        {"detector_cpu_ns": 0, "detector_passes": 40, "detector_rows_walked": 0},
    ]
    before = fleet_run.deltas.before
    after = {**before, "t": before["t"] + 4.0,
             "coordinator": dict(before["coordinator"])}
    for node in per_node:
        for k, v in node.items():
            after["coordinator"][k] = after["coordinator"].get(k, 0) + v
    run = dataclasses.replace(fleet_run, deltas=harness.Deltas(before, after))
    assert harness.load_module("metrics", CPU).read(run) == 40.0 / 4.0
    assert harness.load_module("metrics", ROWS).read(run) == 720 / 200


@pytest.mark.parametrize("name,fields", [
    (CPU, ("detector_cpu_ns",)),
    (ROWS, ("detector_rows_walked", "detector_passes")),
])
def test_a_program_without_the_counters_reads_as_nothing(fleet_run, name, fields):
    reader = harness.load_module("metrics", name)

    def without(snap):
        return {**snap, "coordinator": {
            k: v for k, v in snap["coordinator"].items() if k not in fields}}

    old = dataclasses.replace(fleet_run, deltas=harness.Deltas(
        without(fleet_run.deltas.before), without(fleet_run.deltas.after)))
    assert reader.read(old) is None
    # no time between the snapshots, no pass in it: nothing to divide by
    still = dataclasses.replace(fleet_run, deltas=harness.Deltas(
        fleet_run.deltas.after, fleet_run.deltas.after))
    assert reader.read(still) is None
    empty = harness.Run(cell=fleet_run.cell, config=fleet_run.config,
                        traffic=fleet_run.traffic, seed=0)
    assert reader.read(empty) is None
