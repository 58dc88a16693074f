"""``wal_cpu_us_per_entry`` and ``wal_runs_in_place_pct`` (ISSUE 29):
both cells at 8 groups hold them in their per-layer line, they are the
window's WAL counters with the three nodes' WALs added, and a program
without the counters (the parent commit's) reads as nothing."""

import dataclasses

import pytest

from benchmark import harness
from benchmark import run as R

CPU = "wal_cpu_us_per_entry"
IN_PLACE = "wal_runs_in_place_pct"


@pytest.mark.parametrize("cell", ["kv_run", "fleet_run"])
def test_both_cells_report_them_from_the_counters(bench, cell, request):
    run = request.getfixturevalue(cell)
    declared = {m["name"]: m for m in
                harness.metrics_of(bench, "per_layer", run.cell["name"])}
    line = R.result_line(bench, run, True)["metrics"]
    d = run.deltas
    for name, unit in ((CPU, "us"), (IN_PLACE, "%")):
        assert declared[name]["moves"] == "ops_s"
        assert declared[name]["layer"] == "durability"
        assert "workloads" not in declared[name]
        assert line[name]["unit"] == declared[name]["unit"] == unit
    entries, runs = d.counter("wal", "entries"), d.counter("wal", "runs")
    assert entries > 0 and 0 < runs <= entries
    assert line[CPU]["value"] == d.counter("wal", "writer_cpu_ns") / 1e3 / entries
    assert line[IN_PLACE]["value"] == 100.0 * d.counter("wal", "runs_in_place") / runs
    # a healthy window: every append continued its log, none rewound
    assert line[IN_PLACE]["value"] == 100.0
    # the writers were on a core for some of the window and not all of it
    assert 0 < d.counter("wal", "writer_cpu_ns") < 3 * run.window_s * 1e9


def test_the_three_wals_are_added_up(fleet_run):
    """A window in which each node's WAL booked its own share: the
    readers see the sums, as ``Cluster.snapshot()`` makes them."""
    per_wal = [
        {"writer_cpu_ns": 3_000_000, "entries": 100, "runs": 100, "runs_in_place": 100},
        {"writer_cpu_ns": 1_000_000, "entries": 60, "runs": 30, "runs_in_place": 15},
        {"writer_cpu_ns": 0, "entries": 40, "runs": 10, "runs_in_place": 5},
    ]
    before = fleet_run.deltas.before
    after = {**before, "wal": dict(before["wal"])}
    for wal in per_wal:
        for k, v in wal.items():
            after["wal"][k] = after["wal"].get(k, 0) + v
    run = dataclasses.replace(fleet_run, deltas=harness.Deltas(before, after))
    assert harness.load_module("metrics", CPU).read(run) == 4_000_000 / 1e3 / 200
    assert harness.load_module("metrics", IN_PLACE).read(run) == 100.0 * 120 / 140


@pytest.mark.parametrize("name,fields", [
    (CPU, ("writer_cpu_ns",)),
    (IN_PLACE, ("runs", "runs_in_place")),
])
def test_a_program_without_the_counters_reads_as_nothing(fleet_run, name, fields):
    reader = harness.load_module("metrics", name)

    def without(snap):
        return {**snap, "wal": {k: v for k, v in snap["wal"].items()
                                if k not in fields}}

    old = dataclasses.replace(fleet_run, deltas=harness.Deltas(
        without(fleet_run.deltas.before), without(fleet_run.deltas.after)))
    assert reader.read(old) is None
    # nothing written in the window: no entry to divide by
    still = dataclasses.replace(fleet_run, deltas=harness.Deltas(
        fleet_run.deltas.after, fleet_run.deltas.after))
    assert reader.read(still) is None
    empty = harness.Run(cell=fleet_run.cell, config=fleet_run.config,
                        traffic=fleet_run.traffic, seed=0)
    assert reader.read(empty) is None
