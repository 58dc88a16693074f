"""The per-layer metrics that read the accounts of ISSUE 24 (wave
sub-phases, thread-CPU counters, read accounts, the two commit stages
nothing read): the kv cell at 8 groups, traced, holds all eleven, and
they stand to the metrics beside them as their files say."""

import time

import pytest

from benchmark import harness
from benchmark import run as R

# (conftest.py's; not imported from it, so that this file also collects
# beside the repo's own tests/conftest.py)
SMALL = {"config": {"groups": 8, "records": 128},
         "traffic": {"warmup_s": 0.5, "clients": 4, "trace_s": 2}}
SEED = 3_000_000_019

NEW = {"host_cpu_ms_per_kop", "lock_wait_ms_per_kop", "mailbox_build_p50_ms",
       "step_dispatch_p50_ms", "egress_sync_p50_ms", "ticket_queue_p50_ms",
       "read_register_ms", "read_quorum_ms", "read_fetch_ms",
       "submit_append_p50_ms", "durable_commit_p50_ms"}


@pytest.fixture(scope="module")
def kv_traced(bench):
    run = R.run_cell(bench, "ra_kv_1k_x3.ycsb_a", SEED, 2.0, True,
                     time.monotonic(), say=lambda line, **kw: None,
                     scale=SMALL)
    return run, R.result_line(bench, run, True)


def test_traced_kv_line_holds_the_eleven_metrics(bench, kv_traced):
    run, out = kv_traced
    assert out["correct"] is True, run.violations
    got = out["metrics"]
    assert NEW <= set(got), NEW - set(got)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert got[name]["unit"] == declared[name]["unit"]
        assert isinstance(got[name]["value"], float) and got[name]["value"] > 0
    # work is part of the wall time it is done in
    assert got["host_cpu_ms_per_kop"]["value"] <= \
        got["host_ms_per_kop"]["value"]
    # device_step is its three sub-phases
    parts = sum(run.deltas.hist("wave", p).total_ns for p in
                ("ticket_queue", "egress_sync", "egress_lock_wait"))
    whole = run.deltas.hist("wave", "device_step").total_ns
    assert abs(parts - whole) <= 0.05 * whole
    # a read's three legs end before its caller has the value
    legs = sum(got[m]["value"] for m in
               ("read_register_ms", "read_quorum_ms", "read_fetch_ms"))
    mean_ms = float(run.ops["read"].lat_ns.mean()) / 1e6
    assert 0.5 * mean_ms <= legs <= 1.05 * mean_ms, (legs, mean_ms)


def test_a_program_without_the_accounts_reads_as_nothing(kv_traced):
    """The parent commit's snapshot has none of the new histograms and
    counters: every reader that needs one returns None, none raises."""
    run, _out = kv_traced
    new_hists = ("step_lock_wait", "scatter_dispatch", "mailbox_build",
                 "step_dispatch", "ticket_queue", "egress_sync",
                 "egress_lock_wait")
    new_counters = ("cpu_ns_", "read_register", "read_quorum_ns",
                    "read_quorum_rounds", "state_quer")

    def strip(snap):
        return {**snap,
                "wave": {k: v for k, v in snap["wave"].items()
                         if k not in new_hists},
                "coordinator": {k: v for k, v in snap["coordinator"].items()
                                if not k.startswith(new_counters)}}

    import dataclasses

    old = dataclasses.replace(run, deltas=harness.Deltas(
        strip(run.deltas.before), strip(run.deltas.after)))
    for name in sorted(NEW - {"submit_append_p50_ms",
                              "durable_commit_p50_ms"}):
        assert harness.load_module("metrics", name).read(old) is None, name
