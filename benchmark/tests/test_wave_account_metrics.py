"""The per-layer metrics that read the accounts of ISSUE 35 (the leaves
of ``ingress_drain`` and ``host_egress``, the sender's queue, the
interpreter lock's wait): the kv cell at 8 groups, traced, holds all
nine with their declared units, and a program without the accounts
reads as nothing."""

import dataclasses
import time

import pytest

from benchmark import harness
from benchmark import run as R

# (conftest.py's; not imported from it, so that this file also collects
# beside the repo's own tests/conftest.py)
SMALL = {"config": {"groups": 8, "records": 128},
         "traffic": {"warmup_s": 0.5, "clients": 4, "trace_s": 2}}
SEED = 3_000_000_035

NEW = {"gil_wait_p50_ms", "gil_wait_p95_ms", "ingress_classify_ms_per_kop",
       "ingress_route_ms_per_kop", "egress_follow_ms_per_kop",
       "egress_mirror_ms_per_kop", "egress_rare_ms_per_kop",
       "send_queue_p50_ms", "wave_unaccounted_pct"}
NEW_HISTS = ("ingress_classify", "ingress_route", "ingest_fanout",
             "egress_follow", "egress_mirror", "egress_rare", "send_queue",
             "gil_wait")


@pytest.fixture(scope="module")
def kv_traced(bench):
    run = R.run_cell(bench, "ra_kv_1k_x3.ycsb_a", SEED, 2.0, True,
                     time.monotonic(), say=lambda line, **kw: None,
                     scale=SMALL)
    return run, R.result_line(bench, run, True)


def test_traced_kv_line_holds_the_nine_metrics(bench, kv_traced):
    run, out = kv_traced
    assert out["correct"] is True, run.violations
    got = out["metrics"]
    assert NEW <= set(got), NEW - set(got)
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-9:]] == [
        "gil_wait_p50_ms", "gil_wait_p95_ms", "ingress_classify_ms_per_kop",
        "ingress_route_ms_per_kop", "egress_follow_ms_per_kop",
        "egress_mirror_ms_per_kop", "egress_rare_ms_per_kop",
        "send_queue_p50_ms", "wave_unaccounted_pct"]
    for name in NEW:
        assert "workloads" not in declared[name]
        assert declared[name]["source"] == "program_span"
        assert got[name]["unit"] == declared[name]["unit"]
        assert isinstance(got[name]["value"], float)
        assert got[name]["value"] >= 0
    for name in NEW - {"gil_wait_p50_ms", "wave_unaccounted_pct"}:
        assert got[name]["value"] > 0, name  # (a quiet lock's median may be 0)
    assert 0 <= got["wave_unaccounted_pct"]["value"] <= 100
    assert got["gil_wait_p50_ms"]["value"] <= got["gil_wait_p95_ms"]["value"]
    # the probe sleeps 20 ms: some 50 samples a second of window, all on
    # one coordinator
    n = run.deltas.hist("wave", "gil_wait").n
    assert 0.5 * 50 * run.window_s <= n <= 50 * run.window_s + 1
    # a leaf is inside its phase, and the named leaves cover it
    for phase, leaves in (
            ("ingress_drain", ("ingress_classify", "step_lock_wait",
                               "ingress_route", "ingest_append",
                               "ingest_fanout")),
            ("host_egress", ("egress_follow", "egress_mirror",
                             "egress_apply", "egress_rare"))):
        whole = run.deltas.hist("wave", phase).total_ns
        parts = [run.deltas.hist("wave", p).total_ns for p in leaves]
        assert all(0 <= p <= whole for p in parts), (phase, parts, whole)
        assert 0.8 * whole <= sum(parts) <= 1.001 * whole, (phase, parts)
    # every batch the sender drained waited in its queue once (the
    # snapshots are taken while the three senders run: a drain may lie
    # between its samples and its count at either end)
    batches = run.deltas.counter("coordinator", "egress_thread_batches")
    assert batches > 0
    assert abs(run.deltas.hist("wave", "send_queue").n - batches) <= 12


def test_a_program_without_the_accounts_reads_as_nothing(kv_traced):
    """The parent commit's snapshot has none of the new histograms:
    every one of the nine readers returns None, none raises."""
    run, _out = kv_traced

    def strip(snap):
        return {**snap, "wave": {k: v for k, v in snap["wave"].items()
                                 if k not in NEW_HISTS}}

    old = dataclasses.replace(run, deltas=harness.Deltas(
        strip(run.deltas.before), strip(run.deltas.after)))
    for name in sorted(NEW):
        assert harness.load_module("metrics", name).read(old) is None, name
    none = dataclasses.replace(run, deltas=None)
    for name in sorted(NEW):
        assert harness.load_module("metrics", name).read(none) is None, name
