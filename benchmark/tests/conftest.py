"""The benchmark's own tests run on the CPU at 8 groups, through the
harness's own functions (``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests -q``). They are not part of the repo's tier-1 tests."""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SMALL = {"config": {"groups": 8, "records": 128},
         "traffic": {"warmup_s": 0.5, "clients": 4, "trace_s": 2}}
SEED = 3_000_000_019  # above 2**31, as the driver's are


@pytest.fixture(scope="session")
def bench():
    from benchmark import harness

    return harness.load_benchmark()


@pytest.fixture(scope="session")
def all_cells(bench):
    """``BENCHMARK.json`` plus the entries of the cells that are built and
    rehearsed but not yet proven on the chip (``data/unproven_cells.json``:
    a later PR that proves one moves its entries into ``BENCHMARK.json``)."""
    import json

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "data", "unproven_cells.json")) as f:
        extra = json.load(f)
    return {**bench, **{k: bench[k] + v for k, v in extra.items()}}


def _run(bench, cell, trace=False):
    from benchmark import run as R

    lines = []
    run = R.run_cell(bench, cell, SEED, 2.0, trace, time.monotonic(),
                     say=lambda line, **kw: lines.append((line, kw)),
                     scale=SMALL)
    run.lines = lines
    return run


@pytest.fixture(scope="session")
def kv_run(bench):
    return _run(bench, "ra_kv_1k_x3.ycsb_a")


@pytest.fixture(scope="session")
def fleet_run(all_cells):
    return _run(all_cells, "ra_bench_10k_x3.saturated")


@pytest.fixture(scope="session")
def fleet_traced_run(all_cells):
    return _run(all_cells, "ra_bench_10k_x3.saturated", trace=True)
