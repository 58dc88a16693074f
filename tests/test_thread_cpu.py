"""``scripts/thread_cpu.py`` (ISSUE 31): the threads'-CPU table that
PERF.md's "who is on the core" rests on. The reading names Python
threads by their native id and sees what one burns; the table groups
threads by role and divides by the window; wrapped around a cell at 8
groups it prints its line before the result line and changes nothing
of the run."""

import json
import os
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import thread_cpu  # noqa: E402

pytestmark = pytest.mark.skipif(
    not hasattr(time, "pthread_getcpuclockid"),
    reason="needs the threads' CPU-time clocks")


@pytest.mark.parametrize("name,role", [
    ("ra-batch-det-bench1", "detector"),
    ("ra-segment-writer", "segment writer"),
    ("ra-batch-snd-bench2", "sender"),
    ("ra-batch-bench2", "step"),
    ("ra-wal", "wal writer"),
    ("ra-batch-det-127.0.0.1:43659", "detector"),
    ("ra-batch-127.0.0.1:43659", "step"),
    ("ra-tcp-out-127.0.0.1:43659", "wire writer"),
    ("ra-tcp-in-127.0.0.1:43659", "wire reader"),
    ("ra-tcp-ping-127.0.0.1:43659", "wire liveness"),
    ("ra-tcp-accept-127.0.0.1:43659", "wire liveness"),
    ("fifo-gen-3", "generator"),
    ("ycsb-17", "generator"),
    ("tf_XLATfrtCpuClient/-123", "tf_XLATfrtCpuClient"),
    ("MainThread", "main"),
])
def test_a_threads_role_is_its_name_less_node_and_shard(name, role):
    assert thread_cpu.role_of(name) == role


def test_a_reading_sees_what_a_named_thread_burns():
    stop = threading.Event()

    def burn():
        while not stop.is_set():
            sum(range(1000))

    t = threading.Thread(target=burn, name="ra-batch-det-here", daemon=True)
    before = thread_cpu.read_threads()
    t.start()
    t0 = time.monotonic()
    time.sleep(0.5)
    after = thread_cpu.read_threads()
    seconds = time.monotonic() - t0
    stop.set()
    t.join()
    assert (t.ident, "ra-batch-det-here") in after
    assert (t.ident, "ra-batch-det-here") not in before
    got = thread_cpu.table(before, after, seconds)
    rows = {r["role"]: r for r in got["roles"]}
    assert rows["detector"]["threads"] == 1
    assert 0.2 < rows["detector"]["cores"] <= 1.1
    assert got["process_cores"] >= rows["detector"]["cores"]
    assert abs(sum(r["share"] for r in got["roles"]) - 1.0) < 1e-9
    # the readings add up to the process's own clock
    assert abs(sum(after.values()) - time.process_time()) < 0.5


def test_the_table_adds_a_roles_threads_and_orders_by_cost():
    other = thread_cpu.OTHER
    before = {(1, "ra-batch-a"): 1.0, (2, "ra-batch-b"): 2.0,
              (3, "ra-batch-det-a"): 0.5, (5, "ra-wal"): 9.0,
              (6, "Thread-9 (ended)"): 0.25, other: 4.0}
    # thread 6 ended after 0.125 s more: the process's clock keeps all
    # of it, so the second reading's rest holds its 0.375 s
    after = {(1, "ra-batch-a"): 3.0, (2, "ra-batch-b"): 3.0,
             (3, "ra-batch-det-a"): 1.0, (4, "fifo-gen-0"): 0.25,
             (5, "ra-wal"): 9.0, other: 4.0 + 1.0 + 0.375}
    got = thread_cpu.table(before, after, 10.0)
    assert [(r["role"], r["threads"], r["cores"]) for r in got["roles"]] == [
        ("step", 2, 0.3), (other, 0, 0.1125), ("detector", 1, 0.05),
        ("generator", 1, 0.025)]
    assert got["process_cores"] == 0.4875


def test_wrapped_around_a_cell_it_prints_its_line_and_changes_nothing(
        monkeypatch, capsys):
    """The wrapper's ``main`` with the chip's gate and the cell's size
    taken down to the CPU's: the line holds the detector, the step and
    the sender threads, and the result line follows it."""
    import jax
    from benchmark import harness
    from benchmark import run as R

    small = {"config": {"groups": 8, "records": 128},
             "traffic": {"warmup_s": 0.5, "clients": 4, "trace_s": 2}}
    run_cell = R.run_cell
    monkeypatch.setattr(R, "require_tpu", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(
        R, "run_cell", lambda *a, **kw: run_cell(*a, scale=small, **kw))
    monkeypatch.setattr(harness, "load_module", harness.load_module)
    monkeypatch.setattr(R, "result_line", R.result_line)
    monkeypatch.setattr("ra_tpu.utils.lib.enable_compile_cache",
                        lambda: "/nonexistent")
    assert thread_cpu.main(["--workload", "ra_bench_10k_x3.saturated",
                            "--seed", "3000000019", "--seconds", "2"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert lines[-1]["correct"] and "ops_s" in lines[-1]["metrics"]
    table = lines[-2]
    assert table["line"] == "thread_cpu" and 1.5 < table["window_s"] < 8.0
    rows = {r["role"]: r for r in table["roles"]}
    assert rows["step"]["threads"] == rows["sender"]["threads"] == 3
    assert "egress" not in rows  # the step thread realises its steps
    assert 0 < table["process_cores"] < os.cpu_count()
