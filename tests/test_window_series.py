"""``scripts/window_series.py`` (ISSUE 33): the reading a second that
PERF.md's "what makes a window unsteady" rests on. The series is the
deltas between readings with the operations that ended between them;
wrapped around the wired cell at 8 groups it prints its line before the
result line and changes nothing of the run."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import window_series  # noqa: E402


def _reading(t_s, steps, **over):
    row = {"t_ns": int(t_s * 1e9), "steps": steps}
    for k in window_series.COUNTERS + window_series.WAL + window_series.PHASES:
        row[k] = 0
    row.update(over)
    return row


def test_the_series_is_deltas_with_the_operations_that_ended_between():
    rows = [_reading(10.0, [5, 5, 5]),
            _reading(11.0, [12, 13, 11], gc_pause_ns=75_000_000,
                     gc_collections=1, fsyncs=40, host_pack=1_400_000_000),
            _reading(12.5, [15, 14, 13], gc_pause_ns=75_000_000,
                     gc_collections=1, fsyncs=55, host_pack=2_000_000_000)]
    done = [int(t * 1e9) for t in (9.9, 10.0, 10.5, 10.999, 11.0, 12.4, 12.5)]
    got = window_series.series(rows, done)
    assert [r["s"] for r in got] == [1.0, 1.5]
    assert [r["ops"] for r in got] == [3, 2]  # [t0, t1): 9.9 and 12.5 outside
    assert [r["steps"] for r in got] == [[7, 8, 6], [3, 1, 2]]
    assert [r["gc_pause_ms"] for r in got] == [75.0, 0.0]
    assert [r["gc_collections"] for r in got] == [1, 0]
    assert [r["fsyncs"] for r in got] == [40, 15]
    assert [r["host_pack_ms"] for r in got] == [1400.0, 600.0]
    assert "gc_pause_ns" not in got[0] and "host_pack" not in got[0]


def test_wrapped_around_the_wired_cell_it_prints_its_line_and_changes_nothing(
        monkeypatch, capsys):
    import jax
    from benchmark import harness
    from benchmark import run as R

    small = {"config": {"groups": 8},
             "traffic": {"warmup_s": 0.5, "trace_s": 2, "hot_queues": 4}}
    run_cell = R.run_cell
    monkeypatch.setattr(R, "require_tpu", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(
        R, "run_cell", lambda *a, **kw: run_cell(*a, scale=small, **kw))
    monkeypatch.setattr(harness, "load_module", harness.load_module)
    monkeypatch.setattr(R, "result_line", R.result_line)
    monkeypatch.setattr("ra_tpu.utils.lib.enable_compile_cache",
                        lambda: "/nonexistent")
    assert window_series.main(["--workload", "ra_fifo_10k_x3_wired.hot_queues",
                               "--seed", "3000000019", "--seconds", "3"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert lines[-1]["correct"] and "ops_s" in lines[-1]["metrics"]
    series = lines[-2]
    assert series["line"] == "window_series"
    rows = series["rows"]
    # a reading at each end and one a second between them
    assert 3 <= len(rows) <= 6 and 2.5 < sum(r["s"] for r in rows) < 8.0
    assert all(len(r["steps"]) == 3 for r in rows)
    assert sum(r["ops"] for r in rows) == lines[-1]["attempted"]
    assert sum(r["wire_frames_out"] for r in rows) > 0
    assert sum(r["wire_dropped"] for r in rows) == 0
