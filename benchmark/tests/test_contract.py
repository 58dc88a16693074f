"""``BENCHMARK.json`` against the contract's limits, every name against
its file, and ``main()`` without a TPU."""

import os
import re
import subprocess
import sys

import pytest

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _text_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_names_units_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert all(_text_ok(w) for w in bench["command"])
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[section]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    assert len({m["name"] for m in bench["end_to_end"] + bench["per_layer"]}) \
        == len(bench["end_to_end"]) + len(bench["per_layer"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _text_ok(c["source"]) and _text_ok(c["why"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _text_ok(w["why"])
        assert w["config"] in {c["name"] for c in bench["configs"]}
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e and _text_ok(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) < 65536


def test_every_cell_reports_what_the_contract_wants(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in harness.metrics_of(bench, "end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.metrics_of(bench, "per_layer", w["name"])
        assert layer
        for m in layer:  # the metric it should move is reported in the cell
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_every_name_has_its_file(bench):
    for c in bench["configs"]:
        doc = harness.load_json("configs", c["name"])
        assert doc["source"] == c["source"]
        assert doc["reduced"] == c["reduced"]
        assert {"write", "read", "at_rest"} <= set(doc["guarantees"])
        for kind, key in (("deployments", "deployment"), ("machines", "machine"),
                          ("reference", "reference")):
            harness.load_module(kind, doc[key])
    for w in bench["workloads"]:
        harness.load_module("generators",
                            harness.load_json("traffic", w["traffic"])["generator"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        mod = harness.load_module("metrics", m["name"])
        assert mod.UNIT == m["unit"]
        if "layer" in m:
            assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])
    for dirpath, _dirs, files in os.walk(harness.HERE):
        if "__pycache__" in dirpath:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_histogram_layout_is_the_programs():
    from ra_tpu import obs

    assert harness.SUB_BITS == obs.SUB_BITS
    for b in list(range(0, 200)) + [500, 1000, obs.N_BUCKETS - 1]:
        assert harness.bucket_bounds(b) == obs.bucket_bounds(b)


def test_roofline_inputs():
    from benchmark import roofline

    small, big = roofline.step_bytes(1024, 3), roofline.step_bytes(10240, 3)
    assert big == 10 * small > 0
    assert roofline.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        roofline.peak("no such device", "hbm_bytes_per_s")


def _main(args, cwd=harness.ROOT, script=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    return subprocess.run(
        [sys.executable, script or os.path.join(harness.HERE, "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_main_refuses_without_a_tpu():
    got = _main(["--workload", "ra_kv_1k_x3.ycsb_a", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert got.returncode != 0
    assert "no result" in got.stderr
    assert not any(line.startswith('{"correct"') for line in got.stdout.splitlines())


def test_main_refuses_an_unknown_cell():
    got = _main(["--workload", "nope", "--seed", "1", "--seconds", "1"])
    assert got.returncode != 0 and "no workload" in got.stderr


def test_main_refuses_where_the_program_is_missing(tmp_path):
    """A directory that holds only BENCHMARK.json and benchmark/."""
    import shutil

    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    got = _main(["--workload", "ra_kv_1k_x3.ycsb_a", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                script=str(tmp_path / "benchmark" / "run.py"))
    assert got.returncode != 0 and "no ra_tpu/" in got.stderr
    assert not any(line.startswith('{"correct"') for line in got.stdout.splitlines())
