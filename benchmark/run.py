#!/usr/bin/env python3
"""One cell of the benchmark, in one process, with one last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found in ``BENCHMARK.json``; its configuration, traffic mix,
deployment, generator, machine, reference and every metric's reader are
files under ``benchmark/`` found by the names there (README.md). A run:
set-up (device, compile cache, logs on a filesystem, warmed programs,
election, load, warm-up traffic), the window of ``--seconds``, the drain,
the check against the plain reference, the result line. Earlier lines
are JSON objects with a ``"line"`` key that say how the run went.
"""

import time

T_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, roofline  # noqa: E402


def say(line: str, **fields) -> None:
    """An earlier line: ``t`` is seconds since the process started."""
    print(json.dumps({"line": line, "t": round(time.monotonic() - T_START, 3),
                      **fields}, default=repr), flush=True)


def require_tpu(chips: int):
    """JAX must find ``chips`` TPU devices, or there is no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} TPU device(s); JAX found "
            f"{len(devs)} x {devs[0].platform} - no result")
    return devs[:chips]


def data_bases() -> list:
    """Where the logs may go, in order of preference: this run's
    temporary directory, then the checkout (``ra_data/`` is ignored by
    git). The deployment takes the first that is not memory-backed."""
    return [tempfile.gettempdir(), os.path.join(ROOT, "ra_data")]


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, say=say, scale: dict = None,
             devices=None) -> harness.Run:
    """Set up, measure and check one cell. ``scale`` replaces top-level
    keys of the configuration (``{"config": {...}}``) and of the traffic
    file (``{"traffic": {...}}``): the rehearsal and the tests run the
    same functions at 8 groups on the CPU."""
    import jax

    scale = scale or {}
    cell = harness.find_cell(bench, cell_name)
    config = {**harness.load_json("configs", cell["config"]),
              **scale.get("config", {})}
    traffic = {**harness.load_json("traffic", cell["traffic"]),
               **scale.get("traffic", {})}
    devices = devices or jax.devices()[:cell["chips"]]
    run = harness.Run(cell=cell, config=config, traffic=traffic, seed=seed)
    stats = harness.CompileStats()
    machine = harness.load_module("machines", config["machine"])
    deployment = harness.load_module("deployments", config["deployment"])
    generator_mod = harness.load_module("generators", traffic["generator"])
    reference = harness.load_module("reference", config["reference"])

    cluster = deployment.Cluster(
        config, lambda: machine.make(config.get("machine_args")),
        data_bases(), say)
    trace_dir = None
    try:
        gen = generator_mod.Generator(cluster, config, traffic, seed, say)
        gen.load()
        gen.start()
        time.sleep(float(traffic["warmup_s"]))
        set_up = stats.since()
        say("set_up", cache_hits=stats.cache_hits,
            cache_misses=stats.cache_misses, **set_up)

        # -- the window -------------------------------------------------------
        if trace:
            # a traced window is cut to the traffic file's trace_s: a
            # whole window of trace is too large to bring back. Every
            # per-layer metric is taken over the traced window.
            seconds = min(seconds, float(traffic["trace_s"]))
            trace_dir = tempfile.mkdtemp(prefix="ra_benchmark_trace.")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        mark = stats.mark()
        terms0 = cluster.term_sum()
        before = cluster.snapshot()
        t0_ns = time.monotonic_ns()
        run.setup_s = t0_ns / 1e9 - t_start
        time.sleep(seconds)
        t1_ns = time.monotonic_ns()
        after = cluster.snapshot()
        if trace:
            jax.profiler.stop_trace()
        in_window = stats.since(mark)
        run.window_s = (t1_ns - t0_ns) / 1e9
        run.deltas = harness.Deltas(before, after)
        run.events, wrapped = cluster.events_between(before["t"], after["t"])

        # -- the end ------------------------------------------------------------
        gen.stop(float(traffic["drain_s"]))
        run.issued = gen.issued(t0_ns, t1_ns)
        run.history = gen.history()
        for kind, op in run.history["ops"].items():
            run.ops[kind] = harness.op_window(op, t0_ns, t1_ns)
        say("drained")
        run.observed = reference.observe(cluster, run.history, config, seed)
        run.violations = reference.judge(run.history, run.observed, config)
        say("checked", violations=len(run.violations))
        term_bumps = cluster.term_sum() - terms0
        peak = [d.memory_stats() or {} for d in devices]
        run.device = {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                [m.get("peak_bytes_in_use", 0) for m in peak] or [0]),
        }
        if in_window["compilations"]:
            say("COMPILED_INSIDE_THE_WINDOW", **in_window)
        say("health",
            compilations_in_window=in_window["compilations"],
            lane_wedges=run.deltas.counter("coordinator", "lane_wedges"),
            detector_errors=run.deltas.scalar("detector_errors"),
            term_bumps_since_window_start=term_bumps,
            flight_recorder_wrapped=wrapped,
            steps=run.deltas.scalar("steps"),
            sub_steps=run.deltas.scalar("sub_steps"),
            wal={k: run.deltas.counter("wal", k) for k in (
                "fsyncs", "fsync_time_us", "batches", "entries",
                "bytes_written", "rollovers")},
            issued=run.issued)
        run.step_bytes = roofline.step_bytes(int(config["groups"]),
                                             int(config["replicas"]))
    finally:
        alive = cluster.close()
        # a node that stops makes the others arm one short-lived timer
        # thread per group it led: wait for them, so that nothing this
        # run started is left
        deadline = time.monotonic() + 10
        for t in threading.enumerate():
            if t is not threading.current_thread() and t.daemon:
                t.join(max(0.0, deadline - time.monotonic()))
        say("teardown", threads_that_outlived_stop=alive,
            **getattr(cluster, "close_seconds", {}),
            python_threads=sorted(
                f"{t.name} ({type(t).__name__})" for t in threading.enumerate()
                if t is not threading.current_thread()))
    if trace:
        from benchmark import trace_reduce

        try:
            run.trace = trace_reduce.reduce_file(
                trace_reduce.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if run.trace is not None:
            run.device["busy_s"] = run.trace["busy_s"]
            run.device["window_s"] = run.window_s
    for kind, op in sorted(run.ops.items()):
        say("latency", kind=kind, samples=op.acked, failed=op.failed,
            p50_ms=op.p_ms(50), p95_ms=op.p_ms(95), p99_ms=op.p_ms(99))
    if run.violations:
        say("INCORRECT", violations=run.violations[:20])
    if alive:
        run.violations.append(f"threads outlived stop(): {alive}")
    return run


def result_line(bench: dict, run: harness.Run, trace: bool) -> dict:
    """The last line: with ``--trace 0`` the cell's end-to-end metrics,
    with ``--trace 1`` its per-layer metrics. A reader that finds
    nothing to read returns nothing, and its metric is left out."""
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in harness.metrics_of(bench, section, run.cell["name"]):
        reader = harness.load_module("metrics", m["name"])
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": reader.UNIT}
    out = {
        "correct": not run.violations,
        "attempted": run.acked + run.failed,
        "failed": run.failed,
        "metrics": metrics,
        "device": run.device,
    }
    if trace and run.trace is not None:
        phases = []
        for name in ("ingress_drain", "host_pack", "device_step",
                     "host_egress", "aer_fanout"):
            h = run.deltas.hist("wave", name)
            if h is not None:
                # host seconds per second of the window, the three
                # coordinators' threads added: what the host was doing
                # while the device waited (the program writes no span
                # into the profiler's trace yet)
                phases.append([f"host phase {name}, s per window s",
                               h.total_ns / 1e9 / run.window_s])
        out["breakdown"] = {
            "device_ops": run.trace["device_ops"][:10],
            "idle_gaps": (phases + run.trace["idle_gaps"])[:10],
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "ra_tpu")):
        raise SystemExit("benchmark: no ra_tpu/ beside benchmark/ in this "
                         "checkout: nothing to measure - no result")
    devices = require_tpu(cell["chips"])

    import ra_tpu
    from ra_tpu.utils.lib import enable_compile_cache

    cache_dir = enable_compile_cache()
    say("start", workload=args.workload, seed=args.seed,
        program=os.path.dirname(os.path.abspath(ra_tpu.__file__)),
        seconds=args.seconds, trace=args.trace, compile_cache_dir=cache_dir,
        compile_cache_warm=os.path.isdir(cache_dir)
        and bool(os.listdir(cache_dir)))
    run = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), T_START, devices=devices)
    if args.trace and run.trace is None:
        raise SystemExit("benchmark: the trace holds no operation on the "
                         "device - no result")
    print(json.dumps(result_line(bench, run, bool(args.trace))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
