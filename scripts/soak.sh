#!/usr/bin/env bash
# Combined-fault soak: the slow job that runs AFTER the tier-1 gate,
# next to scripts/flake_gate.sh.
#
# Phase 1 runs the pinned soak grid (tests/test_soak.py -m soak:
# 3 seeds x 2 backends x 2 workloads, every nemesis dimension armed at
# once). Phase 2 is the flake gate over FRESH seeds: N extra combined
# runs per backend straight through the harness, so a liveness or
# conservation bug outside the pinned seeds still gets caught. Any
# failure prints the repro bundle (seed, nemesis schedule, flight
# recorder, health anomalies) on stderr — rerun a single seed with:
#
#   python -m ra_tpu.kv_harness --combined --seed N [--backend tpu_batch]
#
# Usage: scripts/soak.sh [N_EXTRA_SEEDS] [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

N="${1:-5}"
shift || true

echo "== soak: pinned grid (3 seeds x 2 backends x 2 workloads) =="
python -m pytest tests/test_soak.py -q -m soak \
    -p no:cacheprovider -p no:randomly "$@"

echo "== soak: flake gate over $N fresh seeds per backend =="
# the batch backend alternates the native hot-loop runtime on/off per
# seed (docs/INTERNALS.md §18): half the grid proves the disk-fault/
# torn-write failpoints bite through the native fallback seam, half
# proves the pure-Python plane (the actor backend ignores --native)
for seed in $(seq 100 $((99 + N))); do
    for backend in per_group_actor tpu_batch; do
        for workload in kv fifo; do
            native=auto
            [ "$backend" = tpu_batch ] && [ $((seed % 2)) -eq 1 ] \
                && native=off
            echo "-- seed=$seed backend=$backend workload=$workload" \
                 "native=$native"
            python -m ra_tpu.kv_harness --combined --seed "$seed" \
                --ops 200 --backend "$backend" --workload "$workload" \
                --native "$native" \
                >/tmp/soak_run.log 2>&1 \
                || { echo "soak FAILED: seed=$seed backend=$backend" \
                          "workload=$workload native=$native"; \
                     tail -60 /tmp/soak_run.log; exit 1; }
        done
    done
done

echo "== soak: lease read dimension ($N fresh seeds per backend) =="
# linearizable-read dimension (docs/INTERNALS.md §20): leases on,
# one-way partitions, depositions racing the consistent-read stream
for seed in $(seq 200 $((199 + N))); do
    for backend in per_group_actor tpu_batch; do
        echo "-- seed=$seed backend=$backend lease=on"
        python -m ra_tpu.kv_harness --lease --seed "$seed" \
            --ops 100 --backend "$backend" \
            >/tmp/soak_run.log 2>&1 \
            || { echo "soak FAILED: seed=$seed backend=$backend lease=on"; \
                 tail -60 /tmp/soak_run.log; exit 1; }
    done
done

echo "== soak: disk-pressure dimension ($N fresh seeds per backend) =="
# storage-pressure survival plane (docs/INTERNALS.md §21): ENOSPC/
# EDQUOT storms and fsync-latency brownouts layered on the disk-fault
# mix — space-class failures must degrade in place (typed RA_NOSPACE
# rejects, reclaim, probe-loop auto-resume), never restart, and never
# lose an acked write. Partitions/membership off: this lane isolates
# the storage plane so a failure bisects to it directly.
for seed in $(seq 300 $((299 + N))); do
    for backend in per_group_actor tpu_batch; do
        echo "-- seed=$seed backend=$backend disk-pressure"
        python -m ra_tpu.kv_harness --seed "$seed" --ops 120 \
            --backend "$backend" --disk-faults --disk-full --slow-disk \
            --no-partitions --no-membership \
            >/tmp/soak_run.log 2>&1 \
            || { echo "soak FAILED: seed=$seed backend=$backend" \
                      "disk-pressure"; \
                 tail -60 /tmp/soak_run.log; exit 1; }
    done
done

echo "soak: PASS"
