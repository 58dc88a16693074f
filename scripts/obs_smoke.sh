#!/usr/bin/env bash
# Observability smoke gate: serves a short burst on a small started,
# WAL-backed cluster, scrapes the Prometheus exposition +
# system_overview surface, fails on missing or NaN metrics. Sits next
# to scripts/flake_gate.sh in CI: flake_gate protects liveness,
# obs_smoke protects the instruments we debug liveness WITH
# (docs/INTERNALS.md §13).
#
# Usage: scripts/obs_smoke.sh [--groups N] [--cmds N]
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

echo "== obs smoke: served burst + exposition scrape =="
python scripts/obs_smoke.py "$@"
echo "obs smoke: PASS"
