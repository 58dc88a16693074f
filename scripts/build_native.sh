#!/usr/bin/env bash
# Build the native acceleration libraries (docs/INTERNALS.md §18) and
# verify every entry point loads:
#
#   ra_tpu/native/wal_native.<digest>.so  - WAL batch frame + write + fsync
#   ra_tpu/native/rt_native.<digest>.so   - hot-loop runtime: drain-classify,
#                                           mailbox pack scatter
#
# The Python loader builds these on first use, named by the digest of
# their source, and this script builds through it; CI/tier-1 runs
# this FIRST so a broken build fails the job loudly instead of every
# test silently taking the Python fallback. Exits nonzero when a
# compiler is present but the build or load fails.
set -euo pipefail
cd "$(dirname "$0")/.."

if ! command -v g++ >/dev/null; then
    echo "build_native: no g++ on PATH - native paths will use the" \
         "Python fallback" >&2
    exit 0
fi

python - <<'EOF'
import sys
from ra_tpu import native

eps = native.entry_points()
print("native entry points:", eps)
if not all(eps.values()):
    print("build_native: g++ is present but entry points failed to "
          "build or load", file=sys.stderr)
    sys.exit(1)
EOF
echo "build_native: OK"
