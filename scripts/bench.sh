#!/usr/bin/env bash
# Benchmark trajectory recorder: run the lifted-restriction suite and
# the PlanServe load test, and write the merged BENCH_<pr>.json (per-leg
# wall time + backend + serving throughput) at the repo root, so every
# PR leaves a perf baseline the next one can regress against.
#
#   scripts/bench.sh [pr-number]
#
# Without an argument the PR number is inferred as one past the number
# of PR entries already recorded in CHANGES.md (i.e. "this PR").
# Off-TPU the legs run in interpret mode on bounded sizes; on a TPU
# they compile for the chip.
set -euo pipefail
cd "$(dirname "$0")/.."

PR="${1:-$(($(grep -c '^- PR' CHANGES.md) + 1))}"
FLAGS=(--json)
LIFTED="$(mktemp)"
SERVE="$(mktemp)"
trap 'rm -f "$LIFTED" "$SERVE"' EXIT
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.lifted "${FLAGS[@]}" > "$LIFTED"
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m benchmarks.serve --json > "$SERVE"
python - "$LIFTED" "$SERVE" > "BENCH_${PR}.json" <<'PY'
import json
import sys

rec = json.load(open(sys.argv[1]))
rec["serving"] = json.load(open(sys.argv[2]))["serving"]
json.dump(rec, sys.stdout, indent=1)
print()
PY
echo "wrote BENCH_${PR}.json"
