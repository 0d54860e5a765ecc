#!/usr/bin/env bash
# CI entry point: tier-1 test suite, then the benchmark harness in smoke
# mode (snapshot + nodeprog + writepath + coordination + recovery +
# serving — coordination covers the tau sweep's aggressive-concurrency
# corner, the historical oracle CycleError; nodeprog's smoke includes
# the ragged get_edges/clustering section; serving asserts the windowed
# read-admission equivalence bit, exercises the shed/retry sweep at
# smoke sizes, and exports a causal trace from its obs section), then
# the trace gate (Chrome trace-event schema on the exported smoke
# trace, plus a generated traced run asserting critical-path stage
# sums tile each request's e2e latency within 1% and the trace-driven
# protocol invariants hold), then the replication hardening stages
# (the replica test battery under three distinct PYTHONHASHSEED values
# — bit-identity must not hinge on dict iteration order — and a forced
# two-pod replication smoke), then the docs consistency check
# (README/docs exist, links + WeaverConfig/Counters/module references
# resolve, README results table matches the checked-in BENCH files).
# Exits non-zero on ANY failure (pytest failure, benchmark exception,
# equivalence-bit regression, or docs drift — benchmarks/run.py already
# exits 1 if any module raises).
#
# Usage: scripts/ci.sh            # from anywhere; cd's to the repo root
# Deps:  requirements-dev.txt (pinned); jax/numpy come with the image.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "=== tier-1 pytest ==="
python -m pytest -x -q

echo "=== multi-device (8 forced host devices) ==="
# re-runs the tests that self-skip under a single device: the
# device-sharded column-plane equivalence
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q tests/test_device_shard.py

echo "=== benchmarks (smoke) ==="
python -m benchmarks.run --smoke

echo "=== trace check ==="
# schema-validate the trace the serving smoke run exported, then run
# the generated-trace gate (attribution tiling + invariant checkers)
python scripts/check_trace.py trace_serving_smoke.json
python scripts/check_trace.py
rm -f trace_serving_smoke.json trace_smoke.json

echo "=== replication tests x3 hash seeds ==="
# replica reads must be bit-identical to the primary regardless of
# Python's per-process hash randomization (dict/set iteration order)
for hs in 0 1 2; do
    echo "--- PYTHONHASHSEED=$hs ---"
    PYTHONHASHSEED=$hs python -m pytest -q tests/test_replica.py
done

echo "=== forced multi-pod smoke ==="
# two-pod deployment: in-pod replica routing must beat cross-pod reads
REPRO_FORCE_PODS=1 REPRO_BENCH_SMOKE=1 python -m benchmarks.replication

echo "=== docs check ==="
python scripts/check_docs.py

echo "=== CI OK ==="
