#!/usr/bin/env bash
# Minimal CI gate: koordlint first (fast, stdlib-only — fails in
# seconds on a hygiene regression), then the tier-1 pytest battery from
# ROADMAP.md on the CPU backend. Exit code is the first failing stage's.
set -euo pipefail
cd "$(dirname "$0")/.."

# under GitHub Actions, findings come out as ::error workflow commands
# so the runner turns them into inline PR annotations at the flagged
# line; on a desk the human text format stays
LINT_FORMAT=${GITHUB_ACTIONS:+--format github}

echo "=== koordlint (python -m tools.lint) ==="
python -m tools.lint ${LINT_FORMAT}

echo "=== koordlint self-lint (--root tools) ==="
# the analyzers obey their own rules: the tools tree is linted as a
# standalone root (same empty-baseline bar as the repo scan)
python -m tools.lint --root tools ${LINT_FORMAT}

echo "=== koordshape Tier B (device-free eval_shape gate) ==="
JAX_PLATFORMS=cpu python tools/shapecheck.py

echo "=== koordshape mutation smoke (gate liveness) ==="
# flip one dtype in a TEMP COPY of ops/feasibility.py and assert the
# gate fails on it — a shapecheck that can't catch the seeded mutation
# is a green-but-dead gate
JAX_PLATFORMS=cpu python tools/shapecheck.py --self-test-mutation

echo "=== koordpad Tier B (differential pad-inertness gate) ==="
# every contract runs concretely twice (zero-pad vs declared-fill pads):
# real regions must be bit-identical, output pad bands must hold their
# declared fills (tools/padcheck.py)
JAX_PLATFORMS=cpu python tools/padcheck.py

echo "=== koordpad dual-tier mutation smoke (gate liveness) ==="
# one seeded pad leak per tier in a TEMP COPY: a dropped schedulable
# conjunction only the differential run can see (padcheck must FAIL),
# and a dropped -1-index clamp only the static pass can see (the
# pad-soundness lint must flag PS002)
JAX_PLATFORMS=cpu python tools/padcheck.py --self-test-mutation

echo "=== koordrace Tier B (deterministic interleaving gate) ==="
# the guarded concurrent classes (store ingest/update/read, journal
# append/prune/reload, tracer span storm, metrics observe/export) run
# under a seeded deterministic scheduler across rr + random +
# bounded-preemption schedules; same seed must replay the same
# schedule (tools/racecheck.py)
JAX_PLATFORMS=cpu python tools/racecheck.py

echo "=== koordrace dual-tier mutation smoke (gate liveness) ==="
# one seeded lock drop per tier in a TEMP COPY: ingest's version guard
# on a fresh lock only the interleaving explorer can see (racecheck
# must FAIL, race-guard lint must pass), and a cold-path MetricCache
# unlock only the guarded-by contracts can see (GB001 must fire,
# racecheck must pass) — complementarity, not redundancy
JAX_PLATFORMS=cpu python tools/racecheck.py --self-test-mutation

echo "=== full-gate cascade smoke (2k pods x 200 nodes, CPU) ==="
# correctness + straggler-count assertions, not wall-clock: cascade
# on/off conformance, device-tail drain, single-stats-readback
# consistency (tools/cascade_smoke.py) — the cascade path runs on
# every push even when no test touches it
JAX_PLATFORMS=cpu python tools/cascade_smoke.py

echo "=== chaos smoke (fault-injection matrix, CPU) ==="
# every fault class in koordinator_tpu/testing/faults.py: detected
# (guard word bit / FailureClass / typed delta reason), quarantined,
# service completes the cycle, and clean-row placements bit-identical
# to the no-fault oracle (tools/chaos_smoke.py) — correctness only,
# never wall-clock
JAX_PLATFORMS=cpu python tools/chaos_smoke.py

echo "=== crash smoke (kill-injected recovery matrix, CPU) ==="
# every named crash point in koordinator_tpu/testing/faults.py
# CRASH_POINTS: a child service is SIGKILLed at the point mid-batch,
# the restarted service recovers via checkpoint restore + commit-
# journal replay, and final placements must be BIT-IDENTICAL to the
# no-crash oracle — exactly one journal record per (epoch, chunk),
# torn tails surfaced with a typed reason (tools/crash_smoke.py)
JAX_PLATFORMS=cpu python tools/crash_smoke.py

echo "=== koordtrace smoke (observability contract, CPU) ==="
# a journaled, traced service on a small full-gate workload: every
# committed cycle carries the full host span skeleton under one cycle
# id, the Chrome dump is valid trace-event JSON (Perfetto-loadable),
# fault-injected cycles carry quarantine/retry/backoff/ladder records,
# every span name resolves against obs/phases.py, and journal_append
# span attrs join to the commit journal (tools/trace_smoke.py)
JAX_PLATFORMS=cpu python tools/trace_smoke.py

echo "=== koordcost drift gate (static cost/memory baseline, CPU) ==="
# every contracted kernel + the flagship cascade forms lowered and
# priced (flops, bytes accessed, donation-aware static peak, per-phase
# attribution, packed-representation bytes) and compared against
# perf/COST_BASELINE.json with loud provenance — any move beyond
# tolerance without a restamp fails with COST DRIFT (tools/costcheck.py)
JAX_PLATFORMS=cpu python tools/costcheck.py

echo "=== koordcost mutation smoke (gate liveness + complementarity) ==="
# a seeded bf16->f32 upcast in the packable path in a TEMP COPY: the
# cost gate must FAIL on the bytes drift while koordlint and shapecheck
# — hygiene and shapes, not bytes — must PASS the mutated tree
JAX_PLATFORMS=cpu python tools/costcheck.py --self-test-mutation

echo "=== warm-cache smoke (compile-cache warm-start gate, CPU) ==="
# the flagship cycle runs in three REAL child processes against ONE
# compile-cache dir: cold (compiles, populates manifest), warm (ZERO
# XLA compilations, placements bit-identical), restart recovery
# (compiled_programs == 0, replay bit-identical) — the cross-process
# warm-start contract (tools/warm_cache_smoke.py); same-host only by
# construction, the dir lives and dies inside the stage
JAX_PLATFORMS=cpu python tools/warm_cache_smoke.py

echo "=== tier-1 tests (JAX_PLATFORMS=cpu) ==="
set -o pipefail
rm -f /tmp/_t1.log
# `|| rc=$?` keeps set -e from aborting before the DOTS_PASSED
# diagnostic — the pass count matters MOST on the failure path
rc=0
timeout -k 10 1500 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log || rc=$?
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)"
exit "$rc"
