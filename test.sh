#!/usr/bin/env bash
# Canonical tier-1 test entry point (see ROADMAP.md).
#
# Pins the bits of environment the suite assumes:
#   * PYTHONPATH includes src/ (the repo is run from source, not installed);
#   * JAX_PLATFORMS=cpu unless set: the suite runs on XLA's CPU backend (a
#     TPU host's chip belongs to chip_smoke.py and the benchmarks);
#   * XLA_FLAGS requests 8 host platform devices so multi-device semantics
#     are exercisable on CPU (SNIPPETS.md test.sh idiom).  test_distributed
#     re-pins its own count inside subprocesses either way, and an existing
#     XLA_FLAGS is respected.
#
# Usage: bash test.sh [pytest args...]   e.g. bash test.sh tests/test_sharding.py -k moe
#        bash test.sh --fast             tier-1 minus the slow spawn-subprocess
#                                        tests (pytest -m "not slow") — the CI
#                                        quick lane.  Includes the in-process
#                                        campaign E2E suite (tests/test_campaign.py
#                                        carries no slow marks).
#        bash test.sh --cov              the --fast lane under pytest-cov with
#                                        the ratcheting coverage floor (the CI
#                                        coverage lane; needs pytest-cov)
#        bash test.sh --bench-smoke      quick perf-harness sanity: runs
#                                        benchmarks/optimizer_throughput.py --quick,
#                                        benchmarks/configstore_roundtrip.py --quick,
#                                        benchmarks/compile_cold_warm.py --quick,
#                                        benchmarks/serve_scenarios.py --quick,
#                                        benchmarks/online_tuning.py --quick
#                                        and benchmarks/fault_tolerance.py --quick
#                                        and asserts each wrote valid JSON
#                                        (benchmarks/check_bench.py), so the
#                                        tracked perf trajectory can't rot silently.
#        bash test.sh --bench-gate       continuous-benchmarking gate: runs ALL
#                                        registered benchmarks (benchmarks/runner.py
#                                        --quick), appends one context-keyed record
#                                        per metric to results/bench/trajectory.jsonl,
#                                        and FAILS on a statistically significant
#                                        regression vs the stored baseline
#                                        (noise-level jitter passes).
#        bash test.sh --lint-invariants  mloslint: the repo's MLOS invariants
#                                        (docs/INVARIANTS.md, MLOS001-MLOS008)
#                                        checked over the whole tree, ratcheted
#                                        against mloslint_baseline.json; writes
#                                        results/analysis/lint_report.json.
#                                        Stdlib-only (no jax needed).
set -euo pipefail
cd "$(dirname "$0")"
export PYTHONPATH="$PWD/src${PYTHONPATH:+:$PYTHONPATH}"
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

if [[ "${1:-}" == "--bench-smoke" ]]; then
  shift
  python benchmarks/optimizer_throughput.py --quick "$@"
  python -m benchmarks.check_bench optimizer_throughput --expect-quick
  # Configstore round-trip: two flash_attention contexts tuned in one run,
  # distinct bests persisted, a fresh process resolves each, lookup cost recorded.
  python benchmarks/configstore_roundtrip.py --quick
  python -m benchmarks.check_bench configstore_resolve --expect-quick
  # Cold vs warm compile across fresh interpreters: the persistent
  # compilation cache must make restarts faster (stats.compare verdict),
  # and the xla_runtime winner must promote + resolve through the store.
  python benchmarks/compile_cold_warm.py --quick
  python -m benchmarks.check_bench compile_cold_warm --expect-quick
  # Continuous-vs-gang serving A/B over seeded traffic mixes: the heavy-tail
  # scenario must yield a stats.compare verdict of `improved` on tokens/s.
  python -m benchmarks.serve_scenarios --quick
  python -m benchmarks.check_bench serve_scenarios --expect-quick
  # Online shadow/canary tuning recovers a traffic-mix shift: at least one
  # canary promotes through the store gate, and the online-tuned server must
  # beat the frozen config on the post-shift mix (stats.compare `improved`).
  python -m benchmarks.online_tuning --quick
  python -m benchmarks.check_bench online_tuning --expect-quick
  # Fault-injected training: SIGKILL'd runs must resume bit-identically with
  # zero re-measured campaign evals, torn checkpoints must fall back, and
  # async checkpointing must beat blocking (stats.compare `improved`).
  python -m benchmarks.fault_tolerance --quick
  python -m benchmarks.check_bench fault_tolerance --expect-quick
  exit 0
fi

if [[ "${1:-}" == "--bench-gate" ]]; then
  shift
  python -m benchmarks.runner --quick --gate "$@"
  exit 0
fi

if [[ "${1:-}" == "--lint-invariants" ]]; then
  shift
  exec python -m repro.analysis.lint --json results/analysis/lint_report.json "$@"
fi

if [[ "${1:-}" == "--fast" ]]; then
  shift
  exec python -m pytest -q -m "not slow" "$@"
fi

if [[ "${1:-}" == "--cov" ]]; then
  shift
  # Coverage floor is a RATCHET: starts at the measured baseline of this
  # lane (fast tests); raise it as coverage lands, never lower it.
  python -c "import pytest_cov" 2>/dev/null || {
    echo "test.sh --cov requires pytest-cov (pip install pytest-cov)"; exit 2; }
  mkdir -p results/coverage
  exec python -m pytest -q -m "not slow" \
    --cov=repro --cov-report=term --cov-report=xml:coverage.xml \
    --cov-report=html:results/coverage --cov-fail-under=60 "$@"
fi

exec python -m pytest -q "$@"
