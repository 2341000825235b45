#!/usr/bin/env bash
# CI gate, tiered (reference premerge flow, jenkins/spark-premerge-build.sh:
# static validation first, then the correctness net — split so premerge
# finishes in minutes and the >58-min serial full suite runs nightly):
#
#   ./ci.sh            SMOKE tier (<15 min): docs drift, compile check,
#                      tracelint, the fast `-m 'not slow'` tier-1 set, and
#                      the fixed-seed chaos soak.
#   CI_FULL=1 ./ci.sh  the smoke tier PLUS the full suite with the
#                      MemoryCleaner leak gate — the nightly bar.
#                      (SRT_FULL=1 is the legacy spelling, still honored.)
set -euo pipefail
cd "$(dirname "$0")"

echo "== docs drift =="
python tools/gen_docs.py >/dev/null
if ! git diff --quiet -- docs/; then
  echo "FAIL: docs/ drifted from code. Commit the regenerated docs." >&2
  git diff --stat -- docs/ >&2
  exit 1
fi
echo "ok"

echo "== compile check =="
python -m compileall -q spark_rapids_tpu tools benchmarks tests chipbench chip_smoke.py

echo "== tracelint (trace-safety & registry consistency) =="
# Static analyzer (docs/analysis.md): eval_tpu implementations vs the
# plan/typechecks.py host_assisted declarations, registry drift, the
# unlocked-module-state concurrency lint, the TL02x resource-lifetime
# + lock-discipline passes (leak-freedom on all paths, blocking-under-
# lock, the declared lock order, chaos coverage of unwind paths), and the
# TL03x jit-discipline passes (cache-key stability, static-shape
# bucketing, trace purity, donated-buffer safety over every
# cached-program surface, plus TL034: the plan-cache fingerprint
# builders in serving/ — pinned identity only, no per-query values,
# live conf reads or bare schema objects). Fails on any finding not in
# tools/tracelint_baseline.txt. The docs-drift gate above doubles as the
# freshness gate for the analyzer-sourced execution-mode column in
# docs/supported_ops.md.
python -m tools.tracelint

echo "== obs self-check (metrics registry + flight recorder + tracer) =="
# Exercises the always-on observability plane in-process (docs/
# observability.md): registry counter/gauge/histogram round trips with
# quantile readouts, query-lifecycle histograms, CONCURRENT per-query
# tracing with counted (never silent) capacity drops, and the flight
# recorder's postmortem bundle assembly.
python -m tools.obs_report --self-check

echo "== api validation (registry + conf + metrics consistency) =="
# Structural registry contracts plus the conf-consistency check: every
# spark.rapids.tpu.*/spark.rapids.shuffle.* key read in the package is
# declared in config.py and documented in docs/configs.md, and vice
# versa (no documented-but-dead keys, and no declared key that only a
# comment or docstring spells). The metrics
# mirror rides along: every counter/gauge/histogram registry key emitted
# in the package appears in docs/observability.md's registry table and
# vice versa, so dashboards built from the docs never watch a dead name.
python -m tools.api_validation

echo "== fast tier-1 gate (not slow) =="
# Fail fusion/pipelining/dispatch regressions in minutes: the hot
# general-path surface (opjit cache, stage fusion incl. the join/agg
# segment stages and partition-batched dispatch counters, pipelined
# shuffle, basic ops, shuffle/exchange, the query timeline tracer +
# bundle reconciliation, the device parquet decode oracles incl. the
# O(row-groups) dispatch assertion, and the mesh data plane — collective
# exchange parity across fusion/coalesce, the O(exchanges) launch
# counter, AQE device statistics, the lost-shard/slow-link chaos heal,
# the fused-compact/overlap bit-identity + mid-segment chaos soak, the
# collective-path AQE skew splits (test_aqe_skew.py),
# and the mesh efficiency profiler: phase-wall attribution, skew/
# straggler reporting, the collective watchdog, zero profiler syncs)
# and the device-native string pipeline — BYTE_ARRAY decode oracles,
# the dictionary-encoded collective exchange round trip + overflow
# fallback, and the dictionary-coded group-key dispatch assertion),
# plus the SLO serving layer (docs/serving.md: class precedence/EDF/
# aging/quota ordering, typed QueryShed front door, sched.shed chaos,
# leak-free shed rounds — the N=16 soak is slow-marked and rides the
# CI_FULL full suite), and the repeated-query hot path (docs/serving.md
# "Plan cache & logical optimizer": fingerprint collision/punch-out
# semantics, hit/re-bind bit-identity incl. pushed parquet filters,
# conf/fileset/relation invalidation, LRU bounds, cross-session sharing,
# plus the optimizer oracle — every pass vs rules-off ground truth on
# TPC-H/TPC-DS shapes and the per-rule off-switches), with the slow
# markers excluded.
python -m pytest \
  tests/test_opjit_cache.py tests/test_stage_fusion.py \
  tests/test_pipelined_shuffle.py tests/test_basic_ops.py \
  tests/test_shuffle.py tests/test_tracelint.py tests/test_obs.py \
  tests/test_obs_serving.py tests/test_serving.py \
  tests/test_parquet_device_decode.py tests/test_resource_lifecycle.py \
  tests/test_mesh_shuffle.py tests/test_mesh_dataplane.py \
  tests/test_mesh_profile.py tests/test_query_lifecycle.py \
  tests/test_string_pipeline.py tests/test_aqe_skew.py \
  tests/test_env_skips.py tests/test_recompile_stability.py \
  tests/test_plan_cache.py tests/test_logical_optimizer.py \
  -x -q -m 'not slow' -p no:cacheprovider

echo "== chaos tier (fixed-seed fault injection) =="
# Seeded chaos soak (docs/robustness.md): injection armed at every site
# across several fixed seeds; representative queries must stay bit-identical
# to a clean run with zero leaks and all semaphore permits returned, and
# corrupted/truncated shuffle blocks must heal via lineage recompute.
# The query-lifecycle soak rides here too: N=4 concurrent sessions ×
# mixed queries under seeded chaos (incl. the sched.admit and
# query.cancel sites), bit-identical to single-session runs with zero
# permit/HBM leaks and per-session bundles that reconcile.
python -m pytest tests/test_chaos.py \
  'tests/test_query_lifecycle.py::test_concurrent_session_soak_bit_identical_zero_leaks' \
  -x -q -m 'not slow' -p no:cacheprovider

if [[ "${CI_FULL:-0}" != "1" && "${SRT_FULL:-0}" != "1" ]]; then
  echo "CI green (smoke tier). Full suite + leak gate: CI_FULL=1 ./ci.sh"
  exit 0
fi

echo "== full suite (+ leak gate) =="
# SRT_LEAK_GATE makes conftest fail the run when the process-wide
# MemoryCleaner still tracks live device resources after the last test
# (reference: shutdown leak logging treated as a bug, Plugin.scala:581-596).
# stderr is teed so the ATEXIT shutdown report can be re-checked below: the
# in-process gate runs at pytest_sessionfinish, before interpreter shutdown,
# so a leak surfacing only in atexit hooks must also fail CI.
STDERR_LOG=$(mktemp)
trap 'rm -f "$STDERR_LOG"' EXIT
# plain redirection (NOT a >(tee ...) substitution: bash doesn't wait for
# the tee, so a grep could read a partial file); replayed to stderr after —
# including on failure, or set -e would discard the diagnostics (and the
# EXIT trap the log) before anyone sees them
SRT_LEAK_GATE=1 python -m pytest tests/ -x -q 2> "$STDERR_LOG" \
  || { cat "$STDERR_LOG" >&2; exit 1; }
cat "$STDERR_LOG" >&2

echo "== shutdown leak report =="
if grep -q "leaked resources at shutdown" "$STDERR_LOG"; then
  echo "FAIL: MemoryCleaner reported leaks at interpreter shutdown:" >&2
  grep -A5 "leaked resources at shutdown" "$STDERR_LOG" >&2
  exit 1
fi
echo "ok"

echo "CI green (full tier)."
