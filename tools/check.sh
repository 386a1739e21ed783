#!/usr/bin/env bash
# The one pre-merge gate: lint -> static analysis -> coverage lints ->
# bench-gate self-test.
#
#   tools/check.sh                 # full run, fail on any gate
#   tools/check.sh --changed-only  # analysis scoped to git-changed files
#
# --changed-only keeps the loop fast as the package grows: stage 2
# still analyzes the whole package (the call graph, DL01's lock graph
# and the PR01/PR02 protocol map are whole-project facts — a file-scoped
# parse would fabricate '<no-handler>' findings for senders whose
# handler lives elsewhere) but REPORTS only findings in the dcnn_tpu/*.py
# files changed vs HEAD (staged, unstaged, and the last commit) via
# --only, and ruff runs on just that set. Stage 3's cross-directory
# lints are skipped. The full run remains the tier-1 contract
# (tests/test_analysis.py::test_live_package_zero_unsuppressed).
#
# Stages:
#   1. ruff (error tier + bugbear subset B006/B008/B023/B025,
#      [tool.ruff.lint] in pyproject.toml). Skipped with a notice when
#      ruff is not installed — the container image does not ship it; the
#      AST-level F-class issues are then still partially covered by
#      stage 2's parse pass.
#   2. python -m dcnn_tpu.analysis — trace-safety (TS01-TS06 incl. the
#      retrace/recompile check), concurrency (CC01-CC03), deadlock
#      (DL01 lock-order cycles, DL02 blocking-under-lock), frame-protocol
#      conformance over the four framed-TCP surfaces (PR01 handler
#      exhaustiveness, PR02 generation/nonce fencing), and atomicity
#      (AT01) against the committed baseline (docs/static_analysis.md).
#      Zero unsuppressed findings required. The monitoring-plane modules
#      (obs/tsdb.py sampler thread -> CC02 lifecycle + AT01 persistence,
#      obs/rules.py edge state + obs/fleet.py poll thread -> CC01
#      guarded_by) are covered with zero baseline entries, as are the
#      continuous-batching decode modules (serve/kvcache.py free-list +
#      tables and serve/decode.py scheduler state -> CC01 guarded_by;
#      the bucketed decode step -> TS06 retrace-clean: one jit, one
#      lower().compile() session per bucket).
#   3. coverage lints (full runs only — they span tests/ and docs/):
#      --fault-coverage (every FaultPlan trip point armed by a test),
#      --metric-drift (obs.registry emissions <-> docs/observability.md,
#      both directions), and --span-coverage (every recorded tracer span
#      maps to a goodput bucket in obs/goodput.SPAN_BUCKETS).
#   4. benchmarks/compare.py --self-test — the bench regression gate's
#      own fixture run (planted 25% drop must flag; clean history must
#      pass).
#
# Tier-1 pytest is intentionally NOT chained here (it has its own runner
# and budget); this script is the fast pre-merge loop.
set -uo pipefail
cd "$(dirname "$0")/.."

changed_only=0
for arg in "$@"; do
  case "$arg" in
    --changed-only) changed_only=1 ;;
    *) echo "unknown argument: $arg" >&2; exit 2 ;;
  esac
done

fail=0

# the report scope: everything, or just the changed dcnn_tpu python files
analysis_args=(dcnn_tpu/)
run_analysis=1
ruff_paths=(.)
if [[ "$changed_only" == 1 ]]; then
  mapfile -t changed < <(
    { git diff --name-only HEAD 2>/dev/null;
      git diff --name-only --cached 2>/dev/null;
      git diff --name-only HEAD~1..HEAD 2>/dev/null; } \
    | sort -u | grep -E '^dcnn_tpu/.*\.py$' || true)
  # drop deleted files — the analyzers read from disk
  existing=()
  for f in "${changed[@]:-}"; do
    [[ -n "$f" && -f "$f" ]] && existing+=("$f")
  done
  if [[ ${#existing[@]} -eq 0 ]]; then
    echo "== changed-only: no changed dcnn_tpu/*.py files — analysis skipped =="
    run_analysis=0
    ruff_paths=()
  else
    echo "== changed-only: reporting ${#existing[@]} file(s) =="
    only=$(IFS=,; echo "${existing[*]}")
    analysis_args=(dcnn_tpu/ --only "$only")
    ruff_paths=("${existing[@]}")
  fi
fi

echo "== [1/4] ruff (E/F error tier + bugbear subset) =="
if command -v ruff >/dev/null 2>&1; then
  if [[ ${#ruff_paths[@]} -gt 0 ]] && ! ruff check "${ruff_paths[@]}"; then
    fail=1
  fi
else
  echo "ruff not installed — skipped (pip install ruff to enable)"
fi

echo "== [2/4] dcnn_tpu.analysis =="
if [[ "$run_analysis" == 1 ]]; then
  if ! python -m dcnn_tpu.analysis "${analysis_args[@]}"; then
    fail=1
  fi
fi

if [[ "$changed_only" == 1 ]]; then
  echo "== [3/4] coverage lints — skipped under --changed-only =="
else
  echo "== [3/4] fault-coverage + metric-drift + span-coverage lints =="
  if ! python -m dcnn_tpu.analysis dcnn_tpu --fault-coverage --metric-drift --span-coverage; then
    fail=1
  fi
fi

echo "== [4/4] bench regression gate self-test =="
if ! python benchmarks/compare.py --self-test; then
  fail=1
fi

if [[ "$fail" != 0 ]]; then
  echo "CHECK FAILED" >&2
  exit 1
fi
echo "all checks passed"
