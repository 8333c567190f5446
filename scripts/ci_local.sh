#!/usr/bin/env bash
# Execute the CI workflow's steps locally (no Actions runner on the TPU
# pod) and record the outcome in artifacts/ci_run.json — the in-repo
# green-run evidence .github/workflows/ci.yml points at.
set -u
cd "$(dirname "$0")/.."
mkdir -p artifacts

START=$(date -u +%Y-%m-%dT%H:%M:%SZ)
declare -A RESULTS
FAIL=0

step() { # name, command...
  local name=$1; shift
  echo "== $name"
  local t0=$SECONDS
  if "$@" > "artifacts/ci_${name}.log" 2>&1; then
    RESULTS[$name]="pass $(($SECONDS - t0))s"
  else
    RESULTS[$name]="FAIL $(($SECONDS - t0))s"
    FAIL=1
    tail -n 20 "artifacts/ci_${name}.log"
  fi
}

# Same step set as .github/workflows/ci.yml (minus pip install — the
# pod image has the deps baked in; minus the standalone helm template —
# tests/test_helm_chart.py renders the chart inside the suite).
step build_native python -c "from dynamo_tpu import native; assert native.ensure_built() is not None"
step test_suite python -m pytest tests/ -q

{
  echo "{"
  echo "  \"started\": \"$START\","
  echo "  \"finished\": \"$(date -u +%Y-%m-%dT%H:%M:%SZ)\","
  echo "  \"git\": \"$(git rev-parse HEAD)\","
  echo "  \"steps\": {"
  first=1
  for k in build_native test_suite; do
    [ $first -eq 0 ] && echo ","
    first=0
    printf '    "%s": "%s"' "$k" "${RESULTS[$k]}"
  done
  echo ""
  echo "  },"
  echo "  \"green\": $([ $FAIL -eq 0 ] && echo true || echo false)"
  echo "}"
} > artifacts/ci_run.json
cat artifacts/ci_run.json
exit $FAIL
