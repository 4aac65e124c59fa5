#!/usr/bin/env bash
# Lint entry point: generic lint (ruff, if installed — config pinned in
# pyproject.toml) + the first-party invariant checker (AST rules +
# jaxpr serving-path audit + simulated-mesh sharding/resource audit).
# Run from anywhere; extra args pass through to the checker (e.g.
# scripts/lint.sh --no-jaxpr --no-mesh file.py; ANALYSIS_SKIP_MESH=1
# also skips the mesh audit).
set -uo pipefail
cd "$(dirname "$0")/.."

rc=0
if command -v ruff >/dev/null 2>&1; then
  ruff check llm_weighted_consensus_tpu tests || rc=$?
else
  echo "lint.sh: ruff not installed; skipping generic lint" \
       "(first-party invariant checker still runs)" >&2
fi

env JAX_PLATFORMS=cpu python -m llm_weighted_consensus_tpu.analysis "$@" \
  || rc=$?

# concurrency-discipline audit, explicitly by name: even when the main
# invocation above is scoped down (file args, --no-concurrency, or a
# host-level ANALYSIS_SKIP_CONCURRENCY), the lock-model registry and
# LWC014-016 still gate the whole package before lint.sh reports green.
env JAX_PLATFORMS=cpu ANALYSIS_SKIP_CONCURRENCY= \
  python -m llm_weighted_consensus_tpu.analysis \
  --rules LWC014,LWC015,LWC016 --no-jaxpr --no-mesh || rc=$?
exit $rc
