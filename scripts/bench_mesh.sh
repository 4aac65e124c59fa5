#!/usr/bin/env bash
# dp-scaling bench on 8 VIRTUAL CPU devices — counts (dispatches per
# request, numerics vs single-device) and a work-conserving overhead
# ratio, never a device rate.  bench_scaling.py itself runs on whatever
# JAX gives it and needs 8 devices; this wrapper is the explicit CPU run,
# with a hard timeout like t1.sh.  Run from the repo root.
set -o pipefail
cd "$(dirname "$0")/.."
timeout -k 10 880 env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python bench_scaling.py
