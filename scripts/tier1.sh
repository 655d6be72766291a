#!/usr/bin/env bash
# Tier-1 verification: the fast, CPU-only slice of the suite.
#
#   bash scripts/tier1.sh             # pytest -x -q, slow tests deselected
#   bash scripts/tier1.sh -m ""       # override: run everything
#
# Forces the host-CPU backend with 8 virtual devices (override the count
# with REPRO_HOST_DEVICES — the CI device matrix runs 8 and 16 so both
# square and rectangular worker x model mesh factorizations are
# exercised) so the sharding / collective paths (shard_map, ppermute
# gossip, comm='axis', the 2D worker x model mesh) run without
# accelerators; Pallas kernels run via interpret mode.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=${REPRO_HOST_DEVICES:-8}${XLA_FLAGS:+ $XLA_FLAGS}"

# Persistent jit-compile cache: the suite's wall clock is dominated by
# per-test XLA compiles, which are identical run to run. CI persists this
# directory via actions/cache (keyed on jax version + runner platform);
# locally it just makes the second run fast. Threshold 0 caches even
# sub-second compiles — there are hundreds of small ones.
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}"
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="${JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS:-0}"
mkdir -p "$JAX_COMPILATION_CACHE_DIR"

# Parallelize across cores when pytest-xdist is available (CI installs it;
# falls back to serial where it isn't). The wall clock is dominated by
# per-test jit compiles, which parallelize embarrassingly well.
# -x still aborts the whole session on first failure under xdist;
# --max-worker-restart 0 keeps a crashed worker from respawning past it,
# and the cache provider is disabled so workers don't race on .pytest_cache.
# --dist loadgroup keeps each xdist_group (tests/test_tpu_compile.py, which
# loads the TPU compiler) on one worker.
XDIST_ARGS=()
if python -c "import xdist" >/dev/null 2>&1; then
  XDIST_ARGS=(-n auto --dist loadgroup --max-worker-restart 0
              -p no:cacheprovider)
fi

# Doctests of the documented public API. Scoped to the nine modules
# with runnable examples — --doctest-modules over all of src/ would
# import every module (some gate on devices/deps) and execute every
# stray example. set -e aborts the run if any example drifted.
python -m pytest -q --doctest-modules \
  src/repro/core/api.py \
  src/repro/core/topology.py \
  src/repro/core/schedule.py \
  src/repro/train/loop.py \
  src/repro/train/grad.py \
  src/repro/train/damping.py \
  src/repro/checkpoint/io.py \
  src/repro/analysis/invariants.py \
  src/repro/serve/publish.py

exec python -m pytest -x -q "${XDIST_ARGS[@]}" "$@"
