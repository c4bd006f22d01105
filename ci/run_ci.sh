#!/usr/bin/env bash
# CI entry (reference Jenkinsfile + ci/build.py + runtime_functions.sh,
# collapsed to the tiers that exist on a single host):
#
#   ci/run_ci.sh sanity    - compile every python file + native build
#   ci/run_ci.sh fast      - pre-merge test tier (< 2 min)
#   ci/run_ci.sh nightly   - full suite + example sweep + graft entry
#
# Env: JAX_PLATFORMS=cpu is forced for test tiers (tests/conftest.py
# sets it); on the chip the program is checked by chip_smoke.py
# (docs/testing.md).
set -euo pipefail
cd "$(dirname "$0")/.."

tier="${1:-fast}"

case "$tier" in
  sanity)
    python -m compileall -q mxtpu tools tests example
    # check_static = all mxlint passes incl. the whole-program contract
    # gates (lock-order, wire-protocol, fault-coverage, env-drift) with
    # a 15s wall-clock budget; emits mxlint_findings.{json,sarif}
    python ci/check_static.py
    python ci/check_robustness.py
    make -C mxtpu/_native
    ;;
  fast)
    JAX_PLATFORMS=cpu python -m pytest tests/ -m fast -q
    JAX_PLATFORMS=cpu python ci/check_comms_perf.py
    JAX_PLATFORMS=cpu python ci/check_guard_overhead.py
    JAX_PLATFORMS=cpu python ci/check_module_perf.py
    JAX_PLATFORMS=cpu python ci/check_module_perf.py --dist
    JAX_PLATFORMS=cpu python ci/check_module_perf.py --amp
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu python ci/check_mesh_perf.py
    JAX_PLATFORMS=cpu python ci/check_embedding_perf.py
    JAX_PLATFORMS=cpu python ci/check_replication.py
    JAX_PLATFORMS=cpu python ci/check_partition.py
    JAX_PLATFORMS=cpu python ci/check_elastic.py
    JAX_PLATFORMS=cpu python ci/check_autoscale.py
    JAX_PLATFORMS=cpu python ci/check_serving.py
    JAX_PLATFORMS=cpu python ci/check_generate_perf.py
    JAX_PLATFORMS=cpu python ci/check_rollout.py
    JAX_PLATFORMS=cpu python ci/check_streaming.py
    JAX_PLATFORMS=cpu python ci/check_observability.py
    # lock-witness smoke: re-run the kvstore-window/replication/batcher
    # slice with the runtime witness armed; fails on any access the
    # static lockset model calls guarded that the run saw unguarded
    JAX_PLATFORMS=cpu python ci/check_lock_witness.py
    ;;
  nightly)
    JAX_PLATFORMS=cpu python -m pytest tests/ -q
    JAX_PLATFORMS=cpu python tools/run_examples.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu python __graft_entry__.py
    ;;
  *)
    echo "usage: $0 {sanity|fast|nightly}" >&2
    exit 2
    ;;
esac
echo "ci tier '$tier' OK"
