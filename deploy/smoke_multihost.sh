#!/usr/bin/env bash
# Multi-host serving smoke: the cluster subsystem's two wire gates
# (tests/test_cluster_serving.py, markers slow+load):
#
#   (1) kill-host-mid-traffic — a 3-host wire cluster with the serving
#       tier ON in every host process is driven by seeded open-loop
#       signal-dominant traffic; one host is SIGKILLed mid-window. The
#       gate: the victim domain's p99 (clocked from intended send time)
#       holds its SLO, zero parity divergence anywhere (serving tier,
#       migration hydration, post-run oracle<->device verify), the
#       survivors' stolen-shard admits are >=80% snapshot-hydrated (a
#       warm failover, not a replay storm), and events/s/cluster is
#       recorded next to events/s/pod;
#   (2) planned rebalance — the cluster grows by one host; the losing
#       hosts snapshot their moving resident rows through the shared
#       store, the gaining host hydrates, and every migrated row's
#       payload CRC is byte-identical to the oracle.
#
# The scenario duration is env-tunable (CLUSTER_DURATION_S). The hosts
# pre-compile their flush kernels at boot (CADENCE_TPU_SERVING_WARM);
# the first run on a fresh machine pays those compiles once into the
# persistent JAX cache.
#
# Usage: deploy/smoke_multihost.sh [extra pytest args]
# CPU gate: it checks parity, counts and SLOs on XLA's CPU backend and no
# device rate; the run on the accelerator is `python chip_smoke.py`.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu \
    CLUSTER_DURATION_S="${CLUSTER_DURATION_S:-12}" \
    python -m pytest tests/test_cluster_serving.py -q "$@"
