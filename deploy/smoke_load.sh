#!/usr/bin/env bash
# Load smoke: run the two-domain overload scenario against a REAL 2-host
# wire cluster for 30s — one domain (the aggressor) driven at 2x its
# per-domain quota, the other (the victim) running the standard mixed
# open-loop traffic, seeded wire chaos in every process AND seeded
# store faults in the store-server process (CADENCE_TPU_STORE_FAULTS
# via the env_per_role seam) — and FAIL unless
#   (a) the victim domain's p99 (clocked from intended send time) holds
#       its SLO,
#   (b) the shed counters are NONZERO on the hosts' /metrics and >= 90%
#       of the aggressor's overflow was rejected as typed ServiceBusy,
#   (c) every workflow the traffic produced verifies oracle<->device with
#       zero checksum divergence.
# The assertions live in tests/test_loadgen.py (marker `load`); the
# scenario duration/SLO are env-tunable (LOADGEN_DURATION_S, LOADGEN_*).
#
# Usage: deploy/smoke_load.sh [extra pytest args]
# CPU gate: it checks parity, counts and SLOs on XLA's CPU backend and no
# device rate; the run on the accelerator is `python chip_smoke.py`.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu \
    LOADGEN_DURATION_S="${LOADGEN_DURATION_S:-30}" \
    python -m pytest tests/test_loadgen.py -m load -q "$@"
