#!/usr/bin/env bash
# Observability smoke: boot a onebox, run one workflow, device-replay it,
# scrape /metrics + /health, and FAIL on missing required metric names
# (the assertions live in tests/test_observability.py::TestScrapeSurface) —
# plus the cluster telemetry plane (tests/test_telemetry.py smoke): the
# /timeseries + /hostprof + /flightrec routes, the fleet `admin top`
# rollup over a live 2-host wire cluster with burn-rate gauges, and the
# SIGTERM'd host dumping its own flight record.
#
# Usage: deploy/smoke_observability.sh [extra pytest args]
# CPU gate: it checks parity, counts and SLOs on XLA's CPU backend and no
# device rate; the run on the accelerator is `python chip_smoke.py`.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_observability.py tests/test_telemetry.py \
    -m smoke -q "$@"
