#!/usr/bin/env bash
# Chaos smoke: boot a real 2-host wire cluster twice — once clean, once
# under seeded wire chaos (drops, delays, severed connections) + injected
# store errors — and FAIL unless the final mutable-state checksums are
# byte-identical and the retry/breaker/deadline metrics are observable on
# /metrics (the assertions live in tests/test_chaos_soak.py, marked
# `chaos`; wired like deploy/smoke_observability.sh).
#
# Usage: deploy/smoke_chaos.sh [extra pytest args]
# CPU gate: it checks parity, counts and SLOs on XLA's CPU backend and no
# device rate; the run on the accelerator is `python chip_smoke.py`.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu python -m pytest tests/test_chaos_soak.py \
    -m chaos -q "$@"
