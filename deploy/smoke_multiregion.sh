#!/usr/bin/env bash
# Multi-region failover smoke: the active-active replication subsystem's
# gates (tests/test_multiregion.py):
#
#   (1) warm managed failover (in-process, tier-1 speed) — two regions
#       with snapshot-shipping replication filling the standby's
#       snapshot store; managed_failover pre-hydrates the promoting
#       serving tier BEFORE the active flip (warm steals, parity gated),
#       the bounded replication drain degrades to NDC instead of
#       blocking, and a prehydration failure never fails the flip;
#   (2) replication-seam fuzz — seeded interleaving of one-page apply
#       drains with live traffic, split-brain NDC promotion, poison
#       tasks, heal: byte-identical cross-region checksums, DLQ-only
#       quarantine, zero device-parity divergence (markers slow+fuzz
#       for the wide profile);
#   (3) region kill (wire, markers slow+load) — two real wire regions,
#       standard-mix traffic, SIGKILL of EVERY active-region process
#       mid-window, warm standby promotion under SLO, bounded pre-kill
#       replication lag, post-run oracle<->device verify on BOTH
#       regions (the killed one after relaunching its store from the
#       WAL it crashed with).
#
# The first run on a fresh machine pays the serving tier's flush-kernel
# compiles once into the persistent JAX cache.
#
# Usage: deploy/smoke_multiregion.sh [extra pytest args]
# CPU gate: it checks parity, counts and SLOs on XLA's CPU backend and no
# device rate; the run on the accelerator is `python chip_smoke.py`.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_multiregion.py -q "$@"
