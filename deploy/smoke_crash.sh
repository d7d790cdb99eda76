#!/usr/bin/env bash
# Crash-consistency smoke: sweep the kill-anywhere WAL cut-point matrix on
# BOTH storage backends — seed a onebox workload, truncate the log at every
# record boundary (plus torn mid-record tails on JSONL), recover at each
# cut, and FAIL unless every recovered state is byte-identical to a
# fault-free prefix state with zero recovery-fsck findings (the assertions
# live in tests/test_crashsim.py, marked `crash`; the same sweep is
# runnable by hand via `python -m cadence_tpu --wal X wal crashsim
# --seed-workload 4`).
#
# Usage: deploy/smoke_crash.sh [extra pytest args]
# CPU gate: it checks parity, counts and SLOs on XLA's CPU backend and no
# device rate; the run on the accelerator is `python chip_smoke.py`.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu python -m pytest tests/test_crashsim.py \
    -m crash -q "$@"
