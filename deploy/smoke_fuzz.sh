#!/usr/bin/env bash
# Generative-fuzzer smoke (ISSUE 15 acceptance): a 50-seed fuzzed corpus
# composing ALL 13 decision types (asserted by the coverage counter)
# must replay with zero oracle<->device divergence on the dense and
# wirec paths AND through verify_all (resident/ladder engine tier, NDC
# conflict forks included), and one seeded interleaving run — live
# start/signal/signal-with-start/reset/query/decision traffic against a
# serving-enabled durable Onebox under op chaos + store faults +
# crashpoint kills — must hold tpu.serving/parity-divergence == 0 with
# final checksums byte-identical to a fault-free run and a clean
# recovery fsck at every kill. The run records the next FUZZ_r0N.json
# trajectory at the repo's root (git-ignored).
#
# Usage: deploy/smoke_fuzz.sh [extra `fuzz run` args]
# CPU gate: it checks parity, counts and SLOs on XLA's CPU backend and no
# device rate; the run on the accelerator is `python chip_smoke.py`.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu python -m cadence_tpu fuzz run \
    --seeds "${FUZZ_SEEDS:-50}" --workflows "${FUZZ_WORKFLOWS:-4}" \
    --events "${FUZZ_EVENTS:-100}" --interleave --record "$@"
