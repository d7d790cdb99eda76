#!/usr/bin/env bash
# Fleet-chaos smoke (ISSUE 18 acceptance): a seeded 3-host campaign with
# real SIGKILLs of a service host AND the store server (its WAL fsck'd
# clean before relaunch), one asymmetric partition cut+healed
# mid-traffic, and one membership flap (SIGSTOP past the heartbeat TTL,
# then SIGCONT -> ring rejoin -> fenced shard re-acquire) must end with
# per-workflow checksums byte-identical to a fault-free run of the same
# seed, zero tpu.serving/tpu.migration/replication parity divergence
# summed across every live host, and a clean closing verify_all. The
# run records the next CHAOS_r0N.json trajectory (kill/partition/flap
# counts, checksum identity, fsck findings) at the repo's root
# (git-ignored). A validation arm (--shrink) proves ddmin reduces an injected
# kill-then-signal regression to its 1-minimal 2-op campaign.
#
# Usage: deploy/smoke_fleetchaos.sh [extra `fuzz cluster` args]
# CPU gate: it checks parity, counts and SLOs on XLA's CPU backend and no
# device rate; the run on the accelerator is `python chip_smoke.py`.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu python -m cadence_tpu fuzz cluster \
    --seed "${FLEETCHAOS_SEED:-20260806}" \
    --hosts "${FLEETCHAOS_HOSTS:-3}" \
    --workflows "${FLEETCHAOS_WORKFLOWS:-6}" \
    --kills "${FLEETCHAOS_KILLS:-1}" \
    --store-kills "${FLEETCHAOS_STORE_KILLS:-1}" \
    --partitions "${FLEETCHAOS_PARTITIONS:-1}" \
    --flaps "${FLEETCHAOS_FLAPS:-1}" \
    --record "$@"
