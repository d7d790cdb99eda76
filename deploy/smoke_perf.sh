#!/usr/bin/env bash
# Perf smoke: run the SMALL bench suite through the pipelined bulk
# executor, write the JSON next to the recorded BENCH_r*.json trajectory
# (PERF_smoke.json), and FAIL unless:
#   - crc_parity_wire32 (and the pipelined-path parity) hold;
#   - every suite's transfer_included_rate stays within PERF_TOLERANCE
#     (default 0.5x) of the recorded baseline — by default the newest
#     BENCH_r*.json, overridable with the first arg;
#   - the fallback-under-pressure gate holds: the capacity-escalation
#     ladder's arbitration stays CRC-identical to the oracle-only path,
#     warm trials recompile nothing, and fallback_under_pressure
#     .mixed_rate_median stays within PERF_TOLERANCE of the baseline's —
#     CI catches a reintroduced overflow cliff (BENCH_r05's 3x collapse)
#     right here;
#   - the incremental gate holds: append transactions through the
#     resident-state cache cost O(new events) — long-history appends
#     within 1.5x of short-history appends at equal suffix size
#     (detail.incremental in the recorded JSON);
#   - the SNAPSHOT gate holds (TestSnapshotGate, ISSUE 11): restarting
#     with persisted mutable-state snapshots rebuilds warm — hydrate +
#     replay only the since-snapshot suffix — in <= 0.3x the cold
#     full-replay time on a long-history corpus, with zero cold-vs-warm
#     state divergence and every workflow hydrated from its record
#     (detail.snapshot in the recorded JSON);
#   - the MESH gate holds (TestMeshGate): the serving executor on a mesh
#     of 1 stays byte-identical to the unsharded kernel, warm passes
#     recompile nothing across mesh shapes already seen, mesh-of-N
#     checksums equal mesh-of-1 (detail.mesh_serving.checksum_identity),
#     the recorded mesh-of-1 rate holds vs baseline, and per-device
#     efficiency ≥ 0.7 on a REAL multi-device mesh (a virtual CPU mesh
#     time-shares cores, so only the identity half applies there). The
#     gate runs on a virtual-device CPU mesh via the same
#     --xla_force_host_platform_device_count trick dryrun_multichip
#     uses; CADENCE_TPU_MESH_DEVICES (default 8 here, default 1 in
#     production serving — set it to shard the serving hot path across
#     N devices) sizes it.
#   - the SERVING gate holds (TestServingGate, ISSUE 10): at
#     concurrency >= 8 the device-serving transaction tier coalesces
#     multiple committed transactions per from-state launch (factor
#     > 1.5 at saturation), micro-batched p99 stays at or below the
#     one-launch-per-transaction baseline, warm flushes recompile
#     nothing, and per-transaction oracle<->device parity holds with a
#     zero divergence counter (detail.serving in the recorded JSON);
#   - the FEEDER gate holds (TestFeederGate, ISSUE 9): the native-wirec
#     feeder's sustained ingest rate stays within FEEDER_GATE_RATIO
#     (default 0.5, i.e. within 2x) of the recorded device
#     transfer-included rate, holds vs the baseline's feeder rate, the
#     suffix-append leg costs by appended events, and a warm
#     homogeneous stream provably compiles nothing new;
#   - the VISIBILITY gate holds (TestVisibilityGate, ISSUE 12): every
#     device-served List/Scan/Count answers with exactly the host
#     store's result ids (parity divergence pinned at 0), warm repeats
#     of a seen query shape recompile nothing, and the recorded
#     detail.visibility section carries the rows/s-scanned sweep (the
#     device-vs-host rate gate engages on real-device recordings only);
#   - the pure-Python wirec fallback stays byte-identical: the full
#     feeder + wirec test suites run AGAIN with the native encoder
#     disabled (CADENCE_TPU_NATIVE_WIREC=0), so a native-only
#     divergence can never hide behind the fast path.
# The assertions live in tests/test_perf_gate.py, marked `perf`.
#
# Usage: deploy/smoke_perf.sh [baseline.json] [extra pytest args]
# CPU gate: it checks parity, counts and SLOs on XLA's CPU backend and no
# device rate; the run on the accelerator is `python chip_smoke.py`.
set -euo pipefail
cd "$(dirname "$0")/.."

BASELINE="${1:-}"
if [ $# -ge 1 ]; then shift; fi
if [ -z "$BASELINE" ]; then
    BASELINE=$(ls -1 BENCH_r*.json 2>/dev/null | sort | tail -1 || true)
fi
[ -n "$BASELINE" ] || { echo "no baseline BENCH_r*.json found"; exit 1; }

OUT="PERF_smoke.json"
echo "perf smoke: baseline=$BASELINE -> $OUT"
env BENCH_NS_WORKFLOWS="${BENCH_NS_WORKFLOWS:-16384}" \
    BENCH_NS_EVENTS="${BENCH_NS_EVENTS:-128}" \
    BENCH_NS_CHUNK="${BENCH_NS_CHUNK:-4096}" \
    BENCH_SUITE_WORKFLOWS="${BENCH_SUITE_WORKFLOWS:-16384}" \
    BENCH_TRIALS="${BENCH_TRIALS:-3}" \
    BENCH_INCR_WORKFLOWS="${BENCH_INCR_WORKFLOWS:-512}" \
    BENCH_INCR_SHORT="${BENCH_INCR_SHORT:-32}" \
    BENCH_INCR_LONG="${BENCH_INCR_LONG:-256}" \
    BENCH_SNAP_WORKFLOWS="${BENCH_SNAP_WORKFLOWS:-256}" \
    BENCH_SNAP_EVENTS="${BENCH_SNAP_EVENTS:-384}" \
    BENCH_VIS_SIZES="${BENCH_VIS_SIZES:-5000,20000}" \
    BENCH_VIS_TRIALS="${BENCH_VIS_TRIALS:-3}" \
    python bench.py > "$OUT"

# mesh gate, on a virtual-device CPU mesh (the dryrun_multichip
# XLA_FLAGS trick; tests/conftest.py applies the same flag, so the
# in-process mesh tests see CADENCE_TPU_MESH_DEVICES virtual devices).
# When the main bench ran on a SINGLE device its recorded mesh_serving
# section is vacuous (devices=1, identity trivially true) — re-measure
# the serving executor on the virtual mesh and splice that in, so the
# recorded checksum-identity/rate gate always covers N > 1. A
# multi-device bench (real hardware) keeps its genuine section, and the
# ≥0.7 efficiency gate engages on it.
MESH_N="${CADENCE_TPU_MESH_DEVICES:-8}"
env CADENCE_TPU_MESH_DEVICES="$MESH_N" \
    XLA_FLAGS="--xla_force_host_platform_device_count=${MESH_N}" \
    JAX_PLATFORMS=cpu \
    BENCH_MESH_WORKFLOWS="${BENCH_MESH_WORKFLOWS:-1024}" \
    python - "$OUT" <<'PY'
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
out = sys.argv[1]
doc = json.load(open(out))
if doc["detail"].get("mesh_serving", {}).get("devices", 1) <= 1:
    import bench
    from cadence_tpu.core.checksum import DEFAULT_LAYOUT
    from cadence_tpu.utils import compile_cache
    compile_cache.enable()
    doc["detail"]["mesh_serving"] = bench._mesh_serving(
        int(os.environ["BENCH_MESH_WORKFLOWS"]), DEFAULT_LAYOUT)
    json.dump(doc, open(out, "w"))
    print("mesh_serving re-measured on the virtual mesh:",
          doc["detail"]["mesh_serving"]["devices"], "devices")
PY
env PERF_CURRENT="$OUT" PERF_BASELINE="$BASELINE" \
    CADENCE_TPU_MESH_DEVICES="$MESH_N" \
    XLA_FLAGS="--xla_force_host_platform_device_count=${MESH_N}" \
    JAX_PLATFORMS=cpu python -m pytest \
    tests/test_perf_gate.py::TestMeshGate -m perf -q

# python-fallback parity: the whole feeder/wirec suite with the native
# encoder pinned OFF — the byte-identical-fallback contract of ISSUE 9
env CADENCE_TPU_NATIVE_WIREC=0 JAX_PLATFORMS=cpu \
    python -m pytest tests/test_feeder.py tests/test_wirec.py \
    tests/test_native_packer.py -q

exec env PERF_CURRENT="$OUT" PERF_BASELINE="$BASELINE" \
    JAX_PLATFORMS=cpu python -m pytest tests/test_perf_gate.py \
    -m perf -q "$@"
