"""Benchmark: the north-star replay measured for real, plus the suite table.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "events/s/chip", "vs_baseline": N,
   "detail": {...}}

The baseline is the derived per-chip north-star rate from BASELINE.md: 1M
workflows x 1k events on a v5e-8 in <60s => >=16.7M events/s aggregate
=> ~2.08M events/s/chip. vs_baseline = headline_rate / 2.08e6.

What runs (r3 verdict asks #1/#7 — honest, minimal-D2H measurement):

1. NORTH STAR: BENCH_NS_WORKFLOWS (default 1,000,000) workflows x
   BENCH_NS_EVENTS (default 1,000) events, every history DISTINCT: the
   fused device generator+replay+checksum kernel (ops/genkernel.py +
   ops/crc.py) births each event from a per-workflow RNG stream inside
   the same scan that replays it, reduces the canonical payload to a
   per-workflow CRC32 ON DEVICE, and the host pulls 4 bytes/workflow.
   The r3 chunk-rate swing (1.9x) was host-side CRC32 of full payload
   rows interleaved with the dispatch pipeline; with the checksum on
   chip the host leg is a [W] u32 pull and the swing collapses —
   min/median/max are reported to show it. CRC spot-parity: sample
   workflows re-materialized from the same RNG stream, ORACLE-replayed,
   host CRC32 vs the device CRC compared.
2. SUITE TABLE: all five BASELINE corpus suites, BENCH_SUITE_WORKFLOWS
   (default 16,384) DISTINCT host-generated histories each, packed to
   the wire32 int32 lane format, pre-placed on device (the host-fed
   configuration the product replays), BENCH_TRIALS timed trials of
   replay + device checksum + [W] CRC pull -> events/s/chip
   min/median/max. A separate `transfer_included` row times the SAME
   work with the host->device copy of the wire32 tensor INSIDE the
   timed region — where the host link is the bound it is reported as
   such, never hidden.
3. FEEDER: sustained wire-bytes -> C++ packer -> device rate on a warm
   executable (native/feeder.py), next to the packer's standalone rate.

HBM high-water: device.memory_stats() where the platform provides it,
else XLA's CompiledMemoryStats for the north-star executable
(argument+output+temp) — never silently null (r3 weak #4).

Scale knobs exist for CI only; the defaults ARE the north star.
"""
import json
import os
import statistics
import sys
import time

import numpy as np

BASELINE_PER_CHIP = 16_700_000 / 8  # BASELINE.md derived kernel rate


def _hbm_peak(compiled) -> dict:
    """HBM high-water: live allocator stats if the platform exposes them,
    else the compiled executable's static memory analysis."""
    import jax

    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats and stats.get("peak_bytes_in_use"):
            return {"hbm_peak_bytes": int(stats["peak_bytes_in_use"]),
                    "hbm_source": "memory_stats"}
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
        return {"hbm_peak_bytes": int(total),
                "hbm_source": "compiled_memory_analysis"}
    except Exception:
        return {"hbm_peak_bytes": None, "hbm_source": "unavailable"}


def _pipelined_transfer(corpus, mesh, layout, n_chunks: int, depth: int):
    """Stream a pre-packed wirec corpus through the MESH-AWARE serving
    executor (engine/executor.stream_wirec_mesh — the same code path the
    dryrun_multichip diagnostic runs) in W chunks: the per-device H2D
    slice copies of chunk N+1 overlap the sharded replay of chunk N, so
    the transfer-included rate approaches the resident kernel rate
    instead of serializing link + compute. Pack cost is zero by design —
    the chunks come pre-packed, the warm pack-cache configuration of the
    production path (engine/cache.PackCache)."""
    from cadence_tpu.engine.executor import stream_wirec_mesh

    def run_once():
        crc, errors, _report = stream_wirec_mesh(
            corpus, mesh, layout, n_chunks=n_chunks, depth=depth)
        return crc, errors

    return run_once


def _suite_table(trials: int, suite_workflows: int, layout):
    """Host-encoded corpora (the product's replay configuration): distinct
    histories, wirec-compressed lanes (~10-18 B/event, ops/wirec.py)
    decoded on device, replay + checksum on device, 4B/wf pulled. The
    transfer-included rate streams the corpus through the pipelined bulk
    executor (chunked H2D overlapping the kernel); the one-shot rate and
    the wire32 rate are kept as comparison points."""
    import jax

    from cadence_tpu.gen.corpus import SUITES, generate_corpus
    from cadence_tpu.native.wirec import pack_wirec_auto
    from cadence_tpu.ops.encode import LANE_EVENT_ID, encode_corpus, to_wire32
    from cadence_tpu.parallel.mesh import (
        make_mesh,
        replay_sharded_crc,
        replay_wirec_sharded_crc,
        shard_events32,
        shard_wirec,
    )

    from cadence_tpu.utils.concurrency import pack_threads as _pack_threads

    mesh = make_mesh()
    n_devices = jax.device_count()
    pack_threads = _pack_threads()  # the one CADENCE_TPU_PACK_THREADS knob
    pipeline_depth = 3
    table = {}
    for suite in SUITES:
        histories = generate_corpus(suite, num_workflows=suite_workflows,
                                    seed=20260730, target_events=120)
        events_np = encode_corpus(histories)
        real = int((events_np[:, :, LANE_EVENT_ID] > 0).sum())
        t0 = time.perf_counter()
        # chunk-parallel host pack (native C++ encoder when available,
        # byte-identical pure-Python otherwise): scales with cores
        corpus = pack_wirec_auto(events_np, num_threads=pack_threads)
        t_pack = time.perf_counter() - t0
        wire = to_wire32(events_np)

        def run_resident(parts):
            from cadence_tpu.parallel.mesh import _replay_wirec_crc_with_stats
            crc, errors, _ = _replay_wirec_crc_with_stats(
                *parts, corpus.profile, layout)
            return np.asarray(crc), np.asarray(errors)

        parts = shard_wirec(corpus, mesh)
        crcs, errors = run_resident(parts)  # compile + warm
        rates = []
        for _ in range(trials):
            t0 = time.perf_counter()
            run_resident(parts)
            rates.append(real / (time.perf_counter() - t0) / n_devices)
        # transfer-inclusive, PIPELINED: the corpus streams through the
        # bulk executor in chunks, each chunk's H2D overlapping the
        # previous chunk's kernel. The host link is still the floor —
        # but it now hides behind compute instead of adding to it. The
        # chunk count must divide W and keep shards whole.
        n_chunks = next(nc for nc in (4, 2, 1)
                        if suite_workflows % nc == 0
                        and (suite_workflows // nc) % n_devices == 0)
        run_pipelined = _pipelined_transfer(corpus, mesh, layout, n_chunks,
                                            pipeline_depth)
        crc_p, err_p = run_pipelined()  # compile + warm (same executable)
        xfer_rates = []
        for _ in range(trials):
            t0 = time.perf_counter()
            run_pipelined()
            xfer_rates.append(real / (time.perf_counter() - t0) / n_devices)
        # one-shot comparison: the r05 configuration (single H2D + launch)
        t0 = time.perf_counter()
        crc_x, err_x, _ = replay_wirec_sharded_crc(corpus, mesh, layout)
        np.asarray(crc_x)
        t_xfer = time.perf_counter() - t0
        # uncompressed comparison: the r04 configuration
        t0 = time.perf_counter()
        crc_w, _, _ = replay_sharded_crc(shard_events32(wire, mesh), mesh,
                                         layout)
        crc_w = np.asarray(crc_w)
        t_xfer32 = time.perf_counter() - t0
        table[suite] = {
            "workflows": suite_workflows,
            "distinct_histories": True,
            "events": real,
            "wire_format": "wirec",
            "bytes_per_event": round(corpus.bytes_per_event(), 2),
            "pack_s": round(t_pack, 3),
            "pack_threads": pack_threads,
            "rate_min": round(min(rates)),
            "rate_median": round(statistics.median(rates)),
            "rate_max": round(max(rates)),
            "transfer_included_rate": round(
                statistics.median(xfer_rates)),
            "transfer_included_rate_min": round(min(xfer_rates)),
            "transfer_included_rate_oneshot": round(real / t_xfer / n_devices),
            "transfer_included_rate_wire32": round(
                real / t_xfer32 / n_devices),
            "transfer_chunks": n_chunks,
            "pipeline_depth": pipeline_depth,
            "h2d_bytes": int(corpus.wire_bytes),
            "h2d_bytes_wire32": int(wire.nbytes),
            "error_workflows": int((errors != 0).sum()),
            "crc_xor": int(np.bitwise_xor.reduce(crcs.astype(np.uint32))),
            "crc_parity_wire32": bool(
                (crc_w == crcs.astype(np.uint32)).all()),
            "crc_parity_pipelined": bool(
                (crc_p == crcs.astype(np.uint32)).all()
                and (err_p == errors).all()),
        }
    return table


def _north_star(workflows: int, max_events: int, chunk: int, seed: int,
                parity_samples: int, layout):
    """The measured 1M x 1k run: fused device generator+replay+checksum
    (every history DISTINCT, born on device, hashed on device); the host
    pulls one u32 per workflow. Returns the headline stats dict."""
    import jax

    from cadence_tpu.core.checksum import crc32_of_row, payload_row
    from cadence_tpu.core.checksum import STICKY_ROW_INDEX
    from cadence_tpu.ops.encode import decode_lanes
    from cadence_tpu.ops.genkernel import (
        generate_and_replay_sharded_crc,
        generate_lanes,
    )
    from cadence_tpu.oracle.state_builder import StateBuilder
    from cadence_tpu.parallel.mesh import make_mesh

    n_devices = jax.device_count()
    # CI-scale requests smaller than a chunk shrink the chunk instead of
    # silently inflating the run
    chunk = min(chunk, max(workflows, n_devices))
    # ONE code path at every n: the SPMD shard_map kernel over the device
    # mesh — a single chip routes through a mesh of 1 (identical outputs,
    # same executable shape), so the single-chip north star measures the
    # exact kernel the fleet runs instead of a divergent unsharded twin
    mesh = make_mesh()
    chunk = -(-chunk // n_devices) * n_devices

    def run_chunk(sd, lo):
        return generate_and_replay_sharded_crc(sd, lo, chunk, max_events,
                                               mesh, layout)

    n_chunks = -(-workflows // chunk)

    # warm/compile on the first chunk's shape (cold compile reported, not
    # amortized into the steady rate)
    t0 = time.perf_counter()
    crc, _ = run_chunk(seed + 1, 0)
    np.asarray(crc)
    compile_s = time.perf_counter() - t0

    total_events = 0
    total_errors = 0
    chunk_rates = []
    crc_accum = 0
    first_crcs = None

    # depth-2 software pipeline: dispatch chunk i+1 (JAX async) BEFORE
    # blocking on chunk i's 4B/wf pull, so any host-link stall overlaps
    # the next chunk's on-device compute
    real = chunk * max_events  # the generator fills every slot
    t_start = time.perf_counter()
    in_flight = run_chunk(seed, 0)
    t_prev = t_start
    for ci in range(n_chunks):
        crc, errors = in_flight
        if ci + 1 < n_chunks:
            in_flight = run_chunk(seed, (ci + 1) * chunk)
        crcs_np = np.asarray(crc).astype(np.uint32)
        errors_np = np.asarray(errors)
        now = time.perf_counter()
        chunk_rates.append(real / (now - t_prev))  # completion interval
        t_prev = now
        total_events += real
        total_errors += int((errors_np != 0).sum())
        crc_accum ^= int(np.bitwise_xor.reduce(crcs_np))
        if ci == 0:
            first_crcs = crcs_np[:parity_samples].copy()
    wall_s = time.perf_counter() - t_start

    # CRC spot-parity: materialize the SAME rng stream's lanes for a
    # sample block, oracle-replay them, host-CRC the canonical payload,
    # compare against the device-computed CRC
    sample_n = min(parity_samples, chunk)
    lanes = np.asarray(generate_lanes(seed, 0, sample_n, max_events))
    parity_fail = 0
    for i in range(sample_n):
        ms = StateBuilder().replay_history(decode_lanes(lanes[i]))
        expected = payload_row(ms, layout)
        expected[STICKY_ROW_INDEX] = 0
        if np.uint32(crc32_of_row(expected)) != first_crcs[i]:
            parity_fail += 1

    if n_devices > 1:
        hbm = {"hbm_peak_bytes": None, "hbm_source": "sharded-skip"}
    else:
        # memory analysis of the executable that actually ran: the
        # mesh-of-1 shard_map kernel, not an unsharded twin
        import jax.numpy as jnp

        from cadence_tpu.ops.genkernel import _sharded_fn
        fn = _sharded_fn(mesh, chunk, max_events, layout, to_crc=True)
        compiled = fn.lower(jnp.int64(seed),
                            jnp.zeros((1,), jnp.int64)).compile()
        hbm = _hbm_peak(compiled)

    return {
        "workflows": n_chunks * chunk,
        "max_events": max_events,
        "chunk_workflows": chunk,
        "chunks": n_chunks,
        "real_events": total_events,
        "distinct_histories": True,  # per-workflow RNG stream, no tiling
        "checksum_on_device": True,  # host pulls 4 bytes/workflow
        "wall_s": round(wall_s, 3),
        "rate": total_events / wall_s,
        "chunk_rate_min": round(min(chunk_rates)),
        "chunk_rate_median": round(statistics.median(chunk_rates)),
        "chunk_rate_max": round(max(chunk_rates)),
        "chunk_rate_note": ("host leg is a [W] u32 pull; r3's 1.9x swing "
                            "was host-side row CRC32 contending with the "
                            "dispatch pipeline, now on device"),
        "compile_s": round(compile_s, 3),
        "error_workflows": total_errors,
        "oracle_fallback_rate": total_errors / (n_chunks * chunk),
        "crc_xor": crc_accum,
        "parity_samples": sample_n,
        "parity_failures": parity_fail,
        **hbm,
    }


def _fallback_suite(suite_workflows: int, layout):
    """The adversarial mixed path (SURVEY §7 hard part 3): a corpus where
    ~2.5% of workflows overflow the device pending tables.

    The device flags them (TABLE_OVERFLOW) and the capacity-escalation
    LADDER (engine/ladder.py) re-replays exactly those rows on device at
    widened K — gathered into a compact wirec sub-corpus, K→2K→4K — with
    the Python oracle arbitrating only the ladder's residue. Both legs
    sit inside the timed region, so the mixed rate is measured under
    pressure, never assumed cliff-free. Reported alongside: per-rung row
    counts and seconds, the residual-oracle count, CRC parity against
    the ORACLE-ONLY arbitration path (computed outside the timed region:
    the ladder must change nothing but the speed), and the ladder
    compile counters proving warm trials recompiled nothing."""
    import jax

    from cadence_tpu.core.checksum import (
        STICKY_ROW_INDEX,
        crc32_of_row,
        payload_row,
    )
    from cadence_tpu.engine.ladder import EscalationLadder
    from cadence_tpu.gen.corpus import generate_corpus
    from cadence_tpu.native.wirec import pack_wirec_auto
    from cadence_tpu.ops.encode import LANE_EVENT_ID, encode_corpus
    from cadence_tpu.oracle.state_builder import StateBuilder
    from cadence_tpu.parallel.mesh import (
        _replay_wirec_crc_with_stats,
        make_mesh,
        shard_wirec,
    )
    from cadence_tpu.utils import metrics as cm

    mesh = make_mesh()
    n_devices = jax.device_count()
    histories = generate_corpus("overflow", num_workflows=suite_workflows,
                                seed=20260730, target_events=120)
    events_np = encode_corpus(histories)
    real = int((events_np[:, :, LANE_EVENT_ID] > 0).sum())
    corpus = pack_wirec_auto(events_np)
    parts = shard_wirec(corpus, mesh)
    ladder = EscalationLadder(layout,
                              mesh=mesh if n_devices > 1 else None)

    def device_leg():
        crc, errors, _ = _replay_wirec_crc_with_stats(
            *parts, corpus.profile, layout)
        return np.asarray(crc).astype(np.uint32), np.asarray(errors)

    def ladder_leg(crcs, errors):
        """Batched widened-K re-replay of flagged rows; the per-workflow
        oracle arbitrates only what the top rung could not hold."""
        fixed = crcs.copy()
        flagged = np.nonzero(errors != 0)[0]
        cap = ladder.capacity_flagged(errors)
        cap_set = set(cap.tolist())
        residual = [int(i) for i in flagged if i not in cap_set]
        if len(cap):
            crc_l, resolved, _ = ladder.escalate_wirec(corpus, cap)
            fixed[cap[resolved]] = crc_l[resolved]
            residual += [int(i) for i in cap[~resolved]]
        for i in residual:
            ms = StateBuilder().replay_history(histories[i])
            row = payload_row(ms, layout)
            row[STICKY_ROW_INDEX] = 0
            fixed[i] = np.uint32(crc32_of_row(row))
        return fixed, len(residual)

    crcs, errors = device_leg()        # compile + warm
    flagged = np.nonzero(errors != 0)[0]
    ladder_leg(crcs, errors)           # compile + warm the rung variants

    reg = cm.DEFAULT_REGISTRY
    misses0 = reg.counter(cm.SCOPE_TPU_FALLBACK, cm.M_LADDER_CACHE_MISSES)
    rates, ladder_s = [], []
    final, n_residual = crcs, 0
    for _ in range(3):
        t0 = time.perf_counter()
        crcs, errors = device_leg()
        t1 = time.perf_counter()
        final, n_residual = ladder_leg(crcs, errors)
        t2 = time.perf_counter()
        rates.append(real / (t2 - t0) / n_devices)
        ladder_s.append(t2 - t1)
    warm_recompiles = (reg.counter(cm.SCOPE_TPU_FALLBACK,
                                   cm.M_LADDER_CACHE_MISSES) - misses0)

    # oracle-only arbitration (the pre-ladder path), OUTSIDE the timed
    # region: the ladder is a perf path, so its result must be
    # byte-identical — same crc_xor or the suite fails loudly
    oracle_only = crcs.copy()
    for i in flagged:
        ms = StateBuilder().replay_history(histories[i])
        row = payload_row(ms, layout)
        row[STICKY_ROW_INDEX] = 0
        oracle_only[i] = np.uint32(crc32_of_row(row))

    return {
        "workflows": suite_workflows,
        "events": real,
        "wire_format": "wirec",
        "oracle_fallback_rate": round(len(flagged) / suite_workflows, 4),
        "fallback_workflows": int(len(flagged)),
        "mixed_rate_median": round(statistics.median(rates)),
        "device_only_events": int(real - sum(
            (events_np[i, :, LANE_EVENT_ID] > 0).sum() for i in flagged)),
        "ladder_leg_s_median": round(statistics.median(ladder_s), 3),
        "ladder_rungs": ladder.last_run,
        "ladder_max_rungs": ladder.max_rungs,
        "ladder_recompiles_warm": int(warm_recompiles),
        "residual_oracle_rows": int(n_residual),
        "crc_xor": int(np.bitwise_xor.reduce(final)),
        "crc_xor_oracle_only": int(np.bitwise_xor.reduce(oracle_only)),
        "crc_parity_oracle_only": bool((final == oracle_only).all()),
        "note": ("device replay + widened-K ladder re-replay of flagged "
                 "workflows (residue to the host oracle), all inside "
                 "the timed region"),
    }


def _incremental_suite(layout, workflows: int = 0, short_events: int = 0,
                       long_events: int = 0, txns: int = 0):
    """Append-transaction latency vs history length: the serving-path
    claim of the resident-state cache (engine/resident.py) measured for
    real.

    Two corpora — SHORT and LONG histories — each: full-replay once to
    pin every workflow's state in HBM, then (a) TIMED single-workflow
    append transactions (lookup + suffix pack through the pack cache +
    from-state replay + payload readback, the decision-hot-loop shape)
    and (b) one batched append pass over the rest for throughput. The
    O(new events) contract is that the long corpus's append latency
    tracks the short one's (equal suffix sizes ⇒ equal launched shapes)
    — `long_vs_short_p50_ratio` near 1.0, never near
    long_events/short_events. tests/test_perf_gate.py gates the ratio at
    1.5x; full replay of the same corpora is timed alongside so the
    JSON shows what the cache is buying."""
    import jax.numpy as jnp

    from cadence_tpu.engine.cache import PackCache, content_address
    from cadence_tpu.engine.ladder import EscalationLadder
    from cadence_tpu.engine.resident import ResidentStateCache
    from cadence_tpu.gen.corpus import generate_corpus
    from cadence_tpu.ops.encode import (
        LANE_EVENT_ID,
        assemble_corpus,
        encode_batches_resumable,
    )
    from cadence_tpu.ops.payload import payload_rows
    from cadence_tpu.ops.replay import replay_events

    workflows = workflows or int(os.environ.get("BENCH_INCR_WORKFLOWS",
                                                "512"))
    short_events = short_events or int(os.environ.get("BENCH_INCR_SHORT",
                                                      "32"))
    long_events = long_events or int(os.environ.get("BENCH_INCR_LONG",
                                                    "256"))
    txns = txns or int(os.environ.get("BENCH_INCR_TXNS", "32"))
    txns = min(txns, max(1, workflows // 4))
    warm = min(8, workflows - txns) if workflows > txns else 0

    out = {}
    for label, target in (("short", short_events), ("long", long_events)):
        hists = generate_corpus("basic", num_workflows=workflows,
                                seed=20260803, target_events=target)
        keys = [("bench", f"wf-{label}-{i}", "r")
                for i in range(workflows)]
        pack_cache = PackCache(max_size=workflows + 8)
        cache = ResidentStateCache(
            layout, ladder=EscalationLadder(layout),
            budget_bytes=1 << 34)

        # seed: ONE full replay of every prefix (the cold path), states
        # pinned row by row — also timed, as the baseline the cache beats
        prefix_rows = [pack_cache.encode(k, h[:-1])
                       for k, h in zip(keys, hists)]
        corpus = assemble_corpus(prefix_rows,
                                 max(r.shape[0] for r in prefix_rows))
        t0 = time.perf_counter()
        s = replay_events(jnp.asarray(corpus), layout)
        rows = np.asarray(payload_rows(s, layout))
        full_replay_s = time.perf_counter() - t0
        branch = np.asarray(s.current_branch)
        for i, k in enumerate(keys):
            cache.admit(k, content_address(hists[i][:-1]),
                        cache.extract_row(s, i), rows[i], int(branch[i]))

        def one_txn(i):
            """One append transaction: the decision-hot-loop shape."""
            k, h = keys[i], hists[i]
            hit = cache.lookup(k, h)
            assert hit is not None and hit[0] == "suffix", hit
            res = cache.replay_append([(k, hit[1], h)],
                                      encode_suffix=pack_cache.encode_suffix)
            assert res[0].ok
            return res[0]

        for i in range(warm):  # compile + warm the append shapes
            one_txn(i)
        lat = []
        for i in range(warm, warm + txns):
            t0 = time.perf_counter()
            one_txn(i)
            lat.append(time.perf_counter() - t0)
        # batched appends: the bulk re-verify configuration
        rest = list(range(warm + txns, workflows))
        batched_rate = 0.0
        if rest:
            items = [(keys[i], cache.lookup(keys[i], hists[i])[1],
                      hists[i]) for i in rest]
            t0 = time.perf_counter()
            results = cache.replay_append(
                items, encode_suffix=pack_cache.encode_suffix)
            dt = time.perf_counter() - t0
            assert all(r.ok for r in results)
            batched_rate = cache.last_append.events_appended / dt

        real = int((corpus[:, :, LANE_EVENT_ID] > 0).sum())
        suffix_events = [len(h[-1].events) for h in hists[warm:warm + txns]]
        lat.sort()
        out[label] = {
            "workflows": workflows,
            "history_events_mean": round(real / workflows, 1),
            "suffix_events_mean": round(
                sum(suffix_events) / len(suffix_events), 2),
            "append_p50_ms": round(1e3 * lat[len(lat) // 2], 3),
            "append_p95_ms": round(1e3 * lat[int(len(lat) * 0.95)], 3),
            "append_min_ms": round(1e3 * lat[0], 3),
            "batched_append_events_per_sec": round(batched_rate),
            "full_replay_s": round(full_replay_s, 3),
            "txns": txns,
            "chunk_shape": (cache.last_append.chunk_shapes[:1] or
                            [(0, 0)])[0],
        }
    ratio = (out["long"]["append_p50_ms"] / out["short"]["append_p50_ms"]
             if out["short"]["append_p50_ms"] else 0.0)
    return {
        **out,
        "long_vs_short_p50_ratio": round(ratio, 3),
        "shapes_equal": out["short"]["chunk_shape"]
        == out["long"]["chunk_shape"],
        "note": ("append transactions replay ONLY appended batches "
                 "against HBM-resident states; the ratio near 1.0 (not "
                 "near long/short history length) is the O(new events) "
                 "claim. The first corpus's batched/full-replay numbers "
                 "include one-time XLA compiles; the p50s are warmed."),
    }


def _snapshot_suite(layout, workflows: int = 0, target_events: int = 0,
                    trials: int = 0):
    """Warm vs cold restart through the persisted-snapshot tier
    (engine/snapshot.py): the same long-history corpus is verified from
    a fresh resident pool twice — COLD (no snapshots: every workflow
    full-replays its history) and WARM (snapshots persisted, caches
    cleared as a restart would: hydrate + replay only the
    since-snapshot suffix). Both paths run once untimed to compile, and
    the timed trials take the median, so the ratio compares steady
    states. tests/test_perf_gate.py TestSnapshotGate pins warm <= 0.3x
    cold with zero divergence."""
    from cadence_tpu.engine.persistence import Stores
    from cadence_tpu.engine.tpu_engine import TPUReplayEngine
    from cadence_tpu.gen.corpus import generate_corpus
    from cadence_tpu.oracle.state_builder import StateBuilder
    from cadence_tpu.utils import metrics as cm

    workflows = workflows or int(os.environ.get("BENCH_SNAP_WORKFLOWS",
                                                "256"))
    target_events = target_events or int(
        os.environ.get("BENCH_SNAP_EVENTS", "384"))
    trials = trials or int(os.environ.get("BENCH_SNAP_TRIALS", "3"))

    stores = Stores()
    hists = generate_corpus("basic", num_workflows=workflows,
                            seed=20260803, target_events=target_events)
    keys = []
    for h in hists:
        b0 = h[0]
        key = (b0.domain_id, b0.workflow_id, b0.run_id)
        # snapshot point: all but the final batch; the tail commits
        # after the sweep so the warm path genuinely replays a suffix
        for b in h[:-1]:
            stores.history.append_batch(*key, list(b.events))
        ms = StateBuilder().replay_history(
            stores.history.as_history_batches(*key))
        info = ms.execution_info
        info.domain_id, info.workflow_id, info.run_id = key
        stores.execution.upsert_workflow(ms)
        keys.append(key)

    tpu = TPUReplayEngine(stores)
    assert tpu.verify_all().ok
    sweep = tpu.snapshot_sweep(force=True)
    assert sweep.written == workflows, sweep
    # the post-snapshot suffix commits
    for h, key in zip(hists, keys):
        stores.history.append_batch(*key, list(h[-1].events))
        ms = StateBuilder().replay_history(
            stores.history.as_history_batches(*key))
        info = ms.execution_info
        info.domain_id, info.workflow_id, info.run_id = key
        stores.execution.upsert_workflow(ms)

    from cadence_tpu.core.checksum import Checksum
    from cadence_tpu.engine.rebuild import DeviceRebuilder

    reg = cm.DEFAULT_REGISTRY
    total_events = sum(sum(len(b.events) for b in h) for h in hists)
    # the rebuild jobs a restart would hand the rebuilder — read ONCE,
    # outside the timed region (recovery reads the WAL regardless of
    # how states are rebuilt; the snapshot tier's claim is about the
    # REBUILD work, not the log read)
    jobs = [(stores.history.as_history_batches(*key), None)
            for key in keys]

    def run_mode(warm: bool):
        def make():
            rb = DeviceRebuilder(layout)
            if warm:
                rb.snapshots = stores.snapshot
            return rb
        make().rebuild(jobs)  # compile/warm pass for this mode's shapes
        times, states, seeded, suffix_events = [], None, 0, 0
        for _ in range(trials):
            rb = make()  # fresh caches: every trial is a real restart
            pre = reg.counter(cm.SCOPE_TPU_RESIDENT,
                              cm.M_RESIDENT_EVENTS_APPENDED)
            t0 = time.perf_counter()
            states = rb.rebuild(jobs)
            times.append(time.perf_counter() - t0)
            seeded = rb.stats.snapshot_seeded
            suffix_events = reg.counter(
                cm.SCOPE_TPU_RESIDENT,
                cm.M_RESIDENT_EVENTS_APPENDED) - pre
            assert rb.stats.oracle_fallback == 0, rb.stats
        times.sort()
        return times[len(times) // 2], states, seeded, suffix_events

    cold_s, cold_states, _, _ = run_mode(warm=False)
    warm_s, warm_states, hydrated, suffix_events = run_mode(warm=True)
    divergent = sum(
        1 for a, b in zip(cold_states, warm_states)
        if Checksum.of(a).value != Checksum.of(b).value)
    store_stats = stores.snapshot.stats()
    return {
        "workflows": workflows,
        "history_events_mean": round(total_events / workflows, 1),
        "snapshot_records": store_stats["entries"],
        "snapshot_bytes": store_stats["bytes"],
        "cold_restart_s": round(cold_s, 4),
        "warm_restart_s": round(warm_s, 4),
        "warm_vs_cold": round(warm_s / cold_s, 4) if cold_s else 0.0,
        "cold_hydrate_events_per_sec": round(total_events / cold_s)
        if cold_s else 0,
        "warm_hydrate_events_per_sec": round(total_events / warm_s)
        if warm_s else 0,
        "suffix_events_replayed": int(suffix_events),
        "hydrated": hydrated,
        "divergent": divergent,
        "note": ("cold = every workflow's mutable state rebuilt by "
                 "full-history device replay; warm = the persisted "
                 "ReplayState rows hydrate and only the since-snapshot "
                 "suffix replays (fresh rebuilder + caches per trial — "
                 "a genuine restart). Medians over warmed trials; "
                 "hydrate rate counts TOTAL history events made live "
                 "per second of rebuild; divergent counts cold-vs-warm "
                 "state checksum mismatches (must be 0)."),
    }


def _visibility_suite(sizes=None, trials: int = 0):
    """Device-visibility scan rates (ISSUE 12): a synthetic visibility
    population at each BENCH_VIS_SIZES row count, the same selectivity-
    sweep query corpus timed through the HOST store (dict/set indexes +
    per-record predicate) and through the COLUMNAR DEVICE tier
    (ops/scan.py mask kernels, parity off inside the timed region so
    the measurement is the pure device path). Count queries carry the
    rows/s-scanned headline (scalar readback — the HBM-bandwidth
    claim); a selective List is timed separately since it pays host
    materialization of matches. Warm recompiles across the timed
    repeats must be ZERO (the kernel-variant cache counters prove it —
    the acceptance bar TestVisibilityGate pins)."""
    from cadence_tpu.engine.persistence import (
        VisibilityRecord,
        VisibilityStore,
    )
    from cadence_tpu.utils import metrics as cm

    sizes = sizes or [int(s) for s in os.environ.get(
        "BENCH_VIS_SIZES", "10000,100000").split(",") if s]
    trials = trials or int(os.environ.get("BENCH_VIS_TRIALS", "5"))
    reg = cm.DEFAULT_REGISTRY
    sc = cm.SCOPE_TPU_VISIBILITY
    saved = {k: os.environ.get(k) for k in
             ("CADENCE_TPU_VISIBILITY", "CADENCE_TPU_VISIBILITY_PARITY",
              "CADENCE_TPU_VISIBILITY_CAPACITY")}
    out_sizes = []
    try:
        for n in sizes:
            os.environ["CADENCE_TPU_VISIBILITY"] = "0"
            os.environ["CADENCE_TPU_VISIBILITY_CAPACITY"] = str(n)
            import random
            rng = random.Random(20260804)
            store = VisibilityStore()
            base = 1_700_000_000_000_000_000
            for i in range(n):
                attrs = {}
                r = rng.random()
                if r < 0.5:
                    attrs["Priority"] = rng.randrange(0, 10)
                elif r < 0.8:
                    attrs["Tag"] = f"tag-{rng.randrange(4)}"
                rec = VisibilityRecord(
                    domain_id="bench", workflow_id=f"wf-{i}",
                    run_id=f"r-{i}", workflow_type=f"wt-{i % 8}",
                    start_time=base + i * 1000, search_attrs=attrs)
                store.record_started(rec)
                if rng.random() < 0.5:
                    store.record_closed("bench", f"wf-{i}", f"r-{i}",
                                        close_time=base + i * 1000 + 7,
                                        close_status=rng.randrange(0, 3))
            # the selectivity sweep: match fractions from ~0.01% to 100%
            cut99 = base + int(n * 0.999) * 1000
            queries = [
                ("all", ""),
                ("half_open", "CloseStatus = -1"),
                ("type_eighth", "WorkflowType = 'wt-3'"),
                ("attr_tenth", "Priority >= 9"),
                ("narrow_and", "WorkflowType = 'wt-1' AND "
                               "CloseStatus = 0 AND Priority < 2"),
                ("time_tail", f"StartTime > {cut99}"),
            ]

            def run_counts(label):
                t0 = time.perf_counter()
                for _ in range(trials):
                    for _name, q in queries:
                        store.count("bench", q)
                return time.perf_counter() - t0

            host_s = run_counts("host")
            sel = {name: store.count("bench", q) for name, q in queries}

            os.environ["CADENCE_TPU_VISIBILITY"] = "1"
            os.environ["CADENCE_TPU_VISIBILITY_PARITY"] = "0"
            # warm pass: bootstrap flush + one compile per query shape
            for _name, q in queries:
                store.count("bench", q)
                store.query("bench", q)
            pre_miss = reg.counter(sc, cm.M_LADDER_CACHE_MISSES)
            dev_s = run_counts("device")
            warm_recompiles = (reg.counter(sc, cm.M_LADDER_CACHE_MISSES)
                               - pre_miss)
            # a selective list (materializes matches on the host);
            # warm its shape first — the timed repeats must measure the
            # steady state, not the one-off compile
            list_q = "WorkflowType = 'wt-3' AND CloseStatus = -1"
            store.query("bench", list_q)
            t0 = time.perf_counter()
            for _ in range(trials):
                store.query("bench", list_q)
            list_dev_s = (time.perf_counter() - t0) / trials
            # parity pass (outside the timed region): every query's
            # device ids re-checked against the host evaluator
            os.environ["CADENCE_TPU_VISIBILITY_PARITY"] = "1"
            pre_div = reg.counter(sc, cm.M_VIS_DIVERGENCE)
            for _name, q in queries:
                store.count("bench", q)
                store.query("bench", q)
            divergence = reg.counter(sc, cm.M_VIS_DIVERGENCE) - pre_div
            view = store._device
            if view is not None:
                view.stop()
            scans = trials * len(queries)
            out_sizes.append({
                "rows": n,
                "queries_per_trial": len(queries),
                "selectivity": {k: round(v / n, 5)
                                for k, v in sel.items()},
                "host_rows_per_sec": round(n * scans / host_s)
                if host_s else 0,
                "device_rows_per_sec": round(n * scans / dev_s)
                if dev_s else 0,
                "speedup": round(host_s / dev_s, 3) if dev_s else 0.0,
                "device_count_ms": round(dev_s / scans * 1000, 4),
                "host_count_ms": round(host_s / scans * 1000, 4),
                "device_selective_list_ms": round(list_dev_s * 1000, 4),
                "warm_recompiles": int(warm_recompiles),
                "parity_divergence": int(divergence),
            })
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {
        "sizes": out_sizes,
        "parity": all(s["parity_divergence"] == 0 for s in out_sizes),
        "warm_recompiles": sum(s["warm_recompiles"] for s in out_sizes),
        "note": ("rows/s = table rows logically scanned per second of "
                 "Count traffic (device: one mask kernel + 8-byte "
                 "readback per query; host: index-planned per-record "
                 "predicate). Warm recompiles across timed repeats "
                 "must be 0; parity pass re-checks every query's ids "
                 "against the host evaluator."),
    }


def _mesh_serving(workflows: int, layout):
    """The pod-scale north-star section (ISSUE 7): events/s/POD and
    per-device efficiency measured THROUGH THE SERVING EXECUTOR
    (engine/executor.replay_corpus_mesh — the exact chunked, pipelined,
    per-device-staged path the engine's verify/rebuild hot path runs,
    and the same code dryrun_multichip diagnoses). A mesh of 1 is timed
    first (the single-chip serving baseline the perf gate pins), then
    the full mesh; mesh-of-N payload rows must be byte-identical to
    mesh-of-1 — sharding is a speed axis, never a result axis. On a
    virtual CPU mesh the devices share physical cores, so
    per_device_efficiency reports scaling OVERHEAD there (virtual_mesh
    flags it); on real hardware the perf gate holds it ≥ 0.7."""
    import jax

    from cadence_tpu.engine.executor import replay_corpus_mesh
    from cadence_tpu.gen.corpus import generate_corpus
    from cadence_tpu.ops.encode import LANE_EVENT_ID, encode_corpus
    from cadence_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    n = len(devices)
    workflows = -(-workflows // n) * n
    hists = generate_corpus("basic", num_workflows=workflows,
                            seed=20260730, target_events=60)
    events = encode_corpus(hists)
    real = int((events[:, :, LANE_EVENT_ID] > 0).sum())
    chunk = max(n, workflows // 4)

    def rate_on(mesh):
        replay_corpus_mesh(events, mesh, layout,
                           chunk_workflows=chunk)  # compile + warm
        best, rows, errors = 0.0, None, None
        for _ in range(3):
            t0 = time.perf_counter()
            rows, errors, _branch, _rep = replay_corpus_mesh(
                events, mesh, layout, chunk_workflows=chunk)
            best = max(best, real / (time.perf_counter() - t0))
        return best, rows, errors

    rate_1, rows_1, err_1 = rate_on(make_mesh(devices[:1]))
    out = {
        "workflows": workflows,
        "events": real,
        "devices": n,
        "chunk_workflows": chunk,
        "serving_executor": True,
        "virtual_mesh": devices[0].platform == "cpu",
        "rate_n1": round(rate_1),
        "events_per_sec_pod": round(rate_1),
        "error_workflows": int((err_1 != 0).sum()),
        "per_device_efficiency": 1.0,
        "checksum_identity": True,
    }
    if n > 1:
        rate_n, rows_n, err_n = rate_on(make_mesh(devices))
        out.update({
            f"rate_n{n}": round(rate_n),
            "events_per_sec_pod": round(rate_n),
            "speedup": round(rate_n / rate_1, 4),
            "per_device_efficiency": round(rate_n / (rate_1 * n), 4),
            # the PR-5 invariant, extended to the serving path: mesh-of-N
            # must produce the SAME bytes as mesh-of-1 on the same corpus
            "checksum_identity": bool((rows_n == rows_1).all()
                                      and (err_n == err_1).all()),
        })
    return out


def _cluster_serving(layout, hosts_n: int = 0, workflows: int = 0,
                     target_events: int = 0):
    """Multi-host device serving (ISSUE 13): the cluster scale-out of
    the serving tier measured in-process. Workflows partition across H
    simulated hosts by the SAME ring the wire cluster routes with
    (membership.HashRing + shard_id_for_workflow), each host running its
    OWN TPUReplayEngine + ServingScheduler — independent resident pools,
    independent drains — and every host's append round drives
    concurrently. `events_per_sec_cluster` is the summed appended-event
    rate over the whole fleet's wall window, recorded next to the
    single-host `events_per_sec_pod` baseline. The migration leg then
    proves the subsystem's state story: host A's resident rows snapshot
    out through the shared store (engine/migration.MigrationManager),
    host B hydrates + suffix-replays, and every migrated payload must be
    byte-identical to the oracle. On the virtual CPU mesh all "hosts"
    share physical cores, so cluster scaling reports coordination
    overhead there (virtual flag), exactly like detail.mesh_serving."""
    import threading

    from cadence_tpu.core.checksum import STICKY_ROW_INDEX, payload_row
    from cadence_tpu.engine.cache import batch_crc
    from cadence_tpu.engine.membership import (
        HashRing,
        shard_id_for_workflow,
    )
    from cadence_tpu.engine.migration import MigrationManager
    from cadence_tpu.engine.persistence import Stores
    from cadence_tpu.engine.serving import ServingScheduler
    from cadence_tpu.engine.tpu_engine import TPUReplayEngine
    from cadence_tpu.gen.corpus import generate_corpus
    from cadence_tpu.oracle.state_builder import StateBuilder

    hosts_n = hosts_n or int(os.environ.get("BENCH_CLUSTER_HOSTS", "2"))
    workflows = workflows or int(os.environ.get("BENCH_CLUSTER_WORKFLOWS",
                                                "64"))
    target_events = target_events or int(
        os.environ.get("BENCH_CLUSTER_EVENTS", "96"))
    num_shards = 8
    hists = generate_corpus("basic", num_workflows=workflows,
                            seed=20260804, target_events=target_events)
    appends = 4  # warm round + timed round, two batches each
    prefix = min(len(h) for h in hists) - appends
    assert prefix > 1, (prefix, appends)
    keys = [("bench", f"cs-{i}", "r") for i in range(workflows)]
    counts = {k: prefix for k in keys}
    by_key = {k: h for k, h in zip(keys, hists)}

    def read_batches(key):
        return by_key[key][:counts[key]]

    def expected_for(key):
        ms = StateBuilder().replay_history(read_batches(key))
        row = payload_row(ms, layout)
        row[STICKY_ROW_INDEX] = 0
        return row, int(ms.version_histories.current_index)

    def build_fleet(n):
        """n hosts, each owning its ring slice of the keys."""
        ring = HashRing([f"host-{i}" for i in range(n)])
        fleet = {}
        for i in range(n):
            name = f"host-{i}"
            tpu = TPUReplayEngine(Stores(), layout)
            sched = ServingScheduler(tpu, max_batch=8, max_wait_us=2000,
                                     read_batches=read_batches)
            sched.warm(e_shapes=(16, 32))
            fleet[name] = sched
        owned = {name: [] for name in fleet}
        for k in keys:
            sid = shard_id_for_workflow(k[1], num_shards)
            owned[ring.lookup(f"shard-{sid}")].append(k)
        return fleet, owned

    def drive_fleet(fleet, owned, conc_per_host=4):
        """One append per owned workflow on every host, all hosts
        concurrent; returns (wall seconds, total appended events)."""
        errs = []
        total_events = [0]
        lock = threading.Lock()
        threads = []

        def worker(sched, share):
            # a raising submit/result must surface in errs, not die
            # silently with the thread — a dropped share would publish
            # an under-counted (but plausible) cluster rate
            try:
                for k in share:
                    counts[k] += 1
                    batch = read_batches(k)[-1]
                    row, br = expected_for(k)
                    ticket = sched.submit(k, row, br, batch_crc(batch))
                    res = ticket.result(timeout=300.0)
                    with lock:
                        total_events[0] += len(batch.events)
                        if not (res.ok and res.parity_ok):
                            errs.append(res)
            except Exception as exc:
                with lock:
                    errs.append(exc)

        for name, sched in fleet.items():
            share = owned[name]
            for i in range(conc_per_host):
                sl = share[i::conc_per_host]
                if sl:
                    threads.append(threading.Thread(
                        target=worker, args=(sched, sl)))
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert not errs, errs[:3]
        return wall, total_events[0]

    def measure(n):
        fleet, owned = build_fleet(n)
        # seed + warm: one cold round pins every prefix state, one
        # append round traces the from-state shapes (untimed)
        for name, sched in fleet.items():
            for k in owned[name]:
                row, br = expected_for(k)
                sched.submit(k, row, br, batch_crc(read_batches(k)[-1]))
            assert sched.drain(timeout=300.0)
        drive_fleet(fleet, owned)
        wall, events = drive_fleet(fleet, owned)
        for sched in fleet.values():
            sched.stop()
        return events / wall

    rate_pod = measure(1)
    rate_cluster = measure(hosts_n)

    # -- the migration leg: losing host -> shared store -> gaining host --
    stores = Stores()
    mig_keys = []
    for h in hists[:16]:
        b0 = h[0]
        key = (b0.domain_id, b0.workflow_id, b0.run_id)
        for b in h[:prefix]:
            stores.history.append_batch(*key, list(b.events))
        ms = StateBuilder().replay_history(
            stores.history.as_history_batches(*key))
        info = ms.execution_info
        info.domain_id, info.workflow_id, info.run_id = key
        stores.execution.upsert_workflow(ms)
        mig_keys.append(key)
    loser = TPUReplayEngine(stores, layout)
    assert loser.verify_all().ok
    out = MigrationManager("bench-loser", num_shards,
                           loser).migrate_out(range(num_shards))
    # one committed batch lands between snapshot and steal (the live
    # suffix the gaining host must catch up)
    for key, h in zip(mig_keys, hists):
        stores.history.append_batch(*key, list(h[prefix].events))
        ms = StateBuilder().replay_history(
            stores.history.as_history_batches(*key))
        info = ms.execution_info
        info.domain_id, info.workflow_id, info.run_id = key
        stores.execution.upsert_workflow(ms)
    gainer = TPUReplayEngine(stores, layout)
    t0 = time.perf_counter()
    rep = MigrationManager("bench-gainer", num_shards,
                           gainer).hydrate_shards(range(num_shards))
    hydrate_s = time.perf_counter() - t0
    identical = all(
        (np.asarray(gainer.resident.entry_for(k).payload) ==
         _expected_row_of(stores, k, layout)).all()
        for k in mig_keys if gainer.resident.entry_for(k) is not None)

    return {
        "hosts": hosts_n,
        "workflows": workflows,
        "num_shards": num_shards,
        "virtual": True,  # simulated hosts share this process's cores
        "events_per_sec_pod": round(rate_pod),
        "events_per_sec_cluster": round(rate_cluster),
        "cluster_speedup": round(rate_cluster / rate_pod, 4),
        "migration": {
            "snapshotted_out": out.snapshotted,
            "hydrated": rep.hydrated,
            "cold": rep.cold,
            "stale": rep.stale,
            "suffix_events": rep.suffix_events,
            "hydrate_s": round(hydrate_s, 4),
            "parity_divergence": rep.parity_divergence,
            "payload_identity": bool(identical and rep.hydrated > 0),
        },
    }


def _expected_row_of(stores, key, layout):
    from cadence_tpu.core.checksum import STICKY_ROW_INDEX, payload_row

    ms = stores.execution.get_workflow(*key)
    row = payload_row(ms, layout)
    row[STICKY_ROW_INDEX] = 0
    return row


def _feeder_rate(layout):
    """The ingest pipeline: wire bytes → wirec encoder (native C++ fused
    pass when the .so loads — the ISSUE 9 path — byte-identical
    pure-Python otherwise) → pinned staging buffers → H2D → device
    decode+replay+checksum → 4B/wf back; the wire32 (uncompressed)
    sustained rate is kept as the comparison point, and the
    suffix-append leg measures the warm re-verify configuration
    (PackCache suffix repack + resident from-state replay)."""
    from cadence_tpu.gen.corpus import generate_corpus
    from cadence_tpu.native import packing
    from cadence_tpu.native.feeder import feed_corpus32, feed_corpus_wirec

    if not packing.native_available():
        return None
    histories = generate_corpus("basic", num_workflows=16384, seed=7,
                                target_events=100)
    chunk = 8192
    feed_corpus_wirec(histories[:chunk], chunk_workflows=chunk,
                      layout=layout)  # warm
    _, errors, report = feed_corpus_wirec(histories, chunk_workflows=chunk,
                                          layout=layout)
    feed_corpus32(histories[:chunk], chunk_workflows=chunk,
                  layout=layout)  # warm
    _, errors32, report32 = feed_corpus32(histories, chunk_workflows=chunk,
                                          layout=layout)
    return {
        "wire_format": "wirec",
        "native_wirec": report.native_wirec,
        "events": report.events,
        "sustained_events_per_sec": round(report.events_per_sec),
        "pack_only_events_per_sec": round(report.pack_events_per_sec),
        "compress_s": round(report.compress_s, 3),
        "h2d_s": round(report.h2d_s, 3),
        "bytes_per_event": round(report.bytes_per_event, 2),
        "profile_refits": report.profile_refits,
        "pipeline_depth": report.depth,
        "pack_queue_wait_s": round(report.pack_queue_wait_s, 3),
        "error_workflows": int((errors != 0).sum()),
        "wire32_sustained_events_per_sec": round(report32.events_per_sec),
        "wire32_error_workflows": int((errors32 != 0).sum()),
        "suffix_append": _feeder_append_rate(layout),
    }


def _feeder_append_rate(layout, workflows: int = 0):
    """The suffix-append feeder leg: every workflow gets one appended
    batch and the stream re-verifies through feed_appends — PackCache
    suffix repack (O(new events) host cost) + from-state replay against
    HBM-resident states. The rate counts APPENDED events (the honest
    denominator for an append stream); history_events_per_sec is the
    full-history rate an O(history) path would have had to sustain for
    the same wall time, i.e. what residency buys."""
    import jax.numpy as jnp

    from cadence_tpu.engine.cache import PackCache, content_address
    from cadence_tpu.engine.ladder import EscalationLadder
    from cadence_tpu.engine.resident import ResidentStateCache
    from cadence_tpu.gen.corpus import generate_corpus
    from cadence_tpu.native.feeder import feed_appends
    from cadence_tpu.ops.encode import LANE_EVENT_ID, assemble_corpus
    from cadence_tpu.ops.payload import payload_rows
    from cadence_tpu.ops.replay import replay_events

    workflows = workflows or int(os.environ.get("BENCH_FEED_APPEND_WF",
                                                "2048"))
    hists = generate_corpus("basic", num_workflows=workflows,
                            seed=20260803, target_events=80)
    keys = [("bench", f"feed-append-{i}", "r") for i in range(workflows)]
    pack_cache = PackCache(max_size=workflows + 8)
    cache = ResidentStateCache(layout, ladder=EscalationLadder(layout),
                               budget_bytes=1 << 34)
    prefix_rows = [pack_cache.encode(k, h[:-1])
                   for k, h in zip(keys, hists)]
    corpus = assemble_corpus(prefix_rows,
                             max(r.shape[0] for r in prefix_rows))
    s = replay_events(jnp.asarray(corpus), layout)
    rows = np.asarray(payload_rows(s, layout))
    branch = np.asarray(s.current_branch)
    for i, k in enumerate(keys):
        cache.admit(k, content_address(hists[i][:-1]),
                    cache.extract_row(s, i), rows[i], int(branch[i]))
    items = [(k, h) for k, h in zip(keys, hists)]
    # warm the append shapes on a disjoint HALF (compile outside the
    # timed pass; warmed items would re-verify as exact hits and skew
    # it, and both halves pow2-bucket to the same launch shape so the
    # timed pass provably reuses the warmed executable)
    warm_n = workflows // 2
    feed_appends(items[:warm_n], cache, pack_cache)
    items = items[warm_n:]
    results, report = feed_appends(items, cache, pack_cache)
    history_events = int((corpus[warm_n:, :, LANE_EVENT_ID] > 0).sum()) \
        + report.events
    return {
        "workflows": len(items),
        "appended_events": report.events,
        "appended_events_per_sec": round(report.events_per_sec),
        "history_events_per_sec": round(history_events / report.wall_s
                                        if report.wall_s else 0.0),
        "chunks": report.chunks,
        "ok": int(sum(1 for r in results if r.ok)),
        "wall_s": round(report.wall_s, 3),
    }


def _serving_suite(layout, workflows: int = 0, target_events: int = 0,
                   levels=(1, 2, 4, 8)):
    """The device-serving transaction tier (engine/serving.py) measured
    at the scheduler seam: N submitter threads drive committed append
    transactions (each waits for its device parity result — offered
    concurrency == N), the scheduler coalesces them into shared
    from-state launches, and the suite records coalescing factor and
    latency percentiles per concurrency level. An UNBATCHED baseline
    (max_batch=1, zero window — one launch per transaction) runs at the
    top level so the micro-batching claim is a measured ratio, not a
    design note; tests/test_perf_gate.py TestServingGate pins
    batched p99 <= unbatched p99, factor > 1.5 at saturation, zero
    warm recompiles, zero parity divergence."""
    import threading

    from cadence_tpu.core.checksum import STICKY_ROW_INDEX, payload_row
    from cadence_tpu.engine.cache import batch_crc
    from cadence_tpu.engine.persistence import Stores
    from cadence_tpu.engine.serving import ServingScheduler
    from cadence_tpu.engine.tpu_engine import TPUReplayEngine
    from cadence_tpu.gen.corpus import generate_corpus
    from cadence_tpu.oracle.state_builder import StateBuilder
    from cadence_tpu.ops.replay import replay_from_state_to_payload
    from cadence_tpu.utils import metrics as cm

    workflows = workflows or int(os.environ.get("BENCH_SERVING_WORKFLOWS",
                                                "64"))
    target_events = target_events or int(
        os.environ.get("BENCH_SERVING_EVENTS", "96"))
    hists = generate_corpus("basic", num_workflows=workflows,
                            seed=20260803, target_events=target_events)
    # every level appends TWO batches per workflow (an untimed warm
    # round traces this level's stack/flush shapes, then the timed
    # round); the prefix leaves enough tail for all levels plus the
    # unbatched baseline
    appends_needed = 2 * len(levels) + 2
    min_batches = min(len(h) for h in hists)
    assert min_batches > appends_needed + 1, (min_batches, appends_needed)
    prefix = min_batches - appends_needed
    keys = [("bench", f"sv-{i}", "r") for i in range(workflows)]
    counts = {k: prefix for k in keys}
    by_key = {k: h for k, h in zip(keys, hists)}

    def read_batches(key):
        return by_key[key][:counts[key]]

    def expected_for(key):
        ms = StateBuilder().replay_history(read_batches(key))
        row = payload_row(ms, layout)
        row[STICKY_ROW_INDEX] = 0
        return row, int(ms.version_histories.current_index)

    registry = cm.DEFAULT_REGISTRY

    def make_scheduler(max_batch, max_wait_us):
        tpu = TPUReplayEngine(Stores(), layout)
        sched = ServingScheduler(tpu, max_batch=max_batch,
                                 max_wait_us=max_wait_us,
                                 read_batches=read_batches)
        sched.warm(e_shapes=(16, 32))
        # seed: one cold submit per workflow pins every prefix state
        for k in keys:
            row, br = expected_for(k)
            sched.submit(k, row, br, batch_crc(read_batches(k)[-1]))
        assert sched.drain(timeout=300.0)
        return sched

    def drive(sched, conc, wf_slice):
        """conc threads, each appending one batch per owned workflow and
        blocking on its parity ticket; returns sorted latencies."""
        lats, errs = [], []
        lock = threading.Lock()
        barrier = threading.Barrier(conc)
        shares = [wf_slice[i::conc] for i in range(conc)]

        def worker(share):
            barrier.wait()
            for k in share:
                counts[k] += 1
                row, br = expected_for(k)
                t0 = time.perf_counter()
                ticket = sched.submit(k, row, br,
                                      batch_crc(read_batches(k)[-1]))
                res = ticket.result(timeout=300.0)
                dt = time.perf_counter() - t0
                with lock:
                    lats.append(dt)
                    if not (res.ok and res.parity_ok):
                        errs.append(res)

        threads = [threading.Thread(target=worker, args=(s,))
                   for s in shares if s]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs[:3]
        lats.sort()
        return lats

    suite = {"workflows": workflows, "levels": [], "parity_divergence": 0}
    # max_batch pinned to the top concurrency level: warm() derives its
    # widths from it, so the suite pre-compiles exactly the flush shapes
    # the drive can produce (a wider batch would just warm more shapes)
    sched = make_scheduler(max_batch=max(levels), max_wait_us=4000)
    size0 = None
    for conc in levels:
        drive(sched, conc, keys)  # warm round: trace this level's shapes
        if size0 is None:
            # everything after the first level's warm round must reuse
            # the compiled from-state executables — zero warm recompiles
            size0 = replay_from_state_to_payload._cache_size()
        pre_txn = registry.counter(cm.SCOPE_TPU_SERVING, cm.M_SERVING_TXNS)
        pre_launch = registry.counter(cm.SCOPE_TPU_SERVING,
                                      cm.M_SERVING_LAUNCHES)
        lats = drive(sched, conc, keys)
        txns = registry.counter(cm.SCOPE_TPU_SERVING,
                                cm.M_SERVING_TXNS) - pre_txn
        launches = registry.counter(cm.SCOPE_TPU_SERVING,
                                    cm.M_SERVING_LAUNCHES) - pre_launch
        suite["levels"].append({
            "concurrency": conc,
            "txns": txns,
            "launches": launches,
            "coalescing_factor": round(txns / launches, 3) if launches
            else 0.0,
            "p50_ms": round(1e3 * lats[len(lats) // 2], 3),
            "p99_ms": round(1e3 * lats[min(len(lats) - 1,
                                           int(len(lats) * 0.99))], 3),
        })
    suite["warm_recompiles"] = (replay_from_state_to_payload._cache_size()
                                - size0)
    sched.stop()

    # unbatched baseline: one launch per transaction (max_batch=1, no
    # window) at the top concurrency — what the tier costs WITHOUT
    # micro-batching (warm round first, same as the batched levels)
    top = max(levels)
    unbatched = make_scheduler(max_batch=1, max_wait_us=0)
    drive(unbatched, top, keys)
    lats = drive(unbatched, top, keys)
    unbatched.stop()
    suite["unbatched"] = {
        "concurrency": top,
        "p50_ms": round(1e3 * lats[len(lats) // 2], 3),
        "p99_ms": round(1e3 * lats[min(len(lats) - 1,
                                       int(len(lats) * 0.99))], 3),
    }
    batched_top = next(lv for lv in suite["levels"]
                       if lv["concurrency"] == top)
    suite["batched_p99_ms"] = batched_top["p99_ms"]
    suite["unbatched_p99_ms"] = suite["unbatched"]["p99_ms"]
    suite["coalescing_factor_at_top"] = batched_top["coalescing_factor"]
    suite["parity_divergence"] = registry.counter(
        cm.SCOPE_TPU_SERVING, cm.M_SERVING_DIVERGENCE)
    suite["note"] = (
        "submitters block on per-transaction parity tickets, so offered "
        "concurrency == thread count; batched levels share one "
        "from-state launch per flush window, the unbatched baseline "
        "pays one launch per transaction")
    return suite


def _fuzz_suite(layout, trials: int = 0):
    """Promoted fuzz corpora as permanent bench suites (ROADMAP item 4):
    every fuzz_specs/*.json (written by `fuzz promote`, gen/fuzz.py
    CorpusSpec) regenerates byte-identically from its seed, replays on
    the wirec path for a timed rate, and parity-gates the CRCs against
    the oracle — a discovered adversarial structure stays both a perf
    input and a correctness gate. Empty when nothing is promoted."""
    import jax.numpy as jnp

    from cadence_tpu.core.checksum import crc32_of_row
    from cadence_tpu.gen import fuzz as fuzz_mod
    from cadence_tpu.native.wirec import pack_wirec_auto
    from cadence_tpu.ops.encode import LANE_EVENT_ID, encode_corpus
    from cadence_tpu.ops.replay import replay_wirec_to_crc

    trials = trials or int(os.environ.get("BENCH_TRIALS", "5"))
    table = {}
    for spec in fuzz_mod.load_specs(os.path.dirname(
            os.path.abspath(__file__))):
        histories = spec.generate()
        events_np = encode_corpus(histories)
        real = int((events_np[:, :, LANE_EVENT_ID] > 0).sum())
        corpus = pack_wirec_auto(events_np)
        arrs = (jnp.asarray(corpus.slab), jnp.asarray(corpus.bases),
                jnp.asarray(corpus.n_events))
        crc, errors = replay_wirec_to_crc(*arrs, corpus.profile, layout)
        crc = np.asarray(crc).astype(np.uint32)
        errors = np.asarray(errors)
        rates = []
        for _ in range(trials):
            t0 = time.perf_counter()
            c, e = replay_wirec_to_crc(*arrs, corpus.profile, layout)
            np.asarray(c)
            rates.append(real / (time.perf_counter() - t0))
        expected = np.array([
            crc32_of_row(fuzz_mod.oracle_final_row(h, layout))
            for h in histories], dtype=np.uint32)
        clean = errors == 0
        table[spec.name] = {
            "seed": spec.seed, "profile": spec.profile,
            "workflows": len(histories), "events": real,
            "digest": spec.digest[:12],
            "rate_median": round(statistics.median(rates)),
            "rate_min": round(min(rates)),
            "error_workflows": int((~clean).sum()),
            "crc_parity": bool((crc[clean] == expected[clean]).all()),
            "note": spec.note,
        }
    return table


def _replication_suite(layout):
    """Standby bulk apply (the multi-region standby's steady state): one
    seeded active-region corpus — serving tier on, mid-corpus forced
    sweep shipping snapshot records down the stream — published ONCE,
    then drained by two independent standby consumers off the same
    replication queue: the device twin ON (snapshot-seeded bulk apply,
    per-apply parity gate) and the CADENCE_TPU_REPL_DEVICE=0 kill-switch
    host-only path. Times each apply drain and byte-compares every
    replicated row across the two paths — the kill switch must restore
    the host-only result exactly."""
    from cadence_tpu.core.checksum import payload_row
    from cadence_tpu.engine.domainrepl import DomainReplicationProcessor
    from cadence_tpu.engine.multicluster import ReplicatedClusters
    from cadence_tpu.engine.onebox import Onebox
    from cadence_tpu.engine.replication import (
        HistoryReplicator,
        ReplicationTaskProcessor,
    )
    from cadence_tpu.models.deciders import SignalDecider
    from cadence_tpu.utils import metrics as cm

    domain, tl = "bench-repl", "bench-repl-tl"
    workflows = int(os.environ.get("BENCH_REPL_WORKFLOWS", "32"))
    signals = int(os.environ.get("BENCH_REPL_SIGNALS", "6"))

    # aggressive snapshot policy for the corpus (read at Snapshotter
    # construction, which happens inside ReplicatedClusters.__init__)
    knobs = {"CADENCE_TPU_SNAPSHOT_MIN_EVENTS": "1",
             "CADENCE_TPU_SNAPSHOT_EVERY_EVENTS": "4"}
    saved = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    try:
        clusters = ReplicatedClusters(num_hosts=1, num_shards=4)
        host_only = Onebox(num_hosts=1, num_shards=4,
                           cluster_name="standby")
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else \
                os.environ.__setitem__(k, v)
    clusters.active.enable_serving()
    clusters.register_global_domain(domain)
    wfs = [f"br-wf-{i}" for i in range(workflows)]
    deciders = {wf: SignalDecider(expected_signals=999) for wf in wfs}

    def drive(box):
        for _ in range(500):
            progressed = box.pump_once() > 0
            while True:
                resp = box.frontend.poll_for_decision_task(domain, tl)
                if resp is None:
                    break
                progressed = True
                box.frontend.respond_decision_task_completed(
                    resp.token,
                    deciders[resp.token.workflow_id].decide(resp.history))
            if not progressed and box.matching.backlog() == 0:
                return

    for wf in wfs:
        clusters.active.frontend.start_workflow_execution(
            domain, wf, "signal", tl)
    drive(clusters.active)
    for s in range(signals):
        for wf in wfs:
            clusters.active.frontend.signal_workflow_execution(
                domain, wf, f"{wf}-s{s}")
        drive(clusters.active)
        if s == signals // 2 - 1:
            # mid-corpus snapshot shipment: everything after this is the
            # suffix the standby's device twin applies on seeded keys
            clusters.active.serving.drain(timeout=60)
            clusters.active.tpu.snapshotter().sweep(force=True)
    clusters.active.serving.drain(timeout=60)
    clusters.active.serving.stop()

    clusters.domain_processor.process_once()
    DomainReplicationProcessor(clusters.active.stores, host_only.stores,
                               "standby").process_once()

    def timed_drain(proc):
        t0 = time.perf_counter()
        total = 0
        while True:
            n = proc.process_once(batch_size=100)
            total += n
            if n == 0:
                return total, time.perf_counter() - t0

    def events_applied(box):
        return sum(
            box.stores.execution.get_workflow(*key)
            .execution_info.next_event_id - 1
            for key in box.stores.history.list_runs())

    device_tasks, device_s = timed_drain(clusters.processor)
    host_proc = ReplicationTaskProcessor(
        HistoryReplicator(host_only.stores, rebuilder=host_only.rebuilder,
                          notifier=host_only.notifier),
        clusters.publisher, host_only.stores,
        source_history_reader=clusters._read_source_history,
        tpu=host_only.tpu)
    host_proc.metrics = host_only.metrics
    prev = os.environ.get("CADENCE_TPU_REPL_DEVICE")
    os.environ["CADENCE_TPU_REPL_DEVICE"] = "0"
    try:
        host_tasks, host_s = timed_drain(host_proc)
    finally:
        os.environ.pop("CADENCE_TPU_REPL_DEVICE", None) if prev is None \
            else os.environ.__setitem__("CADENCE_TPU_REPL_DEVICE", prev)

    rows, identical = 0, True
    for key in clusters.standby.stores.history.list_runs():
        a = payload_row(clusters.standby.stores.execution.get_workflow(*key))
        b = payload_row(host_only.stores.execution.get_workflow(*key))
        rows += 1
        if not (a == b).all():
            identical = False
    events = events_applied(clusters.standby)

    def repl_counter(reg, name):
        return reg.counter(cm.SCOPE_REPLICATION, name)

    dreg, hreg = clusters.standby.metrics, host_only.metrics
    return {
        "workflows": workflows, "signals_per_workflow": signals,
        "events_replicated": events, "rows_compared": rows,
        "device": {
            "tasks": device_tasks,
            "drain_s": round(device_s, 4),
            "events_per_sec": round(events / device_s) if device_s else 0,
            "applied": repl_counter(dreg, cm.M_REPL_DEVICE_APPLIED),
            "suffix_events": repl_counter(dreg,
                                          cm.M_REPL_DEVICE_SUFFIX_EVENTS),
            "cold": repl_counter(dreg, cm.M_REPL_DEVICE_COLD),
            "divergence": repl_counter(dreg, cm.M_REPL_DEVICE_DIVERGENCE),
            "snapshots_installed": repl_counter(dreg,
                                                cm.M_REPL_SNAP_INSTALLED),
        },
        "host_only": {
            "kill_switch": "CADENCE_TPU_REPL_DEVICE=0",
            "tasks": host_tasks,
            "drain_s": round(host_s, 4),
            "events_per_sec": round(events / host_s) if host_s else 0,
            "device_applied": repl_counter(hreg, cm.M_REPL_DEVICE_APPLIED),
            "snapshots_installed": repl_counter(hreg,
                                                cm.M_REPL_SNAP_INSTALLED),
        },
        "paths_byte_identical": identical,
    }


def main() -> None:
    ns_workflows = int(os.environ.get("BENCH_NS_WORKFLOWS", "1000000"))
    ns_events = int(os.environ.get("BENCH_NS_EVENTS", "1000"))
    ns_chunk = int(os.environ.get("BENCH_NS_CHUNK", "16384"))
    suite_workflows = int(os.environ.get("BENCH_SUITE_WORKFLOWS", "16384"))
    trials = int(os.environ.get("BENCH_TRIALS", "5"))
    parity_samples = int(os.environ.get("BENCH_PARITY_SAMPLES", "64"))
    seed = int(os.environ.get("BENCH_SEED", "20260730"))

    import jax

    from cadence_tpu.core.checksum import DEFAULT_LAYOUT
    from cadence_tpu.utils import compile_cache

    # persistent compilation cache (utils/compile_cache.py holds the one
    # rule for where): repeated bench invocations skip recompiles
    compile_cache.enable()
    layout = DEFAULT_LAYOUT
    n_devices = jax.device_count()

    north = _north_star(ns_workflows, ns_events, ns_chunk, seed,
                        parity_samples, layout)
    suites = _suite_table(trials, suite_workflows, layout)
    fallback = _fallback_suite(suite_workflows, layout)
    incremental = _incremental_suite(layout)
    snapshot = _snapshot_suite(layout)
    mesh_serving = _mesh_serving(
        int(os.environ.get("BENCH_MESH_WORKFLOWS", "4096")), layout)
    serving = _serving_suite(layout)
    cluster_serving = _cluster_serving(layout)
    visibility = _visibility_suite()
    feeder = _feeder_rate(layout)
    fuzz = _fuzz_suite(layout)
    replication = _replication_suite(layout)

    # observability snapshot: the profiler's pack/h2d/kernel/readback leg
    # decomposition (fed by the instrumented feeder path) plus every tpu.*
    # metric scope — so BENCH_r*.json trajectories diff leg-by-leg
    from cadence_tpu.utils import metrics as cm
    from cadence_tpu.utils.profiler import ReplayProfiler
    observability = {
        "profiler": ReplayProfiler().summary(),
        "metrics": {scope: values
                    for scope, values in cm.DEFAULT_REGISTRY.snapshot().items()
                    if scope.startswith("tpu.")},
    }

    rate_per_chip = north["rate"] / n_devices
    # the pod-scale north star: aggregate events/s across the whole mesh
    # (per-device efficiency rides detail.mesh_serving, measured through
    # the serving executor)
    north["events_per_sec_pod"] = round(north["rate"])
    # the cluster-scale north star: summed serving-tier append rate over
    # every simulated host's wall window (detail.cluster_serving)
    north["events_per_sec_cluster"] = \
        cluster_serving["events_per_sec_cluster"]
    north["rate"] = round(north["rate"])
    print(json.dumps({
        "metric": "replay_events_per_sec_per_chip",
        "value": round(rate_per_chip),
        "unit": "events/s/chip",
        "vs_baseline": round(rate_per_chip / BASELINE_PER_CHIP, 4),
        "detail": {
            "devices": n_devices,
            "platform": jax.devices()[0].platform,
            "north_star": north,
            "suites": suites,
            "fallback_under_pressure": fallback,
            "incremental": incremental,
            "snapshot": snapshot,
            "mesh_serving": mesh_serving,
            "serving": serving,
            "cluster_serving": cluster_serving,
            "visibility": visibility,
            "feeder": feeder,
            "fuzz": fuzz,
            "replication": replication,
            "observability": observability,
        },
    }))


if __name__ == "__main__":
    sys.exit(main())
