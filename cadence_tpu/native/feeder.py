"""Pipelined feeder: wire bytes → C++ packer → device replay chunks.

SURVEY §7 step 6 / §2.6 P7: the host must sustain the kernel's event rate,
so packing and replay overlap. The pipeline itself is the shared bulk
executor (engine/executor.py): a bounded pack THREAD POOL produces chunks
up to `depth` ahead of the device consumer into a ring of preallocated
buffers (no per-chunk allocation), the ring-slot reuse discipline blocks a
packer until the chunk that last used its slot has fully replayed, and the
consumer's `pack-queue-wait` profiler leg says which side of the pipeline
is starving. Every chunk shares one shape and, short of a refit, one
wirec profile, so a single compiled executable serves the whole stream.

Two host→device formats: wirec (ops/wirec.py) is the transfer format, and
`feed_serialized_wirec` its one pipelined loop; the dense int64 lanes
(ops/replay.py `replay_to_payload`) are the reference, which rebuild and
the serving tier also replay.

`FeedReport` carries the sustained end-to-end rate next to the packer's
standalone rate so the pipeline's overhead is always measured.
"""
from __future__ import annotations

import time
from concurrent.futures import Future
from dataclasses import dataclass
from threading import Lock
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..core.checksum import DEFAULT_LAYOUT, PayloadLayout
from ..engine.executor import BulkReplayExecutor
from ..utils import metrics as m
from ..utils import tracing
from ..utils.profiler import ReplayProfiler
from . import packing


@dataclass
class FeedReport:
    workflows: int = 0
    events: int = 0
    chunks: int = 0
    wall_s: float = 0.0
    pack_s: float = 0.0
    #: pipeline shape + producer/consumer balance: time the device
    #: consumer stalled waiting on the pack pool (engine/executor.py)
    depth: int = 0
    pack_queue_wait_s: float = 0.0
    #: host compression cost and wire density
    compress_s: float = 0.0
    wire_bytes: int = 0
    profile_refits: int = 0
    #: which encoder packed the chunks (native C++ fused pass vs the
    #: byte-identical pure-Python path) and what the staged host→device
    #: handoff cost (the pinned-buffer H2D seconds)
    native_wirec: bool = False
    h2d_s: float = 0.0
    #: whole decodes of a chunk's wire blobs that its pack was made of.
    #: The native encoder keeps no lane tensor, so a chunk packed with no
    #: profile (chunk 0, each refit) is decoded twice, measure then emit:
    #: chunks + 1 + refits; the pinned attempt a refit abandons is not
    #: counted. The Python encoder decodes once a chunk.
    decode_passes: int = 0
    #: capacity-escalation ladder inside the call.
    #: Every rung counted: the rows the rungs replayed, their lanes (rows
    #: after the pow2 padding), the real events and the wire bytes of the
    #: gathered sub-corpora. Each flagged row once: resolved by a rung, or
    #: residual (no rung resolved it: it keeps its error code), and its
    #: index in the call's results. `ladder_s` is the consumer thread's
    #: host time inside the ladder. `events` counts no event twice.
    ladder_rows: int = 0
    ladder_lanes: int = 0
    ladder_events: int = 0
    ladder_wire_bytes: int = 0
    ladder_resolved: int = 0
    ladder_residual: int = 0
    ladder_s: float = 0.0
    ladder_indices: Sequence[int] = ()

    @property
    def events_per_sec(self) -> float:
        return self.events / self.wall_s if self.wall_s else 0.0

    @property
    def pack_events_per_sec(self) -> float:
        return self.events / self.pack_s if self.pack_s else 0.0

    @property
    def bytes_per_event(self) -> float:
        return self.wire_bytes / self.events if self.events else 0.0


#: serialized empty history (0 batches) — pads the tail chunk to the
#: steady shape so one executable serves every chunk
_EMPTY_BLOB = b"\x00\x00\x00\x00"


def _resolve_mesh(mesh):
    """Serving-mesh resolution for the ingest pipeline: an explicit mesh
    wins; otherwise the CADENCE_TPU_MESH_DEVICES knob decides — unset
    (the default 1) keeps the exact single-device placement path, any
    other value shards every chunk over the mesh's 'shard' axis with
    per-device slice copies."""
    if mesh is not None:
        return mesh
    from ..parallel.mesh import mesh_devices_requested, serving_mesh
    return serving_mesh() if mesh_devices_requested() != 1 else None


def _mesh_chunk(chunk_workflows: int, mesh) -> int:
    """Round the chunk width up to a whole slice per device."""
    if mesh is None:
        return chunk_workflows
    n = int(mesh.devices.size)
    return -(-chunk_workflows // n) * n


def _chunk_blobs(blobs: Sequence[bytes], lo: int,
                 chunk_workflows: int) -> List[bytes]:
    chunk = list(blobs[lo:lo + chunk_workflows])
    pad = chunk_workflows - len(chunk)
    if pad:
        chunk.extend([_EMPTY_BLOB] * pad)
    return chunk


@tracing.spanned("feed.call")
def feed_serialized_wirec(blobs: Sequence[bytes], max_events: int,
                          chunk_workflows: int = 4096,
                          layout: PayloadLayout = DEFAULT_LAYOUT,
                          num_threads: Optional[int] = None,
                          depth: Optional[int] = None, mesh=None,
                          registry=None
                          ) -> Tuple[np.ndarray, np.ndarray, FeedReport]:
    """The COMPRESSED ingest pipeline: wire bytes → wirec adaptive-
    columnar buffers (~10-18 B/event, ops/wirec.py) → H2D → device
    decode+replay+checksum → 4 bytes/workflow back.

    Two host encoders serve the pack stage, byte-identical by contract
    (tests/test_native_packer.py fuzzes the parity): the NATIVE pipeline
    (native/wirec.cc via CADENCE_TPU_NATIVE_WIREC, default on when the
    .so is loadable) turns wire blobs into wirec buffers a ROW at a
    time — each workflow decoded into a one-row scratch and emitted from
    there, no [W, E, L] lane tensor on the way — in ONE multi-threaded
    C++ call per chunk, staging straight into preallocated ring-slot
    buffers (WirecBuffers — zero Python-side allocation per chunk) that
    hand off to the device through stage_corpus (dlpack where the
    backend accepts it); the pure-Python fallback is the original
    pack_serialized + pack_wirec pair over a dense tensor. Which encoder
    served is a /metrics scrape (tpu.native/*) and rides the report's
    native_wirec flag.

    The wirec profile is measured on the FIRST chunk and pinned so every
    chunk shares one executable; a later chunk whose values fall outside
    the pinned widths triggers a refit (recompute + recompile, and the
    refreshed plan becomes the pin for chunks packed after it) — counted
    in the report, never silent. Both encoders measure profiles with the
    identical decision procedure, so pin/refit behavior cannot depend on
    which one served. The native encoder measures by decoding the chunk
    once more (span `pack.measure`): `decode_passes` and the
    tpu.native/decode-passes counter say what that cost.

    Rows the base pass flags with a CAPACITY error (a workflow holding
    more pending items than the device's tables) are resolved inside the
    call, always, through the widened-K ladder (engine/ladder.py): each
    chunk's flagged rows are copied out of its buffers before its ring
    slot can be packed over, rung 1 is dispatched as the chunk is read
    back — it overlaps later chunks' pack and replay — and after the last
    chunk rung 1 is collected, rungs ≥ 2 run once over every chunk's
    survivors, and resolved rows' CRCs and error words replace the base
    pass's. A row no rung resolves keeps its error code and is counted
    (`ladder_residual`); nothing here calls the Python oracle. A corpus
    that fits the tables pays one np.isin over each chunk's error words."""
    import jax

    from ..engine.ladder import SPAN_GATHER, EscalationLadder
    from ..ops.replay import replay_wirec_to_crc
    from ..ops.wirec import ProfileMisfit, gather_corpus, pack_wirec
    from ..utils.concurrency import pack_threads
    from . import wirec as nwirec

    # what a call does before its first pack task exists: the executor
    # and the ring's staging buffers
    with tracing.span("feed.setup"):
        mesh = _resolve_mesh(mesh)
        chunk_workflows = _mesh_chunk(chunk_workflows, mesh)
        total = len(blobs)
        registry = registry if registry is not None else m.DEFAULT_REGISTRY
        executor = BulkReplayExecutor(depth=depth, mesh=mesh,
                                      registry=registry)
        use_native = nwirec.wirec_native_enabled(registry)
        report = FeedReport(workflows=total, depth=executor.depth,
                            native_wirec=use_native)
        prof = ReplayProfiler()
        ladder = EscalationLadder(layout, registry=registry, mesh=mesh)
        n_chunks = -(-total // chunk_workflows) if total else 0
        # intra-chunk wirec threads: the one CADENCE_TPU_PACK_THREADS knob,
        # split across the pack pool's concurrent workers
        wirec_threads = (num_threads if num_threads is not None
                         else max(1, pack_threads() // executor.depth))
        if use_native:
            # reusable staging: the wirec output triple per ring slot,
            # fully overwritten by every emit (no zeroing, no per-chunk
            # allocation) — the pinned host buffers the H2D stages from
            buffers = [nwirec.WirecBuffers(chunk_workflows, max_events)
                       for _ in range(executor.depth)]
        else:
            buffers = [np.empty((chunk_workflows, max_events,
                                 packing.NUM_LANES), dtype=np.int64)
                       for _ in range(executor.depth)]

    # chunk 0 measures the profile; later pack tasks pin the latest plan
    # (a refit replaces it under the lock)
    first_profile: Future = Future()
    state_lock = Lock()
    shared = {"profile": None, "refits": 0, "decode_passes": 0,
              "pack_s": 0.0, "compress_s": 0.0,
              "events": 0, "wire_bytes": 0, "h2d_s": 0.0}

    def _encode_native(ci, chunk, slot):
        """Streamed native chunk: blobs → wirec a row at a time (decode
        + compress are one pass, so pack_s carries the whole host cost
        and compress_s stays 0). Returns (corpus, compress seconds,
        decode passes)."""
        if ci == 0:
            corpus, _ = nwirec.pack_serialized_wirec(
                chunk, max_events, num_threads=wirec_threads, out=slot)
            with state_lock:
                shared["profile"] = corpus.profile
            first_profile.set_result(corpus.profile)
            return corpus, 0.0, 2
        with tracing.span("pack.first-profile-wait"):
            first_profile.result()
        with state_lock:
            pinned = shared["profile"]
        try:
            corpus, _ = nwirec.pack_serialized_wirec(
                chunk, max_events, profile=pinned,
                num_threads=wirec_threads, out=slot)
        except ProfileMisfit:
            # refit: fresh plan, recompile; later chunks pin it. The
            # streamed pass keeps no decoded lanes, so the chunk is
            # packed again the way chunk 0 is: measure, then emit
            corpus, _ = nwirec.pack_serialized_wirec(
                chunk, max_events, num_threads=wirec_threads, out=slot)
            with state_lock:
                shared["profile"] = corpus.profile
                shared["refits"] += 1
            return corpus, 0.0, 2
        return corpus, 0.0, 1

    def _encode_python(ci, chunk, slot):
        packed = packing.pack_serialized(chunk, max_events,
                                         num_threads=num_threads,
                                         out=slot)
        t1 = time.perf_counter()
        if ci == 0:
            corpus = pack_wirec(packed, num_threads=wirec_threads)
            with state_lock:
                shared["profile"] = corpus.profile
            first_profile.set_result(corpus.profile)
        else:
            with tracing.span("pack.first-profile-wait"):
                first_profile.result()
            with state_lock:
                pinned = shared["profile"]
            try:
                corpus = pack_wirec(packed, profile=pinned,
                                    num_threads=wirec_threads)
            except ProfileMisfit:
                # refit: fresh plan, recompile; later chunks pin it
                corpus = pack_wirec(packed, num_threads=wirec_threads)
                with state_lock:
                    shared["profile"] = corpus.profile
                    shared["refits"] += 1
        return corpus, time.perf_counter() - t1, 1

    def pack(ci):
        chunk = _chunk_blobs(blobs, ci * chunk_workflows, chunk_workflows)
        slot = buffers[ci % executor.depth]
        t0 = time.perf_counter()
        try:
            if use_native:
                corpus, compress_dt, passes = _encode_native(ci, chunk, slot)
            else:
                corpus, compress_dt, passes = _encode_python(ci, chunk, slot)
        except BaseException as exc:
            if ci == 0 and not first_profile.done():
                first_profile.set_exception(exc)
            raise
        pack_dt = time.perf_counter() - t0 - compress_dt
        registry.inc(m.SCOPE_TPU_NATIVE,
                     m.M_NATIVE_PACKS if use_native
                     else m.M_NATIVE_PY_PACKS)
        registry.inc(m.SCOPE_TPU_NATIVE, m.M_NATIVE_DECODE_PASSES, passes)
        with state_lock:
            shared["decode_passes"] += passes
            shared["pack_s"] += pack_dt
            shared["compress_s"] += compress_dt
            shared["events"] += int(corpus.n_events.sum())
            shared["wire_bytes"] += corpus.wire_bytes
        # compression is part of the host pack cost in this pipeline
        # (the executor already recorded the full pack task; fold the
        # split into the report fields instead)
        corpora[ci] = corpus
        return corpus

    def launch(ci, corpus):
        with prof.leg(m.M_PROFILE_H2D):
            t0 = time.perf_counter()
            if mesh is not None:
                from ..parallel.mesh import shard_wirec
                parts = shard_wirec(corpus, mesh)
            else:
                parts = nwirec.stage_corpus(corpus)
            with state_lock:
                shared["h2d_s"] += time.perf_counter() - t0
            prof.h2d(corpus.wire_bytes)
        outs = replay_wirec_to_crc(*parts, corpus.profile, layout)
        # the error words start for the host as soon as the chunk has
        # replayed: whichever thread retires the chunk (a packer, where
        # the packers set the pace) then reads them without a round trip
        # to the device of its own
        outs[1].copy_to_host_async()
        return outs

    def consume(ci, outs):
        with prof.leg(m.M_PROFILE_KERNEL):
            jax.block_until_ready(outs)
        with prof.leg(m.M_PROFILE_READBACK):
            return np.asarray(outs[0]), np.asarray(outs[1])

    #: chunk ci's packed corpus (the native path's are views of ring slot
    #: ci % depth) until the chunk retires; its capacity-flagged rows,
    #: copied out, until the consumer dispatches them; what it dispatched
    corpora: List[Optional[object]] = [None] * n_chunks
    salvaged: dict = {}
    pending: list = []

    def retire(ci, outs):
        # the executor calls this once a chunk, before anything can pack
        # over its ring slot, on whichever thread gets there first
        corpus, corpora[ci] = corpora[ci], None
        flagged = ladder.capacity_flagged(outs[1])
        if len(flagged):
            with tracing.span(SPAN_GATHER):
                salvaged[ci] = (flagged, gather_corpus(corpus, flagged))

    def escalate(ci, out):
        if ci in salvaged:
            flagged, sub = salvaged.pop(ci)
            pending.append((ci * chunk_workflows + flagged,
                            ladder.submit(sub)))
        return out

    start = time.perf_counter()
    results, prep = executor.run(n_chunks, pack, launch, consume, escalate,
                                 retire)
    with tracing.span("feed.gather"):
        first = np.concatenate([r for r, _ in results])[:total]
        errors = np.concatenate([e for _, e in results])[:total]
    report.ladder_s = prep.escalate_s
    if pending:
        t0 = time.perf_counter()
        outcomes = ladder.finish([p for _, p in pending])
        with tracing.span("feed.ladder.patch"):
            for (rows, _), o in zip(pending, outcomes):
                first[rows[o.resolved]] = o.rows[o.resolved]
                errors[rows[o.resolved]] = 0
                report.ladder_resolved += int(o.resolved.sum())
            report.ladder_indices = np.concatenate(
                [rows for rows, _ in pending])
        report.ladder_residual = (len(report.ladder_indices)
                                  - report.ladder_resolved)
        for rung in ladder.last_run:
            report.ladder_rows += rung["rows"]
            report.ladder_lanes += rung["lanes"]
            report.ladder_events += rung["events"]
            report.ladder_wire_bytes += rung["wire_bytes"]
        report.ladder_s += time.perf_counter() - t0
    report.chunks = prep.chunks
    report.pack_queue_wait_s = prep.pack_queue_wait_s
    report.pack_s = shared["pack_s"]
    report.compress_s = shared["compress_s"]
    report.events = shared["events"]
    report.wire_bytes = shared["wire_bytes"]
    report.profile_refits = shared["refits"]
    report.decode_passes = shared["decode_passes"]
    report.h2d_s = shared["h2d_s"]
    report.wall_s = time.perf_counter() - start
    return first, errors, report


def feed_corpus_wirec(histories, chunk_workflows: int = 4096,
                      layout: PayloadLayout = DEFAULT_LAYOUT,
                      max_events: int = 0,
                      depth: Optional[int] = None, mesh=None
                      ) -> Tuple[np.ndarray, np.ndarray, FeedReport]:
    """Convenience: serialize + feed a corpus through the compressed
    wirec pipeline."""
    from ..core.codec import serialize_corpus
    from ..ops.encode import history_length

    if max_events <= 0:
        max_events = max(history_length(h) for h in histories)
    return feed_serialized_wirec(serialize_corpus(histories), max_events,
                                 chunk_workflows, layout, depth=depth,
                                 mesh=mesh)
