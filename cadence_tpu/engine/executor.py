"""Pipelined, MESH-AWARE bulk-replay executor: the ONE hot path every
bulk consumer shares (engine/tpu_engine.py, engine/rebuild.py,
native/feeder.py) — and, since ISSUE 7, the one sharded code path the
dryrun_multichip scaling diagnostic exercises too.

With one thread packing, the device idles most of a bulk replay waiting
on the host. The fix is a producer/consumer pipeline:

- a bounded pack THREAD POOL produces host chunks ahead of the device
  consumer — the double-buffer reuse discipline the feeder used at
  depth 2 (VERDICT r3 weak #1) generalized to depth N: the pack task
  for chunk `ci` first blocks until chunk `ci - depth`'s device outputs
  exist, so a ring slot is never overwritten while its H2D copy can
  still be in flight, and the dispatch queue stays bounded at `depth`
  chunks;
- the consumer launches chunks strictly in order (JAX async dispatch
  returns immediately) and records a `pack-queue-wait` profiler leg for
  every chunk: that leg growing means the host packers are starving the
  device; near-zero means the device is the bottleneck. Either way a
  /metrics scrape now says which SIDE of the pipeline to fix;
- an optional per-chunk `consume` callback reads chunk results back with
  lag 1 behind the launch head, so device outputs never accumulate
  across the whole run (bounding HBM for many-chunk corpora).

Pool sizing: one worker per ring slot. A pack task blocked on its ring
slot parks its worker — exactly the backpressure wanted: when the device
is behind, packers wait; when packing is behind, all `depth` workers
pack concurrently (and the chunk-parallel packers below them fan out
further across cores).

Mesh awareness (ISSUE 7): constructed with a `parallel/mesh.py` mesh,
the executor serves from N devices — each chunk's workflow axis is
partitioned over the mesh's 'shard' axis (the same axis the reference's
shard controller spreads per-workflow state machines across hosts), the
H2D stage splits into per-device slice copies (place_corpus), and the
ring discipline generalizes per device: a ring slot frees only when the
chunk that last used it has fully replayed on EVERY shard of the mesh,
so no device's in-flight slice copy can be overwritten. Per-device
observability lands under `tpu.executor/*` (chunks-dispatched and
launches-in-flight carry a -dev{d} series per mesh position) next to the
aggregate pack-queue-wait. A mesh of 1 is byte-identical to the
single-chip executor — the serving path and the multichip diagnostic
are the same code at every N.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from threading import Lock
from typing import Any, Callable, List, Optional

from ..utils import metrics as m
from ..utils.profiler import ReplayProfiler

#: pipeline depth (ring slots / max chunks in flight); >2 lets the pack
#: pool run ahead of the device by more than one chunk
DEPTH_ENV = "CADENCE_TPU_PIPELINE_DEPTH"
DEFAULT_DEPTH = 3

#: the timeline name of the consumer's wait on chunk 0, in which nothing
#: is in flight yet (its histogram stays pack-queue-wait)
FIRST_CHUNK_WAIT_SPAN = "feed.first-chunk-wait"


def pipeline_depth(depth: Optional[int] = None) -> int:
    """Resolve the pipeline depth: explicit arg > env > default; min 2
    (depth 1 would serialize pack and replay again)."""
    if depth is None:
        depth = int(os.environ.get(DEPTH_ENV, str(DEFAULT_DEPTH)))
    return max(2, depth)


@dataclass
class PipelineReport:
    """Per-run pipeline accounting (FeedReport feeds from this)."""

    chunks: int = 0
    depth: int = 0
    pack_s: float = 0.0             # summed host pack seconds (inside pack_fn)
    pack_queue_wait_s: float = 0.0  # consumer stalled on the pack pipeline
    escalate_s: float = 0.0         # summed retire_fn + escalate_fn seconds
                                    # on the consumer (host side of
                                    # capacity-escalation dispatch)
    wall_s: float = 0.0


class BulkReplayExecutor:
    """Depth-N pack→device pipeline over ordered chunks.

    run() drives three caller hooks:
      pack_fn(ci) -> packed     host-side pack of chunk ci; runs on a pool
                                thread. The executor guarantees chunk
                                ci - depth's device outputs are ready
                                before pack_fn(ci) starts, so pack_fn may
                                reuse ring buffer `ci % depth` freely.
      launch_fn(ci, packed)     dispatch chunk ci to the device (async);
                                returns the device output pytree.
      consume_fn(ci, out)       optional; called in launch order with lag
                                1 behind the newest launch — block/read
                                back here so only O(depth) chunk outputs
                                are ever live.
      escalate_fn(ci, out)      optional (requires consume_fn); called
                                right after consume_fn(ci) with its
                                result, in the same launch order. The
                                capacity-escalation seam: inspect the
                                read-back error lanes and DISPATCH any
                                widened-K re-replay asynchronously here
                                (engine/ladder.py submit) — the pack pool
                                keeps producing up to `depth` chunks
                                ahead the whole time, so escalation never
                                stalls the pack pipeline. Its return
                                value replaces the chunk's output.
      retire_fn(ci, out)        optional; runs exactly ONCE per chunk, with
                                chunk ci's device outputs ready, before
                                anything can write over ring slot
                                ci % depth: on the pack thread that is
                                about to reuse the slot, or on the consumer
                                between consume_fn(ci) and escalate_fn(ci),
                                whichever gets there first. It is the last
                                moment pack_fn(ci)'s host buffers are
                                intact: what a later step needs of them
                                (the ladder's flagged rows) is copied out
                                here, and no packer ever waits on the
                                consumer for it.
    """

    def __init__(self, depth: Optional[int] = None,
                 registry=None, scope: str = m.SCOPE_TPU_REPLAY,
                 mesh=None) -> None:
        self.depth = pipeline_depth(depth)
        self.registry = registry if registry is not None else m.DEFAULT_REGISTRY
        self.scope = scope
        #: device mesh the chunks fan across (None = single-device, no
        #: per-device metric series)
        self.mesh = mesh
        self._n_dev = int(mesh.devices.size) if mesh is not None else 0

    def run(self, num_chunks: int,
            pack_fn: Callable[[int], Any],
            launch_fn: Callable[[int, Any], Any],
            consume_fn: Optional[Callable[[int, Any], Any]] = None,
            escalate_fn: Optional[Callable[[int, Any], Any]] = None,
            retire_fn: Optional[Callable[[int, Any], None]] = None
            ) -> tuple:
        """Returns (outputs, PipelineReport); outputs[ci] is the last
        hook's return value (escalate_fn over consume_fn over
        launch_fn's device output)."""
        import jax

        prof = ReplayProfiler(self.registry, scope=self.scope)
        report = PipelineReport(depth=self.depth)
        exec_scope = self.registry.scope(m.SCOPE_TPU_EXECUTOR)
        in_flight = [0]

        def busy(delta: int) -> None:
            # chunks launched and not yet read back: a host-side count,
            # not a device share. In SPMD every mesh position carries a
            # slice of each in-flight chunk, so the per-device series
            # share the value — the point is the LABELS exist for
            # dashboards keyed by device
            in_flight[0] += delta
            exec_scope.gauge(m.M_EXEC_IN_FLIGHT, float(in_flight[0]))
            for d in range(self._n_dev):
                exec_scope.gauge(m.device_metric(m.M_EXEC_IN_FLIGHT, d),
                                 float(in_flight[0]))

        outs: List[Any] = [None] * num_chunks
        #: ci -> Future resolved with chunk ci's device outputs once
        #: launched; pack tasks block on ci - depth here (ring discipline)
        launched = {ci: Future() for ci in range(num_chunks)}
        retire_locks = [Lock() for _ in range(num_chunks)] \
            if retire_fn is not None else []
        retired = [False] * len(retire_locks)

        def retire(ci: int, out: Any) -> None:
            with retire_locks[ci]:
                if not retired[ci]:
                    retired[ci] = True
                    retire_fn(ci, out)

        def consume(ci: int) -> None:
            raw = outs[ci]
            out = consume_fn(ci, raw)
            t0 = time.perf_counter()
            if retire_fn is not None:
                retire(ci, raw)
            if escalate_fn is not None:
                out = escalate_fn(ci, out)
            report.escalate_s += time.perf_counter() - t0
            outs[ci] = out
            busy(-1)

        def pack_task(ci: int):
            if ci >= self.depth:
                # the ring slot frees only when the chunk that last used
                # it has fully replayed (its outputs existing implies the
                # input transfer was consumed — overwriting the host
                # buffer can no longer corrupt an in-flight H2D copy).
                # Popped (AFTER the result exists — the consumer still
                # has to set it) so the output pytree is dropped as soon
                # as the slot frees: only O(depth) chunk outputs stay
                # live. Deliberately NOT a kernel-leg observation —
                # consume_fn records the kernel leg exactly once per
                # chunk.
                prior = launched[ci - self.depth].result()
                jax.block_until_ready(prior)
                if retire_fn is not None and prior is not None:
                    retire(ci - self.depth, prior)
                del prior
                launched.pop(ci - self.depth, None)
            with prof.leg(m.M_PROFILE_PACK) as leg:
                packed = pack_fn(ci)
            return packed, leg.duration_s

        t_start = time.perf_counter()
        with ThreadPoolExecutor(
                max_workers=self.depth,
                thread_name_prefix="cadence-pack") as pool:
            futs = [pool.submit(pack_task, ci) for ci in range(num_chunks)]
            try:
                for ci in range(num_chunks):
                    with prof.leg(m.M_PROFILE_PACK_WAIT,
                                  span=FIRST_CHUNK_WAIT_SPAN
                                  if ci == 0 else None) as leg:
                        packed, pack_dt = futs[ci].result()
                    wait = leg.duration_s
                    report.pack_queue_wait_s += wait
                    self.registry.observe(m.SCOPE_TPU_EXECUTOR,
                                          m.M_PROFILE_PACK_WAIT, wait)
                    report.pack_s += pack_dt
                    out = launch_fn(ci, packed)
                    outs[ci] = out
                    launched[ci].set_result(out)
                    report.chunks += 1
                    exec_scope.inc(m.M_EXEC_CHUNKS)
                    for d in range(self._n_dev):
                        exec_scope.inc(m.device_metric(m.M_EXEC_CHUNKS, d))
                    busy(+1)
                    if consume_fn is not None and ci >= 1:
                        # lag-1 readback: chunk ci is in flight while
                        # chunk ci-1 is pulled, and outputs never pile up
                        consume(ci - 1)
                if consume_fn is not None and num_chunks:
                    consume(num_chunks - 1)
            finally:
                # a pack/launch failure must not wedge pool shutdown:
                # unblock every pack task still waiting on a launch that
                # will never happen (block_until_ready(None) is a no-op)
                for f in futs:
                    f.cancel()
                for fut in list(launched.values()):
                    if not fut.done():
                        fut.set_result(None)
                # consume-less runs (and error exits) still settle the
                # busy gauge: run() returning means nothing is tracked
                # in flight anymore
                if in_flight[0]:
                    busy(-in_flight[0])
        report.wall_s = time.perf_counter() - t_start
        return outs, report


# ---------------------------------------------------------------------------
# The mesh-aware serving paths — ONE code path at every device count.
# replay_corpus_mesh serves a packed dense corpus from N devices through
# the pipelined executor above; stream_wirec_mesh does the same for a
# compressed wirec corpus reduced to CRCs on device. chip_smoke.py's
# bulk and mesh phases, __graft_entry__.dryrun_multichip's scaling
# diagnostic, and tests/test_mesh_executor.py all call these two
# functions, so the diagnostic and the serving path can never drift.
# ---------------------------------------------------------------------------


def replay_corpus_mesh(events, mesh=None, layout=None,
                       chunk_workflows: Optional[int] = None,
                       depth: Optional[int] = None, registry=None,
                       variants=None):
    """Serve a packed [W, E, L] int64 corpus from the device mesh:
    chunks fan across the mesh's 'shard' axis (per-device H2D slice
    copies, per-device ring discipline via the executor), replay +
    canonical payload run SPMD, and the host reads back rows/errors/
    branch per chunk with the usual lag-1 bound.

    Returns (payload rows [W, width], errors [W], current branch [W],
    PipelineReport). A mesh of 1 (the default, CADENCE_TPU_MESH_DEVICES
    unset) is byte-identical to the pre-mesh single-chip executor;
    any mesh shape yields identical per-workflow rows — sharding the
    workflow axis never changes a row's result.

    Compiled (shape, mesh-size) variants register in the kernel-variant
    cache under tpu.executor/* hit/miss counters, so a warm run across
    mesh shapes already seen provably recompiles nothing."""
    import jax
    import numpy as np

    from ..core.checksum import DEFAULT_LAYOUT
    from ..ops.encode import LANE_EVENT_ID, LANE_EVENT_TYPE
    from ..parallel.mesh import place_corpus, serving_mesh
    from ..utils import compile_cache

    if layout is None:
        layout = DEFAULT_LAYOUT
    if mesh is None:
        mesh = serving_mesh()
    if variants is None:
        variants = compile_cache.DEFAULT_VARIANTS
    registry = registry if registry is not None else m.DEFAULT_REGISTRY
    events = np.asarray(events)
    W, E = int(events.shape[0]), int(events.shape[1])
    n = int(mesh.devices.size)
    if W == 0:
        return (np.zeros((0, layout.width), np.int64),
                np.zeros((0,), np.int32), np.zeros((0,), np.int32),
                PipelineReport())
    if chunk_workflows is None:
        chunk_workflows = int(os.environ.get("CADENCE_TPU_REPLAY_CHUNK",
                                             "4096"))
    # every chunk shares one padded [Wc, E, L] shape, Wc a multiple of
    # the mesh so each device owns a whole slice of every chunk
    Wc = -(-max(1, min(chunk_workflows, W)) // n) * n
    spans = [(lo, min(lo + Wc, W)) for lo in range(0, W, Wc)]
    executor = BulkReplayExecutor(depth=depth, registry=registry, mesh=mesh)
    prof = ReplayProfiler(registry, scope=m.SCOPE_TPU_EXECUTOR)
    exec_scope = registry.scope(m.SCOPE_TPU_EXECUTOR)

    key = ("serve-dense", layout, Wc, E, n)

    def build():
        from ..ops.replay import replay_to_payload_branch

        return lambda ev: replay_to_payload_branch(ev, layout)

    fn = variants.get(key, build, registry, scope=m.SCOPE_TPU_EXECUTOR)

    def pack(ci):
        lo, hi = spans[ci]
        sub = events[lo:hi]
        if sub.shape[0] < Wc:
            pad = np.zeros((Wc - sub.shape[0], E, events.shape[2]),
                           dtype=events.dtype)
            pad[:, :, LANE_EVENT_TYPE] = -1
            sub = np.concatenate([sub, pad])
        if n > 1:
            # real rows per device slice (the skew-visibility counter),
            # scanned HERE in the overlapped pack pool — never on the
            # serial dispatch path the mesh gate times. Meaningless on a
            # mesh of 1, so not computed there.
            slice_w = Wc // n
            for d in range(n):
                rows_d = int((sub[d * slice_w:(d + 1) * slice_w, :,
                                  LANE_EVENT_ID] > 0).any(axis=1).sum())
                exec_scope.inc(m.device_metric(m.M_EXEC_ROWS, d), rows_d)
        return sub

    def launch(ci, sub):
        with prof.leg(m.M_PROFILE_H2D):
            dev = place_corpus(sub, mesh)
            prof.h2d(sub.nbytes)
        return fn(dev)

    def consume(ci, outs):
        with prof.leg(m.M_PROFILE_KERNEL):
            jax.block_until_ready(outs)
        with prof.leg(m.M_PROFILE_READBACK):
            r, e, b = outs
            return np.asarray(r), np.asarray(e), np.asarray(b)

    results, report = executor.run(len(spans), pack, launch, consume)
    rows = np.concatenate([r for r, _, _ in results])[:W]
    errors = np.concatenate([e for _, e, _ in results])[:W]
    branch = np.concatenate([b for _, _, b in results])[:W]
    return rows, errors, branch, report


def stream_wirec_mesh(corpus, mesh=None, layout=None, n_chunks: int = 1,
                      depth: Optional[int] = None, registry=None):
    """Stream a packed wirec corpus through the mesh-aware executor in
    `n_chunks` workflow chunks: each chunk's compressed slab splits into
    per-device slice copies whose H2D overlaps the previous chunk's
    sharded replay, and the device reduces to CRC32s (4 bytes/workflow
    back). `n_chunks` must divide W and keep shards whole.

    Returns (crc32 [W] uint32, errors [W], PipelineReport)."""
    import jax
    import numpy as np

    from ..core.checksum import DEFAULT_LAYOUT
    from ..ops.wirec import WirecCorpus
    from ..parallel.mesh import (
        _replay_wirec_crc_with_stats,
        serving_mesh,
        shard_wirec,
    )

    if layout is None:
        layout = DEFAULT_LAYOUT
    if mesh is None:
        mesh = serving_mesh()
    registry = registry if registry is not None else m.DEFAULT_REGISTRY
    W = int(corpus.slab.shape[0])
    n = int(mesh.devices.size)
    assert n_chunks >= 1 and W % n_chunks == 0, (W, n_chunks)
    step = W // n_chunks
    assert step % n == 0, (step, n)
    chunks = [WirecCorpus(corpus.slab[lo:lo + step],
                          corpus.bases[lo:lo + step],
                          corpus.n_events[lo:lo + step], corpus.profile)
              for lo in range(0, W, step)]
    executor = BulkReplayExecutor(depth=depth, registry=registry, mesh=mesh)

    def pack(ci):
        return chunks[ci]

    def launch(ci, c):
        parts = shard_wirec(c, mesh)
        return _replay_wirec_crc_with_stats(*parts, c.profile, layout)

    def consume(ci, outs):
        jax.block_until_ready(outs)
        crc, errors, _stats = outs
        return (np.asarray(crc).astype(np.uint32), np.asarray(errors))

    results, report = executor.run(len(chunks), pack, launch, consume)
    return (np.concatenate([c for c, _ in results]),
            np.concatenate([e for _, e in results]), report)
