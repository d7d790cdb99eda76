"""The TPU execution-engine plugin: bulk replay/verify on device.

This is the north-star component (BASELINE.json): alongside the per-workflow
engine path, a bulk path that reads MANY workflows' persisted histories,
packs them, replays them in lockstep on the accelerator, and compares the
resulting canonical checksum payloads against the live mutable states.

Reference seams it occupies:
- EngineFactory (shard/controller.go:55-58): constructed per controller and
  offered through it;
- stateRebuilder.Rebuild (execution/state_rebuilder.go:102): the bulk
  analog of single-workflow rebuild;
- scanner/reconciliation (common/reconciliation/invariant): verify_all is a
  concrete-execution invariant check executed on device;
- the mutable-state checksum (execution/checksum.go:36) is the comparison
  oracle on both sides.

The hot path runs on the pipelined bulk-replay executor
(engine/executor.py): keys are CHUNKED (bounding peak host+HBM footprint —
one long-tail history no longer sizes the whole corpus), host packing of
chunk N+1 overlaps the device replay of chunk N, per-workflow encoded
lanes come from the content-addressed pack cache (engine/cache.PackCache —
a warm re-verify of an unchanged corpus skips repacking entirely; an
appended batch repacks only the suffix), and verify_all compares payload
rows ON DEVICE, reading back a mismatch bitmap plus the error lanes
instead of the full [W, width] tensor.

Workflows whose histories exceed kernel capacities no longer fall off to
the per-workflow oracle: capacity-flagged rows gather into a compact
sub-corpus and re-replay ON DEVICE at widened K through the escalation
ladder (engine/ladder.py; rung-1 dispatch rides the executor's escalate
hook, overlapping later chunks' pack/replay). Only rows that still
overflow at the top rung — or whose error no capacity can fix — arbitrate
through the oracle, measured and reported under `tpu.fallback/*`.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..core.checksum import (
    DEFAULT_LAYOUT,
    STICKY_ROW_INDEX,
    PayloadLayout,
    payload_row,
)
from ..oracle.state_builder import StateBuilder
from ..ops.encode import (
    LANE_EVENT_ID,
    LANE_EVENT_TYPE,
    NUM_LANES,
    assemble_corpus,
    encode_segments,
    gather_subcorpus,
)
from ..ops.payload import payload_rows
from ..ops.replay import replay_events, verify_rows
from ..utils import metrics as m
from ..utils import tracing
from ..utils.profiler import ReplayProfiler
from . import resident as resident_mod
from .cache import PackCache, content_address
from .executor import BulkReplayExecutor
from .ladder import EscalationLadder
from .persistence import Stores
from .resident import ResidentStateCache

#: max workflows per device launch on the bulk path; bounds peak host
#: corpus bytes and HBM per chunk (the regression the chunked executor
#: fixes: one [W, E_max, L] corpus sized by the longest history)
CHUNK_ENV = "CADENCE_TPU_REPLAY_CHUNK"
DEFAULT_CHUNK = 4096


def _bucket_events(n: int) -> int:
    """Round the chunk's event axis up to a power of two (min 16): chunks
    with similar histories share one compiled executable instead of one
    per exact max length, and padding rows are no-ops in the kernel."""
    return max(16, 1 << (max(1, int(n)) - 1).bit_length())


@dataclass
class BulkVerifyResult:
    total: int
    verified_on_device: int
    divergent: List[Tuple[str, str, str]] = field(default_factory=list)
    #: keys arbitrated by the per-workflow oracle: the escalation
    #: ladder's RESIDUE (top-rung overflow or non-capacity errors) —
    #: before the ladder this held every device-flagged key
    fallback: List[Tuple[str, str, str]] = field(default_factory=list)
    device_errors: List[Tuple[Tuple[str, str, str], int]] = field(default_factory=list)
    #: keys resolved ON DEVICE by the widened-K re-replay ladder
    escalated: List[Tuple[str, str, str]] = field(default_factory=list)
    #: keys served from the HBM-resident state cache (exact hits replay
    #: nothing; suffix hits replay only the appended batches)
    resident: List[Tuple[str, str, str]] = field(default_factory=list)
    #: subset of `resident` whose entry was hydrated from a PERSISTED
    #: snapshot during this verify (engine/snapshot.py): the cold
    #: partition became a suffix partition for these keys
    snapshot: List[Tuple[str, str, str]] = field(default_factory=list)
    #: how `resident` splits: exact hits, suffix hits and the events the
    #: suffix hits' appends replayed; beside them the events the
    #: full-replay path replayed for the cold keys
    exact_rows: int = 0
    suffix_rows: int = 0
    suffix_events: int = 0
    replayed_events: int = 0

    @property
    def ok(self) -> bool:
        return not self.divergent


@dataclass
class _ChunkPlan:
    """One chunk of the mesh-aware serving run: which keys it carries
    (global indices into the run's key list), which corpus ROW each key
    occupies, and the padded workflow axis. On a mesh of 1 rows are the
    contiguous prefix (today's layout, byte for byte); on a mesh of N
    the chunk is N per-shard slices of P rows each — key k sits in slice
    workflow_shard(k, N), so sharded placement lands every workflow on
    its owning device and the resident pool stays device-local."""

    idx: List[int]
    rows: np.ndarray
    W: int


class TPUReplayEngine:
    """Bulk device replay over persisted histories, served from the
    device mesh (mesh of 1 = the single-chip configuration)."""

    def __init__(self, stores: Stores,
                 layout: PayloadLayout = DEFAULT_LAYOUT,
                 chunk_workflows: Optional[int] = None,
                 pipeline_depth: Optional[int] = None,
                 mesh=None) -> None:
        self.stores = stores
        self.layout = layout
        self.pack_cache = PackCache()
        self.ladder = EscalationLadder(layout)
        #: HBM-resident per-workflow states: verify_all serves unchanged
        #: workflows from the cache and replays only appended batches for
        #: suffix hits; full replay remains the cold-miss and
        #: parity-audit path (engine/resident.py). Sharded across the
        #: mesh with the engine (per-device slices, split budget).
        self.resident = ResidentStateCache(layout, ladder=self.ladder,
                                           pipeline_depth=pipeline_depth)
        self.metrics = m.DEFAULT_REGISTRY
        self.chunk_workflows = (chunk_workflows if chunk_workflows
                                else int(os.environ.get(CHUNK_ENV,
                                                        str(DEFAULT_CHUNK))))
        self.pipeline_depth = pipeline_depth
        #: serving mesh (parallel/mesh.serving_mesh resolves the
        #: CADENCE_TPU_MESH_DEVICES knob); resolved LAZILY so engine
        #: construction never forces JAX backend init
        self._mesh = mesh
        if mesh is not None:
            self._wire_mesh(mesh)
        #: (W, E) of each chunk of the last bulk run — the test seam for
        #: the bounded-footprint contract (a long-tail history inflates
        #: only its own chunk's E)
        self.last_run_chunk_shapes: List[Tuple[int, int]] = []
        #: the real events those chunks held
        self.last_run_events = 0
        #: lazy device-serving scheduler (engine/serving.py); created on
        #: first request so engines that never serve pay nothing
        self._serving = None
        #: lazy checksum-gated snapshot writer (engine/snapshot.py)
        self._snapshotter = None

    def serving_scheduler(self):
        """The micro-batching transaction scheduler bound to THIS
        engine's resident cache / pack cache / ladder / mesh — the
        device-serving tier clusters wire into their history engines
        (engine/serving.ServingScheduler). One per engine: the scheduler
        and verify_all must share the resident pool, or a transaction's
        append and a verify's admit could race different caches."""
        if self._serving is None:
            from .serving import ServingScheduler
            self._serving = ServingScheduler(self)
        return self._serving

    def snapshotter(self):
        """The checksum-gated snapshot writer bound to THIS engine's
        stores / resident pool / pack cache (engine/snapshot.Snapshotter)
        — one per engine for the same reason the serving scheduler is:
        writer and verify must share the resident pool."""
        if self._snapshotter is None:
            from .snapshot import Snapshotter
            self._snapshotter = Snapshotter(
                self.stores, self.resident, self.pack_cache, self.layout,
                registry=self.metrics)
        return self._snapshotter

    def snapshot_sweep(self, keys=None, force: bool = False):
        """Persist snapshots for every resident workflow (or `keys`):
        the deploy/admin warm-up verb — run after a verify pass seeds
        the pool, so the next restart is a warm start."""
        return self.snapshotter().sweep(keys=keys, force=force)

    @property
    def mesh(self):
        if self._mesh is None:
            from ..parallel.mesh import serving_mesh
            self._mesh = serving_mesh()
            self._wire_mesh(self._mesh)
        return self._mesh

    def _wire_mesh(self, mesh) -> None:
        """One mesh through every layer: the escalation ladder re-replays
        flagged rows under the same 'shard' axis (the already-sharded
        replay_sharded_escalated kernels) and the resident pool splits
        its HBM budget into per-device slices."""
        if int(mesh.devices.size) > 1:
            self.ladder.mesh = mesh
        self.resident.set_mesh(mesh)

    @property
    def mesh_size(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def metrics(self):
        return self._metrics

    @metrics.setter
    def metrics(self, registry) -> None:
        """Clusters wire their own registry post-construction (Onebox/
        ServiceHost set `tpu.metrics = ...`); the pack cache's hit/miss
        counters must land on the SAME registry or they never reach that
        cluster's /metrics scrape."""
        self._metrics = registry
        self.pack_cache.metrics = registry
        self.ladder.metrics = registry
        if hasattr(self, "resident"):
            self.resident.metrics = registry
        if getattr(self, "_serving", None) is not None:
            self._serving.metrics = registry
        if getattr(self, "_snapshotter", None) is not None:
            self._snapshotter.metrics = registry

    def _load_histories(self, keys: Sequence[Tuple[str, str, str]]):
        return [
            self.stores.history.as_history_batches(*key) for key in keys
        ]

    def tree_segments(self, key: Tuple[str, str, str]) -> list:
        """One run's full branch tree as encode_segments input: the current
        branch's lineage replays state-carrying; every other branch's
        events beyond the shared prefix are emitted VH-only with
        fork-inheritance from the current branch — the device then holds
        the complete VersionHistories (winner state + loser branch items),
        matching the post-conflict-resolution mutable state
        (ndc/conflict_resolver.go + versionHistories.go on device)."""
        from ..core.events import HistoryBatch

        hs = self.stores.history
        current = hs.get_current_branch(*key)
        cur_lineage = hs.as_history_batches(*key, branch=current)
        segments = [(cur_lineage, current, current, False)]
        cur_events = [e for b in cur_lineage for e in b.events]
        for index in range(hs.branch_count(*key)):
            if index == current:
                continue
            events = hs.read_events(*key, branch=index)
            shared = 0
            while (shared < min(len(events), len(cur_events))
                   and events[shared].id == cur_events[shared].id
                   and events[shared].version == cur_events[shared].version):
                shared += 1
            unique = events[shared:]
            if not unique:
                continue
            segments.append((
                [HistoryBatch(domain_id=key[0], workflow_id=key[1],
                              run_id=key[2], events=unique)],
                index, current, True,
            ))
        return segments

    def _encode_key_rows(self, key: Tuple[str, str, str]) -> np.ndarray:
        """One workflow's encoded [n, L] lane rows. Single-lineage
        histories go through the content-addressed pack cache (append-only
        ⇒ a warm re-verify reuses the rows; an appended batch packs only
        the suffix); multi-branch trees — post-conflict-resolution shapes
        that are not append-only in the cached sense — encode fresh."""
        hs = self.stores.history
        if hs.branch_count(*key) <= 1 and hs.get_current_branch(*key) == 0:
            return self.pack_cache.encode(
                key, hs.as_history_batches(*key))
        segs = self.tree_segments(key)
        total = sum(len(b.events) for seg in segs for b in seg[0])
        return encode_segments(segs, total)

    def _chunk_spans(self, n: int) -> List[Tuple[int, int]]:
        c = max(1, self.chunk_workflows)
        return [(lo, min(lo + c, n)) for lo in range(0, n, c)]

    def _plan_chunks(self, keys: List[Tuple[str, str, str]]
                     ) -> List[_ChunkPlan]:
        """Chunk the key list for the mesh. Mesh of 1: contiguous spans
        padded to the run-constant width — exactly the pre-mesh layout.
        Mesh of N: keys bucket by workflow_shard (the stable key→device
        hash mirroring numHistoryShards→host), each chunk takes up to P
        keys of EVERY bucket so row s*P+i belongs to shard s and sharded
        placement puts each workflow on its owning device."""
        n = self.mesh_size
        if n <= 1:
            pad_to = min(max(1, self.chunk_workflows), len(keys))
            return [_ChunkPlan(idx=list(range(lo, hi)),
                               rows=np.arange(hi - lo), W=pad_to)
                    for lo, hi in self._chunk_spans(len(keys))]
        from ..parallel.mesh import workflow_shard
        buckets: List[List[int]] = [[] for _ in range(n)]
        for i, key in enumerate(keys):
            buckets[workflow_shard(key, n)].append(i)
        per = max(1, -(-self.chunk_workflows // n))
        P = min(per, max((len(b) for b in buckets), default=1))
        plans: List[_ChunkPlan] = []
        off = 0
        while any(len(b) > off for b in buckets):
            idx: List[int] = []
            rows: List[int] = []
            for s, b in enumerate(buckets):
                sl = b[off:off + P]
                idx.extend(sl)
                rows.extend(s * P + j for j in range(len(sl)))
            plans.append(_ChunkPlan(idx=idx, rows=np.asarray(rows,
                                                             dtype=np.int64),
                                    W=n * P))
            off += P
        return plans

    def _pack_chunk(self, chunk_keys: Sequence[Tuple[str, str, str]],
                    rows: np.ndarray, pad_to: int) -> np.ndarray:
        """Encode one chunk of keys into [pad_to, E, L], key j landing
        on corpus row rows[j] (its shard's slice); E is the pow2 bucket
        of THIS chunk's longest history, not the corpus-wide max — the
        bounded-memory contract. All other rows are padding (the kernel
        no-ops them)."""
        rows_list = [self._encode_key_rows(k) for k in chunk_keys]
        E = _bucket_events(max((r.shape[0] for r in rows_list), default=1))
        sub = assemble_corpus(rows_list, E)
        corpus = np.zeros((pad_to, E, NUM_LANES), dtype=np.int64)
        corpus[:, :, LANE_EVENT_TYPE] = -1
        corpus[np.asarray(rows)] = sub
        return corpus

    def _run_chunks(self, keys: List[Tuple[str, str, str]], pack_extra,
                    launch_fn, readback_fn, escalate_fn=None, plans=None):
        """Drive the pipelined executor over key chunks, fanned across
        the serving mesh (per-device H2D slice copies; a mesh of 1 is
        the single-chip configuration, byte for byte).

        pack_extra(chunk_keys, plan) -> host-side extras packed
        alongside the corpus (runs in the pack pool, overlapped with
        device compute; extras sized [plan.W, ...] in ROW space);
        launch_fn(corpus_dev, extras) -> device outs (async);
        readback_fn(outs) -> numpy results per chunk (row space);
        escalate_fn(ci, corpus_np, consumed) -> consumed — optional
        capacity-escalation seam: called right after chunk ci's readback
        with its HOST corpus (held only until then — at most `depth`
        corpora are ever retained, the ring bound), so flagged rows can
        gather and dispatch widened re-replays while later chunks still
        pack and replay.
        Returns (per-chunk results, per-chunk plans)."""
        from ..parallel.mesh import place_corpus

        if plans is None:
            plans = self._plan_chunks(keys)
        mesh = self.mesh
        prof = ReplayProfiler(self.metrics)
        scope = self.metrics.scope(m.SCOPE_TPU_REPLAY)
        executor = BulkReplayExecutor(depth=self.pipeline_depth,
                                      registry=self.metrics, mesh=mesh)
        shapes: List[Optional[Tuple[int, int]]] = [None] * len(plans)
        events: List[int] = [0] * len(plans)
        corpora: dict = {}

        n_dev = int(mesh.devices.size)

        @tracing.spanned("verify.pack")
        def pack(ci):
            plan = plans[ci]
            chunk_keys = [keys[i] for i in plan.idx]
            corpus = self._pack_chunk(chunk_keys, plan.rows, plan.W)
            shapes[ci] = (corpus.shape[0], corpus.shape[1])
            events[ci] = int((corpus[:, :, LANE_EVENT_ID] > 0).sum())
            if n_dev > 1:
                # per-device real-row counters (shard-population skew is
                # a scrape away: tpu.executor/rows-dispatched-dev{d}),
                # scanned in the overlapped pack pool, off the serial
                # dispatch path
                exec_scope = self.metrics.scope(m.SCOPE_TPU_EXECUTOR)
                slice_w = corpus.shape[0] // n_dev
                for d in range(n_dev):
                    rows_d = int((corpus[d * slice_w:(d + 1) * slice_w,
                                         :, LANE_EVENT_ID] > 0)
                                 .any(axis=1).sum())
                    exec_scope.inc(m.device_metric(m.M_EXEC_ROWS, d),
                                   rows_d)
            if escalate_fn is not None:
                corpora[ci] = corpus
            extras = pack_extra(chunk_keys, plan) if pack_extra else None
            return corpus, extras

        def launch(ci, packed):
            corpus, extras = packed
            scope.inc(m.M_KERNEL_LAUNCHES)
            scope.inc(m.M_EVENTS_REPLAYED, events[ci])
            with prof.leg(m.M_PROFILE_H2D):
                corpus_dev = place_corpus(corpus, mesh)
                prof.h2d(corpus.nbytes)
            return launch_fn(corpus_dev, extras)

        def consume(ci, outs):
            with prof.leg(m.M_PROFILE_KERNEL):
                jax.block_until_ready(outs)
            with prof.leg(m.M_PROFILE_READBACK):
                return readback_fn(outs)

        def escalate(ci, consumed):
            return escalate_fn(ci, corpora.pop(ci), consumed)

        with scope.timed():
            results, _report = executor.run(
                len(plans), pack, launch, consume,
                escalate if escalate_fn is not None else None)
        self.last_run_chunk_shapes = [s for s in shapes if s is not None]
        self.last_run_events = sum(events)
        t = self.metrics.timer(m.SCOPE_TPU_REPLAY, m.M_LATENCY)
        if t.total_s > 0:
            self.metrics.gauge(
                m.SCOPE_TPU_REPLAY, m.M_REPLAY_THROUGHPUT,
                self.metrics.counter(m.SCOPE_TPU_REPLAY, m.M_EVENTS_REPLAYED)
                / t.total_s)
        return results, plans

    def replay_tree_payloads(self, keys: Sequence[Tuple[str, str, str]]
                             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Device-replay full branch trees (divergent histories included);
        returns (payload rows, errors, device-chosen current branch).

        Chunked through the bulk executor: host packing overlaps device
        replay, each chunk's event axis is sized to ITS longest history
        (one long-tail workflow no longer inflates the whole corpus), and
        every launch is decomposed into pack/pack-queue-wait/h2d/kernel/
        readback legs so scrapes show which pipeline side is starving."""
        keys = list(keys)
        if not keys:
            width = self.layout.width
            return (np.zeros((0, width), dtype=np.int64),
                    np.zeros((0,), dtype=np.int32),
                    np.zeros((0,), dtype=np.int32))

        def launch(corpus_dev, _extras):
            state = replay_events(corpus_dev, self.layout)
            return (payload_rows(state, self.layout), state.error,
                    state.current_branch)

        def readback(outs):
            rows_dev, err_dev, branch_dev = outs
            return (np.asarray(rows_dev), np.asarray(err_dev),
                    np.asarray(branch_dev))

        results, plans = self._run_chunks(keys, None, launch, readback)
        rows = np.zeros((len(keys), self.layout.width), dtype=np.int64)
        errors = np.zeros((len(keys),), dtype=np.int32)
        branch = np.zeros((len(keys),), dtype=np.int32)
        for plan, (r, e, b) in zip(plans, results):
            rows[plan.idx] = r[plan.rows]
            errors[plan.idx] = e[plan.rows]
            branch[plan.idx] = b[plan.rows]
        return rows, errors, branch

    def _expected_row(self, key: Tuple[str, str, str]
                      ) -> Tuple[np.ndarray, int]:
        """The live mutable state's canonical payload row (sticky masked:
        replay always clears stickiness) and current branch index."""
        live_ms = self.stores.execution.get_workflow(*key)
        row = payload_row(live_ms, self.layout)
        row[STICKY_ROW_INDEX] = 0
        return row, live_ms.version_histories.current_index

    def _partition_resident(self, keys: List[Tuple[str, str, str]]):
        """Split keys by what the resident cache can serve: exact hits
        (no device work), suffix hits (replay appended batches only),
        and cold keys for the full-replay path. Non-single-lineage keys
        (an NDC branch switch happened since the state was pinned) and
        stale addresses (tail overwrite, reset rewrite) invalidate their
        entries here — the cache never serves across those mutations.

        Persisted snapshots turn the cold partition into a SUFFIX
        partition: a would-be-cold key with a valid snapshot hydrates
        the durable state row into the pool (engine/snapshot.py) and
        re-partitions as an exact/suffix hit — the warm-restart path of
        verify_all. Hydrated keys are returned so the result can report
        them."""
        from . import snapshot as snapshot_mod

        exact: List[Tuple[Tuple[str, str, str], object]] = []
        suffix: List[Tuple[Tuple[str, str, str], object, list]] = []
        cold: List[Tuple[str, str, str]] = []
        addresses: dict = {}
        hydrated: List[Tuple[str, str, str]] = []
        snapshots = getattr(self.stores, "snapshot", None)
        hs = self.stores.history
        looked: list = []  # (key, batches, hit), in key order
        for key in keys:
            if (hs.branch_count(*key) > 1
                    or hs.get_current_branch(*key) != 0):
                self.resident.invalidate(key)  # NDC branch switch
                looked.append((key, None, None))
                continue
            batches = hs.as_history_batches(*key)
            looked.append((key, batches, self.resident.lookup(key, batches)))
        # one span a call, not one a key: every miss with a valid record
        # hydrates it into the pool and is looked up again
        with tracing.span("verify.snapshot-consult"):
            for n, (key, batches, hit) in enumerate(looked):
                if batches is None or hit is not None \
                        or not snapshot_mod.seed_from_batches(
                            snapshots, self.resident, self.pack_cache, key,
                            batches, self.layout, self.metrics):
                    continue
                hit = self.resident.lookup(key, batches)
                if hit is not None:
                    hydrated.append(key)
                    looked[n] = (key, batches, hit)
        for key, batches, hit in looked:
            if hit is None:
                if batches is not None:
                    addresses[key] = content_address(batches)
                cold.append(key)
            elif hit[0] == "exact":
                exact.append((key, hit[1]))
            else:
                suffix.append((key, hit[1], batches))
        return exact, suffix, cold, addresses, hydrated

    def verify_all(self, keys: Optional[Sequence[Tuple[str, str, str]]] = None
                   ) -> BulkVerifyResult:
        """Replay persisted histories on device and compare against the live
        mutable states (zero-divergence contract). The compare itself runs
        ON DEVICE: expected payload rows ship with the corpus and the host
        reads back a mismatch bitmap plus the error lanes — not the full
        [W, width] payload tensor.

        Incremental serving path: workflows whose final state is pinned
        in the HBM-resident cache (engine/resident.py) skip full replay —
        an unchanged history verifies against the cached payload with
        zero device work, an appended history replays ONLY the new
        batches against the resident state (O(new events) per
        transaction). Cold misses run the full chunked path below and
        seed the cache from their verified final states, a chunk at a
        time and as views of the chunk's state (resident.admit_chunk): a
        row costs a launch only when its state is first read.

        Capacity-flagged rows (pending-table / version-history / branch
        overflow) escalate through the widened-K ladder: their rung-1
        re-replay is DISPATCHED from the executor's escalate hook as each
        chunk's errors read back — overlapping later chunks — and rungs
        ≥ 2 run once, batched across all chunks' survivors. Rows the
        ladder resolves verify against the live state at the base payload
        width, byte-identically to the oracle; only the ladder's residue
        (plus non-capacity errors) re-runs through the per-workflow
        oracle.

        Each leg of a call is one span: `verify.partition` (the resident
        pool consulted; inside it `verify.snapshot-consult`, the misses'
        persisted records hydrated into the pool), `verify.suffix-replay`
        (the suffix hits' appended batches, where there are any),
        `verify.pack` (a chunk's encode and expected
        rows, on a pack thread), `verify.replay` (launch to results on
        the host) with a chunk's `verify.seed-resident` (verified rows
        pinned into the pool, one call a chunk) inside it, and
        `verify.compare`."""
        if keys is None:
            keys = self.stores.execution.list_executions()
        all_keys = list(keys)
        if not all_keys:
            return BulkVerifyResult(total=0, verified_on_device=0)
        # resolve (and wire) the serving mesh BEFORE the resident
        # partition: the pool's shard structure must be bound before any
        # lookup/admit decides which device slice a key belongs to
        self.mesh
        result = BulkVerifyResult(total=len(all_keys), verified_on_device=0)
        if resident_mod.enabled():
            with tracing.span("verify.partition"):
                exact, suffix, keys, addresses, hydrated = \
                    self._partition_resident(all_keys)
            result.snapshot = hydrated
        else:
            exact, suffix, keys, addresses = [], [], all_keys, {}

        result.exact_rows = len(exact)
        for key, entry in exact:
            row, br = self._expected_row(key)
            result.verified_on_device += 1
            result.resident.append(key)
            if not (entry.payload == row).all() or entry.branch != br:
                result.divergent.append(key)

        if suffix:
            with tracing.span("verify.suffix-replay"):
                outcomes, appended = self.resident.replay_append_report(
                    suffix, encode_suffix=self.pack_cache.encode_suffix)
            result.suffix_rows = len(suffix)
            result.suffix_events = appended.events_appended
            for (key, _entry, batches), res in zip(suffix, outcomes):
                row, br = self._expected_row(key)
                if not res.ok:
                    # entry already invalidated; the per-workflow oracle
                    # arbitrates, exactly like the cold path's residue
                    result.device_errors.append((key, int(res.error)))
                    result.fallback.append(key)
                    oracle_ms = StateBuilder().replay_history(batches)
                    if not (payload_row(oracle_ms, self.layout)
                            == row).all():
                        result.divergent.append(key)
                    continue
                result.verified_on_device += 1
                result.resident.append(key)
                if res.escalated:
                    result.escalated.append(key)
                if not (res.payload == row).all() or res.branch != br:
                    result.divergent.append(key)

        if not keys:
            return result
        from ..parallel.mesh import place_corpus
        mesh = self.mesh
        #: ci -> (capacity-flagged local key indices, pending rung-1
        #: dispatch)
        pending: dict = {}

        def pack_extra(chunk_keys, plan):
            # expected rows live in ROW space ([plan.W, ...]), scattered
            # to each key's shard slice so the on-device compare stays
            # local to the owning device; padding rows' entries are
            # zero-filled garbage the result loop never reads
            expected = np.zeros((plan.W, self.layout.width),
                                dtype=np.int64)
            exp_branch = np.zeros((plan.W,), dtype=np.int32)
            for j, key in enumerate(chunk_keys):
                live_ms = self.stores.execution.get_workflow(*key)
                row = payload_row(live_ms, self.layout)
                # sticky state is active-side only; replay clears it
                # (STICKY_ROW_INDEX note in core/checksum.py)
                row[STICKY_ROW_INDEX] = 0
                expected[plan.rows[j]] = row
                exp_branch[plan.rows[j]] = \
                    live_ms.version_histories.current_index
            return expected, exp_branch

        def launch(corpus_dev, extras):
            expected, exp_branch = extras
            state = replay_events(corpus_dev, self.layout)
            rows_dev = payload_rows(state, self.layout)
            mismatch = verify_rows(rows_dev, place_corpus(expected, mesh),
                                   state.current_branch,
                                   place_corpus(exp_branch, mesh))
            return mismatch, state.error, expected, exp_branch, state

        def readback(outs):
            mismatch_dev, err_dev, expected, exp_branch, state = outs
            return (np.asarray(mismatch_dev), np.asarray(err_dev),
                    expected, exp_branch, state)

        def escalate(ci, corpus, consumed):
            mismatch, errors, expected, exp_branch, state = consumed
            plan = plans_by_ci[ci]
            # errors come back in row space; flag capacity overflow on
            # REAL rows only and remember the flagged keys' positions
            cap_local = self.ladder.capacity_flagged(errors[plan.rows])
            if len(cap_local):
                cap_rows = np.asarray(plan.rows)[cap_local]
                pending[ci] = (cap_local, self.ladder.submit(
                    gather_subcorpus(corpus, cap_rows)))
            # seed the resident cache from this chunk's verified-clean
            # rows: the device row equals the shipped expected row
            # whenever the mismatch bit is clear, so the pool is handed
            # the chunk's state ONCE and pins the rows as views of it
            # (resident.admit_chunk): no launch, no readback and no
            # device buffer a row until somebody reads that row's state.
            # The state reference is dropped here (the ring keeps
            # O(depth) alive); the views keep the state.
            with tracing.span("verify.seed-resident"):
                self.resident.admit_chunk(state, [
                    (keys[i], addresses[keys[i]], r, expected[r],
                     int(exp_branch[r]))
                    for i, r in zip(plan.idx, plan.rows.tolist())
                    if errors[r] == 0 and not mismatch[r]
                    and keys[i] in addresses])
            return mismatch, errors, expected, exp_branch

        plans_by_ci = self._plan_chunks(keys)
        # launch to results on the host; the executor's legs and each
        # chunk's `verify.seed-resident` lie inside
        with tracing.span("verify.replay"):
            results, plans = self._run_chunks(keys, pack_extra, launch,
                                              readback, escalate,
                                              plans=plans_by_ci)
        result.replayed_events = self.last_run_events
        with tracing.span("verify.compare"):
            self._settle(result, keys, plans, results, pending)
        return result

    def _settle(self, result: BulkVerifyResult, keys, plans, results,
                pending: dict) -> None:
        """The last leg of `verify_all`: the ladder's pending rungs read
        back, then every row's verdict from its chunk's mismatch bitmap
        and error lane into `result`."""
        ordered = sorted(pending.items())
        outcomes = self.ladder.finish([p for _, (_, p) in ordered])
        resolved = {}  # (ci, local j) -> (base-width ladder row, branch)
        for (ci, (cap, _)), outcome in zip(ordered, outcomes):
            for k, j in enumerate(cap):
                if outcome.resolved[k]:
                    resolved[(ci, int(j))] = (outcome.rows[k],
                                              outcome.branch[k])

        for ci, (plan, (mismatch, errors, expected, exp_branch)
                 ) in enumerate(zip(plans, results)):
            for j, i in enumerate(plan.idx):
                key = keys[i]
                r = int(plan.rows[j])
                if errors[r] != 0 and (ci, j) in resolved:
                    # the widened-K re-replay cleared the capacity flag:
                    # this row verified on device, no oracle involved.
                    # Same contract as verify_rows: payload rows AND the
                    # device-chosen branch must match the live state
                    result.verified_on_device += 1
                    result.escalated.append(key)
                    rows_l, branch_l = resolved[(ci, j)]
                    if (not (rows_l == expected[r]).all()
                            or branch_l != exp_branch[r]):
                        result.divergent.append(key)
                elif errors[r] != 0:
                    # top-rung overflow or a non-capacity error: the
                    # per-workflow oracle arbitrates, as before
                    result.device_errors.append((key, int(errors[r])))
                    result.fallback.append(key)
                    oracle_ms = StateBuilder().replay_history(
                        self.stores.history.as_history_batches(*key))
                    if not (payload_row(oracle_ms, self.layout)
                            == expected[r]).all():
                        result.divergent.append(key)
                else:
                    result.verified_on_device += 1
                    if mismatch[r]:
                        result.divergent.append(key)
