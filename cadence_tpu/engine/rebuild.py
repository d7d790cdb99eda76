"""Device-first mutable-state rebuilder: the TPU engine on the hot path.

The reference rebuilds a workflow's mutable state by replaying its full
history through stateBuilder one Go object at a time
(execution/state_rebuilder.go:102 Rebuild). Here the O(events) sequential
scan runs on the accelerator for MANY workflows at once (ops/replay), and
the host only performs O(pending) enrichment: the dense final ReplayState
carries every scan-dependent scalar and table, while strings and static
start-attributes (activity IDs, task lists, retry policies, parent
linkage) are hydrated from the event batches the caller already holds —
a dict lookup per pending item, never a per-event Python loop.

Safety: every hydrated state is checked elementwise against the device's
own canonical payload row; a flagged row (kernel error) or a hydration
mismatch falls back to the oracle replayer and is COUNTED — measured,
reported, never silent (SURVEY.md §7). Consumers:

- NDC conflict resolution's winning-branch rebuild (engine/replication.py,
  conflict_resolver.go analog);
- crash-recovery state reconstruction (engine/durability.py,
  the recovery arm of state_rebuilder.go);
- workflow reset's prefix replay (engine/history_engine.py reset_workflow,
  reset/resetter.go:96 analog).
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.checksum import DEFAULT_LAYOUT, PayloadLayout, payload_row
from ..core.enums import EventType
from ..core.events import HistoryBatch, HistoryEvent
from ..oracle.mutable_state import (
    ActivityInfo,
    ChildExecutionInfo,
    DomainEntry,
    MutableState,
    RequestCancelInfo,
    SignalInfo,
    TimerInfo,
    VersionHistory,
    VersionHistoryItem,
)
from ..oracle.state_builder import StateBuilder
from ..utils import tracing


@dataclass
class RebuildStats:
    """Where rebuilds actually ran (the VERDICT-demanded counter)."""

    device: int = 0
    oracle_fallback: int = 0
    #: subset of `device` that resolved through the widened-K escalation
    #: ladder (capacity-flagged histories that stayed on device)
    ladder: int = 0
    #: subset of `device` served by the HBM-resident state cache: an
    #: exact hit hydrates straight from the pinned state (zero replay),
    #: a suffix hit replays only the appended batches
    resident: int = 0
    #: jobs whose resident entry was seeded from a PERSISTED snapshot
    #: (engine/snapshot.py) — the warm-restart path: hydrate + replay
    #: only the since-snapshot suffix, never the full history
    snapshot_seeded: int = 0
    #: how the resident prepass served its jobs: exact hits (no replay),
    #: suffix hits (only the appended batches replayed) and the events
    #: those appends replayed
    exact_rows: int = 0
    suffix_rows: int = 0
    suffix_events: int = 0
    #: what the full-replay path handed to the device: launches, the real
    #: events in them and the dense int64 bytes shipped (padding included)
    chunks: int = 0
    events: int = 0
    dense_bytes: int = 0
    kernel_errors: Dict[int, int] = field(default_factory=dict)

    def merge(self, other: "RebuildStats") -> None:
        self.device += other.device
        self.oracle_fallback += other.oracle_fallback
        self.ladder += other.ladder
        self.resident += other.resident
        self.snapshot_seeded += other.snapshot_seeded
        self.exact_rows += other.exact_rows
        self.suffix_rows += other.suffix_rows
        self.suffix_events += other.suffix_events
        self.chunks += other.chunks
        self.events += other.events
        self.dense_bytes += other.dense_bytes
        for code, n in other.kernel_errors.items():
            self.kernel_errors[code] = self.kernel_errors.get(code, 0) + n


def _rebuilt_history_size(batches: Sequence[HistoryBatch],
                          run_id: str) -> int:
    """Reconstruct mutableState GetHistorySize from the stored batches'
    serialized sizes (one batch == one committed transaction == one WAL
    blob): recovery and standby rebuild must not hand back states whose
    size accounting silently reset to zero — the history-size limits
    would stop protecting exactly the workflows that just failed over.
    For a continue-as-new chain only the final run's batches count (the
    new run starts its own accounting). A batch held as its bytes is
    measured by them, undecoded."""
    from ..core.codec import batch_bytes
    return sum(len(batch_bytes(b)) for b in batches if b.run_id == run_id)


class _EventsById:
    """The events of one run's batches by id, for `_hydrate`'s handful of
    lookups. Over an id-contiguous lineage (what a history store holds)
    the batch that holds an id is found by a bisect on the first ids and
    only it is read, so a batch held as its bytes is decoded only when an
    id in it is asked for (a held batch's ids are checked consecutive when
    it is decoded). Batches that are not contiguous are read whole into a
    dict, the last event of an id winning."""

    def __init__(self, batches: Sequence[HistoryBatch]) -> None:
        from ..core.codec import batch_first_id, batch_last_id
        self._batches = batches
        self._firsts: List[int] = []
        self._dict: Optional[Dict[int, HistoryEvent]] = None
        after = None
        for b in batches:
            first = batch_first_id(b.events)
            after_last = after is None or first == after
            if not after_last or \
                    batch_last_id(b.events) != first + len(b.events) - 1:
                self._read_whole()
                return
            self._firsts.append(first)
            after = first + len(b.events)

    def _read_whole(self) -> None:
        self._dict = {e.id: e for b in self._batches for e in b.events}

    def get(self, event_id: int) -> Optional[HistoryEvent]:
        if self._dict is None:
            k = bisect.bisect_right(self._firsts, event_id) - 1
            if k < 0:
                return None
            events = self._batches[k].events
            j = event_id - self._firsts[k]
            if j >= len(events):
                return None
            if events[j].id == event_id:
                return events[j]
            self._read_whole()
        return self._dict.get(event_id)


class DeviceRebuilder:
    """Batched device replay → full MutableState objects."""

    def __init__(self, layout: PayloadLayout = DEFAULT_LAYOUT,
                 chunk_jobs: Optional[int] = None, mesh=None) -> None:
        import os

        from ..utils.metrics import DEFAULT_REGISTRY
        from .ladder import EscalationLadder
        self.layout = layout
        self.stats = RebuildStats()
        self.metrics = DEFAULT_REGISTRY
        self.ladder = EscalationLadder(layout, registry=self.metrics)
        #: serving mesh (parallel/mesh.serving_mesh knob); resolved
        #: lazily so construction never forces JAX backend init. A
        #: recovery/reset storm's rebuild chunks shard over the same
        #: 'shard' axis as the verify path; the ladder's widened
        #: re-replays ride it too (its state-keeping hydration rungs
        #: stay single-device by design — see ladder._dense_fn)
        self._mesh = mesh
        if mesh is not None and int(mesh.devices.size) > 1:
            self.ladder.mesh = mesh
        #: HBM-resident state cache to consult before full replay
        #: (Onebox wires the cluster's shared cache here — the same one
        #: TPUReplayEngine.verify_all seeds); None skips the consult
        #: unless a snapshot store is wired, which lazily owns one
        self.resident = None
        #: pack cache whose suffix path encodes resident appends
        #: O(suffix). Onebox wires the engine's shared cache; standalone
        #: rebuilders (recovery, the reset-prefix path) OWN one, so a
        #: suffix encode always resumes an interner instead of paying a
        #: full re-encode sliced at the prefix — every consumer is
        #: O(suffix) on the host side too
        from .cache import PackCache
        self.pack_cache = PackCache()
        #: persisted-snapshot store (engine/snapshot.SnapshotStore):
        #: recovery wires the recovered bundle's store here, turning a
        #: host restart into hydrate + replay-since-snapshot instead of
        #: a full-history replay storm
        self.snapshots = None
        #: key -> (snapshot batch count, persisted history_size) for
        #: seeds made this rebuild: hydration recovers history-size
        #: accounting as snapshot size + suffix bytes — O(suffix),
        #: never a prefix re-serialization
        self._snap_sizes: Dict[tuple, Tuple[int, int]] = {}
        #: max jobs per device launch (bounds the [W, E, L] corpus the
        #: same way the replay engine's chunking does)
        self.chunk_jobs = (chunk_jobs if chunk_jobs else
                           int(os.environ.get("CADENCE_TPU_REBUILD_CHUNK",
                                              "2048")))

    @property
    def mesh(self):
        if self._mesh is None:
            from ..parallel.mesh import serving_mesh
            self._mesh = serving_mesh()
            if int(self._mesh.devices.size) > 1:
                self.ladder.mesh = self._mesh
        return self._mesh

    def rebuild_one(self, batches: Sequence[HistoryBatch],
                    domain_entry: Optional[DomainEntry] = None) -> MutableState:
        return self.rebuild([(batches, domain_entry)])[0]

    def rebuild(self, jobs: Sequence[Tuple[Sequence[HistoryBatch],
                                           Optional[DomainEntry]]],
                on_device: bool = True) -> List[MutableState]:
        """Rebuild one MutableState per job (batches, domain_entry).

        `on_device=False` skips JAX entirely and replays through the
        oracle — for read-only CLI invocations where paying backend init
        plus a whole-cluster device replay to answer `domain list` is
        wrong (ADVICE r3).

        Each leg of a device call is one span, never one a job:
        `rebuild.snapshot-consult`, `rebuild.resident-prepass` (inside it,
        where the pool serves jobs, `rebuild.suffix-replay`: the appended
        batches of its suffix hits, and a `rebuild.hydrate` of the rows
        it resolved), `rebuild.encode` (a chunk's `encode_corpus`, on a
        pack thread), `rebuild.replay` (launch to rows on the host),
        `rebuild.hydrate` (rows to MutableStates) and, where rows were
        capacity-flagged, `rebuild.ladder`."""
        if not on_device:
            from ..utils import metrics as m
            self.stats.oracle_fallback += len(jobs)
            scope = self.metrics.scope(m.SCOPE_REBUILD)
            scope.inc(m.M_ORACLE_FALLBACKS, len(jobs))
            done = self.stats.device + self.stats.oracle_fallback
            self.metrics.gauge(m.SCOPE_REBUILD, m.M_FALLBACK_RATE,
                               (self.stats.oracle_fallback / done)
                               if done else 0.0)
            return [self._oracle_rebuild(b, e) for b, e in jobs]
        import jax

        from ..ops.encode import encode_corpus, history_length
        from ..ops.payload import payload_rows
        from ..ops.replay import replay_events_with_tasks

        if not jobs:
            return []
        # persisted-snapshot consult FIRST (warm restart): jobs with a
        # valid snapshot hydrate the durable ReplayState row into the
        # resident pool (seeding the pack cache's interner at the
        # snapshot point), so the resident prepass below serves them as
        # exact/suffix hits — replaying only the since-snapshot suffix
        with tracing.span("rebuild.snapshot-consult"):
            self._seed_from_snapshots(jobs)
        # resident consult: jobs whose key is pinned in the HBM cache
        # rebuild from the resident state — an exact hit hydrates with
        # ZERO replay, a suffix hit replays only the appended batches
        # (lookups are non-authoritative: rebuild may legitimately pass
        # a prefix of the stored history, e.g. a reset point)
        with tracing.span("rebuild.resident-prepass"):
            pre: Dict[int, MutableState] = self._resident_prepass(jobs)
        if pre:
            positions = [i for i in range(len(jobs)) if i not in pre]
            jobs = [jobs[i] for i in positions]
            if not jobs:
                return [pre[i] for i in sorted(pre)]
        else:
            positions = list(range(len(jobs)))
        from ..utils import metrics as m
        from ..utils.profiler import ReplayProfiler
        from .executor import BulkReplayExecutor
        scope = self.metrics.scope(m.SCOPE_REBUILD)
        # rebuilds profile under their own scope so a reset/recovery storm
        # is distinguishable from bulk-verify traffic in the same scrape
        prof = ReplayProfiler(self.metrics, scope=m.SCOPE_REBUILD)

        # chunked through the shared bulk executor: a recovery storm packs
        # chunk N+1 while chunk N replays, and each chunk's event axis is
        # sized to ITS longest history, not the whole job list's. The
        # chunks fan across the serving mesh (workflow axis sharded over
        # 'shard', per-device slice copies; a mesh of 1 is single-chip)
        from ..parallel.mesh import place_corpus
        mesh = self.mesh
        n_dev = int(mesh.devices.size)
        chunk_jobs = max(1, self.chunk_jobs)
        spans = [(lo, min(lo + chunk_jobs, len(jobs)))
                 for lo in range(0, len(jobs), chunk_jobs)]
        executor = BulkReplayExecutor(registry=self.metrics,
                                      scope=m.SCOPE_REBUILD, mesh=mesh)

        def pack(ci):
            lo, hi = spans[ci]
            chunk = jobs[lo:hi]
            max_events = max(history_length(b) for b, _ in chunk)
            with tracing.span("rebuild.encode"):
                corpus = encode_corpus([b for b, _ in chunk], max_events)
            if corpus.shape[0] % n_dev:
                # whole slice per device: pad with no-op rows
                from ..ops.encode import LANE_EVENT_TYPE, NUM_LANES
                pad_w = -(-corpus.shape[0] // n_dev) * n_dev \
                    - corpus.shape[0]
                pad = np.zeros((pad_w, corpus.shape[1], NUM_LANES),
                               dtype=np.int64)
                pad[:, :, LANE_EVENT_TYPE] = -1
                corpus = np.concatenate([corpus, pad])
            return corpus, sum(history_length(b) for b, _ in chunk)

        def launch(ci, packed):
            corpus, chunk_events = packed
            scope.inc(m.M_KERNEL_LAUNCHES)
            scope.inc(m.M_EVENTS_REPLAYED, chunk_events)
            self.stats.chunks += 1
            self.stats.events += chunk_events
            self.stats.dense_bytes += corpus.nbytes
            with prof.leg(m.M_PROFILE_H2D):
                device_corpus = place_corpus(corpus, mesh)
                prof.h2d(corpus.nbytes)
            state, _log = replay_events_with_tasks(device_corpus,
                                                   self.layout)
            return state, payload_rows(state, self.layout)

        def consume(ci, outs):
            state, rows_dev = outs
            with prof.leg(m.M_PROFILE_KERNEL):
                jax.block_until_ready(rows_dev)
            with prof.leg(m.M_PROFILE_READBACK):
                return np.asarray(rows_dev), jax.device_get(state)

        # launch to rows on the host; the executor's own legs (h2d,
        # device-wait, readback on this thread, pack on its pool) lie inside
        with scope.timed(), tracing.span("rebuild.replay"):
            results, _report = executor.run(len(spans), pack, launch,
                                            consume)

        from ..ops.state import CAPACITY_ERRORS

        out: List[Optional[MutableState]] = []
        #: capacity-flagged jobs: (position in `out`, batches, entry) —
        #: re-replayed at widened K in ONE batched ladder pass below
        #: instead of one oracle loop each
        escalate: List[Tuple[int, Sequence[HistoryBatch],
                             Optional[DomainEntry]]] = []
        # rows -> MutableState, each checked against its device row
        with tracing.span("rebuild.hydrate"):
            for (lo, hi), (rows, arrs) in zip(spans, results):
                for i, (batches, entry) in enumerate(jobs[lo:hi]):
                    err = int(arrs.error[i])
                    if err != 0:
                        self.stats.kernel_errors[err] = (
                            self.stats.kernel_errors.get(err, 0) + 1)
                        if err in CAPACITY_ERRORS:
                            escalate.append((len(out), batches, entry))
                            out.append(None)
                            continue
                        self.stats.oracle_fallback += 1
                        scope.inc(m.M_ORACLE_FALLBACKS)
                        out.append(self._oracle_rebuild(batches, entry))
                        continue
                    ms = self._hydrate(arrs, i, batches, entry)
                    if ms is None or not (payload_row(ms, self.layout)
                                          == rows[i]).all():
                        # hydration must reproduce the device's canonical
                        # payload exactly; anything else routes through the
                        # oracle, counted
                        self.stats.oracle_fallback += 1
                        scope.inc(m.M_ORACLE_FALLBACKS)
                        out.append(self._oracle_rebuild(batches, entry))
                        continue
                    self.stats.device += 1
                    scope.inc(m.M_DEVICE_REBUILDS)
                    out.append(ms)

        if escalate:
            with tracing.span("rebuild.ladder"):
                corpus = encode_corpus(
                    [b for _, b, _ in escalate],
                    max(history_length(b) for _, b, _ in escalate))
                outcome, states = self.ladder.escalate_states(corpus)
                for k, (pos, batches, entry) in enumerate(escalate):
                    ms = None
                    if outcome.resolved[k]:
                        arrs_k, row_k = states[k]
                        ms = self._hydrate(arrs_k, row_k, batches, entry)
                    if (ms is not None
                            and (payload_row(ms, self.layout)
                                 == outcome.rows[k]).all()):
                        self.stats.device += 1
                        self.stats.ladder += 1
                        scope.inc(m.M_DEVICE_REBUILDS)
                        out[pos] = ms
                    else:
                        self.stats.oracle_fallback += 1
                        scope.inc(m.M_ORACLE_FALLBACKS)
                        out[pos] = self._oracle_rebuild(batches, entry)
        done = self.stats.device + self.stats.oracle_fallback
        self.metrics.gauge(m.SCOPE_REBUILD, m.M_FALLBACK_RATE,
                           (self.stats.oracle_fallback / done) if done else 0.0)
        return self._merge_prepass(pre, positions, out)

    @staticmethod
    def _merge_prepass(pre: Dict[int, MutableState], positions: List[int],
                       device_out: List[MutableState]) -> List[MutableState]:
        if not pre:
            return device_out
        merged = dict(pre)
        merged.update(zip(positions, device_out))
        return [merged[i] for i in range(len(merged))]

    def _seed_from_snapshots(self, jobs) -> None:
        """Hydrate persisted snapshots into the resident pool for every
        job the pool doesn't already cover. A rebuilder without a wired
        resident cache (standalone recovery) lazily owns one — the
        hydrated states have to live somewhere the prepass can see."""
        from . import resident as resident_mod
        from . import snapshot as snapshot_mod

        if self.snapshots is None or not snapshot_mod.enabled() \
                or not resident_mod.enabled() or not len(self.snapshots):
            return
        if self.resident is None:
            from .resident import ResidentStateCache
            self.resident = ResidentStateCache(self.layout,
                                               ladder=self.ladder,
                                               registry=self.metrics)
        from .cache import address_relation
        for batches, _entry in jobs:
            if not batches:
                continue
            b0 = batches[0]
            key = (b0.domain_id, b0.workflow_id, b0.run_id)
            entry = self.resident.entry_for(key)
            if entry is not None and address_relation(
                    entry.address, batches) in ("exact", "prefix"):
                continue  # the pool already covers this lineage
            if snapshot_mod.seed_from_batches(
                    self.snapshots, self.resident, self.pack_cache, key,
                    batches, self.layout, self.metrics):
                self.stats.snapshot_seeded += 1
                rec = self.snapshots.get(key)
                if rec is not None:
                    self._snap_sizes[key] = (rec.batch_count,
                                             rec.history_size)

    def _resident_prepass(self, jobs) -> Dict[int, MutableState]:
        """Resolve jobs out of the resident state cache: returns
        {job position: hydrated MutableState} for every job it could
        serve. Every resident-hydrated state is checked elementwise
        against the cache's canonical payload row — same contract as the
        full-replay hydration check below; a mismatch simply leaves the
        job to the device path, counted nowhere special (it will be
        measured there)."""
        from . import resident as resident_mod

        cache = self.resident
        if cache is None or not resident_mod.enabled():
            return {}
        from ..utils import metrics as m
        resolved: List[tuple] = []  # (pos, key, batches, entry, rentry)
        suffix_items = []
        suffix_jobs = []
        for pos, (batches, entry) in enumerate(jobs):
            if not batches:
                continue
            b0 = batches[0]
            key = (b0.domain_id, b0.workflow_id, b0.run_id)
            hit = cache.lookup(key, batches, authoritative=False)
            if hit is None:
                continue
            kind, rentry = hit
            if kind == "exact":
                resolved.append((pos, key, batches, entry, rentry))
            else:
                suffix_items.append((key, rentry, batches))
                suffix_jobs.append((pos, batches, entry))
        self.stats.exact_rows += len(resolved)
        if suffix_items:
            with tracing.span("rebuild.suffix-replay"):
                outcomes, appended = cache.replay_append_report(
                    suffix_items,
                    encode_suffix=(self.pack_cache.encode_suffix
                                   if self.pack_cache is not None else None))
            self.stats.suffix_rows += len(suffix_items)
            self.stats.suffix_events += appended.events_appended
            for (pos, batches, entry), (key, _r, _b), res in zip(
                    suffix_jobs, suffix_items, outcomes):
                if not res.ok:
                    continue  # entry invalidated; device path takes it
                hit2 = cache.lookup(key, batches, authoritative=False)
                if hit2 is not None and hit2[0] == "exact":
                    resolved.append((pos, key, batches, entry, hit2[1]))
        if not resolved:
            return {}
        # rows to MutableStates: the name the full-replay path gives it
        with tracing.span("rebuild.hydrate"):
            pre = self._hydrate_resolved(resolved)
        if pre:
            self.stats.device += len(pre)
            self.stats.resident += len(pre)
            scope = self.metrics.scope(m.SCOPE_REBUILD)
            scope.inc(m.M_DEVICE_REBUILDS, len(pre))
        return pre

    def _hydrate_resolved(self, resolved) -> Dict[int, MutableState]:
        """Hydrate MutableStates from resident-served rows, verified
        against each entry's canonical payload. The rows come to the host
        in BULK (`ResidentStateCache.host_rows`): an appended chunk's
        views in one `device_get` of the chunk, rows hydrated from
        snapshot records where they already are, other device rows a
        stack at a time — a restart hydrating thousands of rows must not
        pay a device round-trip, or a `slice_row` launch, a workflow."""
        pre: Dict[int, MutableState] = {}
        host = self.resident.host_rows([r[4] for r in resolved])
        for (pos, key, batches, entry, rentry), (arrs, row) in zip(
                resolved, host):
            ms = self._hydrate(arrs, row, batches, entry,
                               known_size=self._known_size(key, batches))
            if ms is not None and (payload_row(ms, self.layout)
                                   == rentry.payload).all():
                pre[pos] = ms
        return pre

    def _known_size(self, key, batches) -> Optional[int]:
        """history_size recovered from a persisted snapshot: the stored
        accounting plus the since-snapshot suffix bytes — O(suffix).
        None (full recomputation) when no snapshot seeded this key or
        the batches involve a continue-as-new chain (accounting resets
        at the run boundary)."""
        info = self._snap_sizes.get(key)
        if info is None:
            return None
        n, size = info
        if n > len(batches) or any(b.new_run_events for b in batches):
            return None
        from ..core.codec import batch_bytes
        return size + sum(len(batch_bytes(b)) for b in batches[n:])

    @staticmethod
    def _oracle_rebuild(batches, entry) -> MutableState:
        sb = StateBuilder(MutableState(entry))
        for b in batches:
            sb.apply_batch(b)
        ms = sb.new_run_state if sb.new_run_state is not None else sb.ms
        ms.transfer_tasks, ms.timer_tasks, ms.cross_cluster_tasks = [], [], []
        ms.history_size = _rebuilt_history_size(batches,
                                                ms.execution_info.run_id)
        return ms

    def _hydrate(self, arrs, i: int, batches: Sequence[HistoryBatch],
                 entry: Optional[DomainEntry],
                 known_size: Optional[int] = None
                 ) -> Optional[MutableState]:
        """Dense ReplayState row + host-side event attrs → MutableState.

        For a continue-as-new chain the device row ends in the LAST run's
        state; hydration therefore works on the last run's batches.
        `known_size` short-circuits the history-size recomputation (a
        per-batch re-serialization) with the snapshot-recovered value —
        the warm-restart path's O(suffix) accounting."""
        runs: List[List[HistoryBatch]] = [[]]
        for b in batches:
            runs[-1].append(b)
            if b.new_run_events:
                runs.append([HistoryBatch(
                    domain_id=b.domain_id, workflow_id=b.workflow_id,
                    run_id=b.events[-1].get("new_execution_run_id", b.run_id),
                    events=b.new_run_events)])
        last_run = runs[-1]
        by_id = _EventsById(last_run)

        # static/start fields via the oracle on the START BATCH ONLY — the
        # one place all string attributes live; O(1) in history length
        sb = StateBuilder(MutableState(entry))
        try:
            sb.apply_batch(last_run[0])
        except Exception:
            return None
        ms = sb.ms
        ms.transfer_tasks, ms.timer_tasks, ms.cross_cluster_tasks = [], [], []
        ms.history_size = (known_size
                           if known_size is not None and len(runs) == 1
                           else _rebuilt_history_size(
                               last_run, last_run[0].run_id))
        info = ms.execution_info

        # scan-dependent execution scalars from the device
        info.state = int(arrs.state[i])
        info.close_status = int(arrs.close_status[i])
        info.cancel_requested = bool(arrs.cancel_requested[i])
        info.last_first_event_id = int(arrs.last_first_event_id[i])
        info.next_event_id = int(arrs.next_event_id[i])
        info.last_processed_event = int(arrs.last_processed_event[i])
        info.signal_count = int(arrs.signal_count[i])
        info.completion_event_batch_id = int(arrs.completion_event_batch_id[i])
        info.last_event_task_id = int(arrs.last_event_task_id[i])
        info.decision_version = int(arrs.decision_version[i])
        info.decision_schedule_id = int(arrs.decision_schedule_id[i])
        info.decision_started_id = int(arrs.decision_started_id[i])
        info.decision_attempt = int(arrs.decision_attempt[i])
        info.decision_timeout = int(arrs.decision_timeout[i])
        info.decision_scheduled_timestamp = int(arrs.decision_scheduled_ts[i])
        info.decision_started_timestamp = int(arrs.decision_started_ts[i])
        info.decision_original_scheduled_timestamp = int(
            arrs.decision_original_scheduled_ts[i])
        if info.cancel_requested:
            # the latest request, read from the tail: a batch held as its
            # bytes before the request is never decoded for it
            cancel_ev = next(
                (e for b in reversed(last_run) for e in reversed(b.events)
                 if e.event_type == EventType.WorkflowExecutionCancelRequested),
                None)
            if cancel_ev is not None:
                info.cancel_request_id = cancel_ev.get("cancel_request_id", "")
        started_ev = by_id.get(info.decision_started_id)
        if started_ev is not None:
            info.decision_request_id = started_ev.get("request_id", "")

        ms.current_version = int(arrs.current_version[i])

        # version histories (current branch only: rebuilds replay ONE
        # lineage; multi-branch grafting is the caller's bookkeeping)
        count = int(arrs.vh_count[i][int(arrs.current_branch[i])])
        ids = arrs.vh_event_ids[i][int(arrs.current_branch[i])]
        versions = arrs.vh_versions[i][int(arrs.current_branch[i])]
        ms.version_histories.histories[0] = VersionHistory(items=[
            VersionHistoryItem(int(ids[k]), int(versions[k]))
            for k in range(count)
        ])
        ms.version_histories.current_index = 0

        # pending activities
        ms.pending_activity_info_ids.clear()
        ms.pending_activity_id_to_event_id.clear()
        act = arrs.activities
        for k in np.nonzero(act.occ[i])[0]:
            sched_id = int(act.schedule_id[i][k])
            sched_ev = by_id.get(sched_id)
            if sched_ev is None:
                return None
            retry = sched_ev.get("retry_policy")
            started_id = int(act.started_id[i][k])
            astart_ev = by_id.get(started_id)
            ai = ActivityInfo(
                version=int(act.version[i][k]),
                schedule_id=sched_id,
                scheduled_event_batch_id=int(act.batch_id[i][k]),
                scheduled_time=int(act.scheduled_time[i][k]),
                started_id=started_id,
                started_time=int(act.started_time[i][k]),
                activity_id=sched_ev.get("activity_id", ""),
                domain_id=sched_ev.get("domain_id", "") or info.domain_id,
                task_list=sched_ev.get("task_list", ""),
                schedule_to_start_timeout=int(act.sched_to_start[i][k]),
                schedule_to_close_timeout=int(act.sched_to_close[i][k]),
                start_to_close_timeout=int(act.start_to_close[i][k]),
                heartbeat_timeout=int(act.heartbeat[i][k]),
                cancel_requested=bool(act.cancel_requested[i][k]),
                cancel_request_id=int(act.cancel_request_id[i][k]),
                request_id=(astart_ev.get("request_id", "")
                            if astart_ev is not None else ""),
                last_heartbeat_updated_time=int(act.last_heartbeat[i][k]),
                timer_task_status=int(act.timer_status[i][k]),
                attempt=int(act.attempt[i][k]),
                has_retry_policy=bool(act.has_retry[i][k]),
            )
            if ai.has_retry_policy and retry is not None:
                ai.initial_interval = retry.initial_interval_seconds
                ai.backoff_coefficient = retry.backoff_coefficient
                ai.maximum_interval = retry.maximum_interval_seconds
                ai.maximum_attempts = retry.maximum_attempts
                ai.non_retriable_errors = list(retry.non_retriable_error_reasons)
                if retry.expiration_interval_seconds:
                    ai.expiration_time = ai.scheduled_time + (
                        retry.expiration_interval_seconds * 1_000_000_000)
            ms.pending_activity_info_ids[sched_id] = ai
            ms.pending_activity_id_to_event_id[ai.activity_id] = sched_id

        # pending user timers
        ms.pending_timer_info_ids.clear()
        ms.pending_timer_event_id_to_id.clear()
        tmr = arrs.timers
        for k in np.nonzero(tmr.occ[i])[0]:
            started_id = int(tmr.started_id[i][k])
            started = by_id.get(started_id)
            if started is None:
                return None
            ti = TimerInfo(
                version=int(tmr.version[i][k]),
                timer_id=started.get("timer_id", ""),
                started_id=started_id,
                expiry_time=int(tmr.expiry_time[i][k]),
                task_status=int(tmr.task_status[i][k]),
            )
            ms.pending_timer_info_ids[ti.timer_id] = ti
            ms.pending_timer_event_id_to_id[started_id] = ti.timer_id

        # pending children
        ms.pending_child_execution_info_ids.clear()
        ch = arrs.children
        for k in np.nonzero(ch.occ[i])[0]:
            initiated_id = int(ch.initiated_id[i][k])
            init_ev = by_id.get(initiated_id)
            if init_ev is None:
                return None
            started_id = int(ch.started_id[i][k])
            cstart_ev = by_id.get(started_id)
            ms.pending_child_execution_info_ids[initiated_id] = ChildExecutionInfo(
                version=int(ch.version[i][k]),
                initiated_id=initiated_id,
                initiated_event_batch_id=int(ch.batch_id[i][k]),
                started_id=started_id,
                started_workflow_id=init_ev.get("workflow_id", ""),
                started_run_id=(cstart_ev.get("run_id", "")
                                if cstart_ev is not None else ""),
                create_request_id=init_ev.get("create_request_id", ""),
                domain_id=init_ev.get("domain_id", "") or info.domain_id,
                workflow_type_name=init_ev.get("workflow_type", ""),
                parent_close_policy=init_ev.get("parent_close_policy", 0) or 0,
            )

        # pending request-cancels / signals
        ms.pending_request_cancel_info_ids.clear()
        for k in np.nonzero(arrs.cancels.occ[i])[0]:
            initiated_id = int(arrs.cancels.initiated_id[i][k])
            init_ev = by_id.get(initiated_id)
            if init_ev is None:
                return None
            ms.pending_request_cancel_info_ids[initiated_id] = RequestCancelInfo(
                version=int(arrs.cancels.version[i][k]),
                initiated_event_batch_id=int(arrs.cancels.batch_id[i][k]),
                initiated_id=initiated_id,
                cancel_request_id=init_ev.get("cancel_request_id", ""),
            )
        ms.pending_signal_info_ids.clear()
        for k in np.nonzero(arrs.signals.occ[i])[0]:
            initiated_id = int(arrs.signals.initiated_id[i][k])
            init_ev = by_id.get(initiated_id)
            if init_ev is None:
                return None
            ms.pending_signal_info_ids[initiated_id] = SignalInfo(
                version=int(arrs.signals.version[i][k]),
                initiated_event_batch_id=int(arrs.signals.batch_id[i][k]),
                initiated_id=initiated_id,
                signal_request_id=init_ev.get("signal_request_id", ""),
                signal_name=init_ev.get("signal_name", ""),
            )
        return ms
