"""Persisted mutable-state snapshots: every cold path O(suffix).

The reference never rebuilds a live workflow's mutable state from event
0 on the hot path — the ExecutionStore persists it and history is only
consulted for the suffix (PAPER.md §1 layers 2-3, `ExecutionManager`).
PRs 6-10 made the STEADY state O(new events) (resident cache, serving
tier), but every cold consumer — host restart, serving chain break,
cold admit, rebuild — still paid full-history replay. This module is
the durable twin of the resident cache that closes that last residue:

- `SnapshotRecord` is one workflow's device `ReplayState` row (W=1,
  base layout) serialized with its canonical payload, device-chosen
  branch, content address (batch count + last-batch CRC32 — the SAME
  addressing scheme the resident/pack caches share, engine/cache.py),
  the pack interner snapshot (so suffix lanes encoded after hydration
  are byte-identical to a resumed full pack), and a blob CRC;
- `SnapshotStore` holds the latest record per run, durably: `put`
  appends a versioned "snap" record to the WAL (both backends — JSONL
  and SqliteLog — via the stores' attached log; WAL_VERSION v3
  introduces the type through the usual migration machinery) and
  recovery replays the records back in. Invalidation is DERIVED, not
  logged: the history store drops a snapshot whenever a mutation
  rewrites bytes under its address (tail overwrite at/before the
  snapshot point, NDC branch switch, run deletion), and recovery
  replays those same mutation records in the same order, so the
  in-memory store converges without tombstones;
- `Snapshotter` writes records under a policy
  (`CADENCE_TPU_SNAPSHOT_MIN_EVENTS` — the age floor before a workflow
  is worth a record; `CADENCE_TPU_SNAPSHOT_EVERY_EVENTS` — appended
  events between snapshots), and every write is CHECKSUM-GATED: the
  resident payload row must equal the oracle's live mutable-state row
  byte for byte (branch included) or the record is never written;
- `seed_caches` is the one hydration primitive every cold consumer
  shares (`DeviceRebuilder`, `TPUReplayEngine.verify_all`'s partition,
  the serving scheduler's chain-break/cold-admit fallback): validate →
  unpack → admit into the resident pool + seed the pack cache at the
  snapshot point. A torn blob (CRC/shape mismatch), stale address, or
  foreign layout is DETECTED, COUNTED, and IGNORED — the caller falls
  back to full replay; a wrong state is never served. Crash safety is
  the WAL's: the crashsim cut-point matrix sweeps snapshot records like
  any other type.

Counters land under `tpu.snapshot/*` (writes, checksum-skips, hydrates,
ignored-stale, ignored-torn, gate-chains, write-errors) plus the
entry/byte gauges the `admin snapshot` CLI verb rolls up, which the
writer's registry collector reads from the store before every render.
Each gate chain the serving policy runs is the span
`serving.snapshot-gate-chain`, inside the flush's `serving.snapshot`.
"""
from __future__ import annotations

import base64
import os
import threading
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.checksum import DEFAULT_LAYOUT, PayloadLayout
from ..utils import flightrecorder
from ..utils import metrics as m
from ..utils import tracing
from .cache import ContentAddress

#: snapshot record format version (inside the WAL's schema version: the
#: WAL header gates the record SET, this gates the blob layout)
SNAPSHOT_VERSION = 1

#: kill switch: CADENCE_TPU_SNAPSHOT=0 disables both writing and
#: hydration (every cold path back to full replay — the parity-audit
#: configuration, mirroring CADENCE_TPU_RESIDENT)
ENABLE_ENV = "CADENCE_TPU_SNAPSHOT"
#: min TOTAL packed events before a workflow earns a snapshot record
#: (the resident-age floor: tiny histories replay faster than they
#: hydrate)
MIN_EVENTS_ENV = "CADENCE_TPU_SNAPSHOT_MIN_EVENTS"
DEFAULT_MIN_EVENTS = 8
#: appended events since the last snapshot before the next one is due
EVERY_EVENTS_ENV = "CADENCE_TPU_SNAPSHOT_EVERY_EVENTS"
DEFAULT_EVERY_EVENTS = 32


def enabled() -> bool:
    return os.environ.get(ENABLE_ENV, "1") not in ("0", "false", "off")


def layout_signature(layout: PayloadLayout) -> Tuple[int, ...]:
    """The capacity tuple a snapshot's state arrays were shaped by; a
    record hydrates only into the exact layout that wrote it."""
    return (layout.max_version_history_items, layout.max_activities,
            layout.max_timers, layout.max_children,
            layout.max_request_cancels, layout.max_signals,
            layout.max_branches)


# ---------------------------------------------------------------------------
# state-row serialization (ReplayState W=1 pytree <-> bytes)
# ---------------------------------------------------------------------------


#: blob magic: flat little-endian leaf bytes in NamedTuple flatten
#: order (shapes/dtypes are implied by the layout template, so decode
#: is a handful of zero-copy frombuffer views per row — an npz per row
#: costs ~60 zip-member header parses and dominates a warm restart)
_BLOB_MAGIC = b"CSNP1\n"


def pack_state_row(state_row) -> bytes:
    """Serialize a W=1 ReplayState row to bytes: magic + each pytree
    leaf's raw bytes in NamedTuple flatten order — deterministic for a
    fixed layout, so unpack rebuilds the exact pytree from the layout's
    template spec alone."""
    import jax

    from ..ops.state import layout_of
    _treedef, fields, _total, _leaves = _row_template(layout_of(state_row))
    leaves = [np.asarray(l) for l in
              jax.tree_util.tree_leaves(jax.device_get(state_row))]
    parts = [_BLOB_MAGIC]
    for a, (_shape, dtype, _count, _off) in zip(leaves, fields):
        parts.append(np.ascontiguousarray(a, dtype=dtype).tobytes())
    return b"".join(parts)


class SnapshotFormatError(Exception):
    """Blob does not decode into this layout's ReplayState shapes — the
    torn/foreign-snapshot class callers must treat as a miss."""


#: layout signature -> (treedef, [(shape, dtype, count, offset) per
#: leaf], total blob bytes, the initial state's leaves on the host) — the
#: W=1 ReplayState template spec, built ONCE per layout: constructing a
#: fresh init_state (or recomputing per-leaf sizes) per unpack would cost
#: per-key overhead exactly where a warm restart earns its keep; the
#: leaves are the filler rows of a launch stacked on the host
_TEMPLATE_SPECS: Dict[tuple, tuple] = {}
_TEMPLATE_LOCK = threading.Lock()


def _row_template(layout: PayloadLayout):
    key = layout_signature(layout)
    spec = _TEMPLATE_SPECS.get(key)
    if spec is None:
        import jax

        from ..ops.state import init_state
        leaves, treedef = jax.tree_util.tree_flatten(
            jax.device_get(init_state(1, layout)))
        fields = []
        off = len(_BLOB_MAGIC)
        for a in leaves:
            fields.append((a.shape, a.dtype, int(a.size), off))
            off += a.nbytes
        spec = (treedef, fields, off, leaves)
        with _TEMPLATE_LOCK:
            _TEMPLATE_SPECS[key] = spec
    return spec


def unpack_state_row(blob: bytes, layout: PayloadLayout):
    """Bytes → W=1 ReplayState at `layout`; the blob's magic and exact
    byte length are validated against the layout's template spec, so a
    truncated, doctored, or foreign-layout blob raises
    SnapshotFormatError instead of producing a silently-wrong state.
    Leaves are zero-copy frombuffer views that stay host-side: an
    append launch over such rows stacks them on the host and puts the
    launch state on the device once a leaf (`resident._stack_padded`),
    not ~66 puts a workflow."""
    import jax

    treedef, fields, total, _leaves = _row_template(layout)
    if not blob.startswith(_BLOB_MAGIC):
        raise SnapshotFormatError("bad state-blob magic")
    if len(blob) != total:
        raise SnapshotFormatError(
            f"state blob is {len(blob)} bytes; layout expects {total}")
    arrs = [
        np.frombuffer(blob, dtype=dtype, count=count,
                      offset=off).reshape(shape)
        for shape, dtype, count, off in fields
    ]
    return jax.tree_util.tree_unflatten(treedef, arrs)


# ---------------------------------------------------------------------------
# the record + durable store
# ---------------------------------------------------------------------------


@dataclass
class SnapshotRecord:
    """One run's persisted device state at a known history point."""

    key: Tuple[str, str, str]
    batch_count: int          # content address: batches covered
    last_batch_crc: int       # content address: CRC32 of batch n-1
    events: int               # total packed events covered (lane rows)
    history_size: int         # mutable-state history_size at the point
    branch: int               # device-chosen current branch index
    payload: np.ndarray       # [width] int64 canonical payload row
    state_blob: bytes         # packed ReplayState row (pack_state_row)
    blob_crc: int             # CRC32 of state_blob (torn detection)
    interner: Dict[str, int]  # pack interner as of the snapshot point
    layout: Tuple[int, ...]   # layout_signature of the writing engine
    version: int = SNAPSHOT_VERSION

    @property
    def address(self) -> ContentAddress:
        return ContentAddress(self.batch_count, self.last_batch_crc)

    @property
    def nbytes(self) -> int:
        return len(self.state_blob) + self.payload.nbytes


class SnapshotStore:
    """Latest snapshot per run, durable through the cluster WAL.

    The history store holds a back-reference (Stores wires it) and drops
    entries on the content-address-invalidating mutations the resident/
    pack caches key on: a tail overwrite at/before the snapshot point,
    an NDC current-branch switch, and run deletion. Recovery replays the
    same mutation records in the same order, so no tombstone record is
    needed — the in-memory view converges deterministically."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._snaps: Dict[Tuple[str, str, str], SnapshotRecord] = {}
        self._wal = None

    def put(self, rec: SnapshotRecord) -> None:
        from . import crashpoints
        from .durability import snapshot_record
        crashpoints.fire("store.snapshot.put")
        with self._lock:
            self._snaps[rec.key] = rec
            if self._wal is not None:
                self._wal.append(snapshot_record(rec))

    def restore(self, rec: SnapshotRecord) -> None:
        """Recovery: install a record without re-logging it."""
        with self._lock:
            self._snaps[rec.key] = rec

    def get(self, key: Tuple[str, str, str]) -> Optional[SnapshotRecord]:
        with self._lock:
            return self._snaps.get(key)

    def drop(self, key: Tuple[str, str, str]) -> bool:
        with self._lock:
            return self._snaps.pop(key, None) is not None

    def invalidate_overwrite(self, key: Tuple[str, str, str],
                             rewritten_batch_index: int) -> None:
        """A tail overwrite rewrote batches from `rewritten_batch_index`
        on: a snapshot covering any rewritten batch is dead; one strictly
        before the rewrite point is still a valid prefix and survives."""
        with self._lock:
            rec = self._snaps.get(key)
            if rec is not None and rec.batch_count > rewritten_batch_index:
                del self._snaps[key]

    def invalidate_branch_switch(self, key: Tuple[str, str, str]) -> None:
        """NDC moved the current branch: the snapshot's lineage is no
        longer the one consumers replay — same rule as the resident
        cache's branch-switch invalidation."""
        self.drop(key)

    def keys(self) -> List[Tuple[str, str, str]]:
        with self._lock:
            return list(self._snaps.keys())

    def items(self) -> List[Tuple[Tuple[str, str, str], SnapshotRecord]]:
        with self._lock:
            return list(self._snaps.items())

    def __len__(self) -> int:
        with self._lock:
            return len(self._snaps)

    def stats(self) -> Dict[str, object]:
        with self._lock:
            recs = list(self._snaps.values())
        return {
            "entries": len(recs),
            "bytes": sum(r.nbytes for r in recs),
            "events_covered": sum(r.events for r in recs),
        }


# ---------------------------------------------------------------------------
# hydration: snapshot -> resident + pack cache (the shared cold-path seam)
# ---------------------------------------------------------------------------


def validate_record(rec: SnapshotRecord, layout: PayloadLayout,
                    registry=None) -> bool:
    """Cheap integrity gate shared by every consumer: format version,
    layout signature, and blob CRC. Counts and returns False on any
    mismatch — the caller falls back to full replay."""
    reg = registry if registry is not None else m.DEFAULT_REGISTRY
    if rec.version != SNAPSHOT_VERSION \
            or tuple(rec.layout) != layout_signature(layout):
        reg.inc(m.SCOPE_TPU_SNAPSHOT, m.M_SNAP_IGNORED_STALE)
        return False
    if zlib.crc32(rec.state_blob) != rec.blob_crc:
        reg.inc(m.SCOPE_TPU_SNAPSHOT, m.M_SNAP_IGNORED_TORN)
        return False
    return True


def seed_caches(rec: SnapshotRecord, resident, pack_cache,
                layout: PayloadLayout, registry=None) -> bool:
    """Admit a validated snapshot into the resident pool and seed the
    pack cache's interner at the snapshot point, so every later suffix
    encode resumes from the persisted interner (byte-identical to a
    full pack) instead of re-encoding the prefix. The ADDRESS validity
    against the current history is the caller's job (it holds either
    the full batches or the boundary batch from a range read); this
    only guards the blob itself."""
    reg = registry if registry is not None else m.DEFAULT_REGISTRY
    try:
        state_row = unpack_state_row(rec.state_blob, layout)
    except SnapshotFormatError:
        reg.inc(m.SCOPE_TPU_SNAPSHOT, m.M_SNAP_IGNORED_TORN)
        return False
    if not resident.admit(rec.key, rec.address, state_row,
                          rec.payload, rec.branch):
        return False
    if pack_cache is not None:
        pack_cache.seed_suffix(rec.key, rec.address, rec.interner,
                               rec.events)
    reg.inc(m.SCOPE_TPU_SNAPSHOT, m.M_SNAP_HYDRATES)
    return True


def seed_from_batches(snapshots: Optional[SnapshotStore], resident,
                      pack_cache, key, batches,
                      layout: PayloadLayout, registry=None) -> bool:
    """Full-batch-list hydration (verify/rebuild consumers, which hold
    the history anyway): validate the record's content address against
    `batches` (exact or prefix — the resident/pack relation), then seed.
    A stale address (tail overwrite, reset rewrite) is counted and
    ignored; the caller's cold path takes the key."""
    from .cache import address_relation

    if snapshots is None or not enabled():
        return False
    rec = snapshots.get(key)
    if rec is None:
        return False
    reg = registry if registry is not None else m.DEFAULT_REGISTRY
    if not validate_record(rec, layout, reg):
        return False
    if address_relation(rec.address, batches) not in ("exact", "prefix"):
        reg.inc(m.SCOPE_TPU_SNAPSHOT, m.M_SNAP_IGNORED_STALE)
        return False
    return seed_caches(rec, resident, pack_cache, layout, reg)


# ---------------------------------------------------------------------------
# the writer (policy + checksum gate)
# ---------------------------------------------------------------------------


@dataclass
class SweepReport:
    considered: int = 0
    written: int = 0
    skipped_policy: int = 0
    skipped_checksum: int = 0
    skipped_not_at_tip: int = 0
    keys_written: List[tuple] = field(default_factory=list)


class Snapshotter:
    """Checksum-gated snapshot writer over the resident pool.

    One per replay engine (TPUReplayEngine.snapshotter()), sharing its
    stores / resident cache / pack cache / layout. `note_append` feeds
    the appended-events policy counter from the serving tier;
    `snapshot_key` writes one record when the gates pass; `sweep`
    drives every resident key (the admin/deploy warm-up verb)."""

    def __init__(self, stores, resident, pack_cache,
                 layout: PayloadLayout = DEFAULT_LAYOUT,
                 registry=None, min_events: Optional[int] = None,
                 every_events: Optional[int] = None) -> None:
        self.stores = stores
        self.resident = resident
        self.pack_cache = pack_cache
        self.layout = layout
        self.metrics = registry if registry is not None \
            else m.DEFAULT_REGISTRY
        self.min_events = (min_events if min_events is not None
                           else int(os.environ.get(MIN_EVENTS_ENV,
                                                   str(DEFAULT_MIN_EVENTS))))
        self.every_events = (every_events if every_events is not None
                             else int(os.environ.get(
                                 EVERY_EVENTS_ENV,
                                 str(DEFAULT_EVERY_EVENTS))))
        self._lock = threading.Lock()
        #: snapshot-shipping replication hook (engine/replication.
        #: ReplicationPublisher.publish_snapshot): called with every
        #: record this writer persists, so standby regions receive the
        #: same checksum-gated records the local cold paths hydrate from
        self.shipper: Optional[callable] = None
        #: per-key appended events since the last snapshot write
        self._since: Dict[tuple, int] = {}
        #: keys the policy should NOT re-probe until every_events more
        #: accumulate: keys known to hold a stored record, and keys
        #: whose last write attempt failed a gate (widened row, below
        #: the age floor, not at tip). Keeps due() off the store —
        #: which may be a remote proxy on a ServiceHost — and keeps the
        #: full gate chain from re-running per committed transaction.
        self._known: set = set()
        self.metrics.add_collector(self._collect_gauges)

    def _scope(self):
        return self.metrics.scope(m.SCOPE_TPU_SNAPSHOT)

    def note_append(self, key: tuple, events: int) -> None:
        with self._lock:
            if len(self._since) > 65536:
                self._since.clear()  # bounded; cleared keys re-accumulate
            self._since[key] = self._since.get(key, 0) + int(events)

    def due(self, key: tuple) -> bool:
        """Whether the policy wants a fresh record for this key: no
        stored snapshot yet, or enough events appended since the last
        one. The full gates (tip match, checksum) run in snapshot_key.
        The counter check comes first and a known-snapshotted key never
        re-probes the store — due() sits on the serving tier's
        per-transaction path, where the store may be a remote proxy."""
        if not enabled():
            return False
        with self._lock:
            if self._since.get(key, 0) >= self.every_events:
                return True
            if key in self._known:
                return False
        if self.stores.snapshot.get(key) is None:
            return True
        self._defer(key)
        return False

    def _defer(self, key: tuple, reset_counter: bool = False) -> None:
        """Mark a key not-due until every_events more accumulate (a
        record exists, or — with reset_counter — the last write attempt
        failed a gate): the per-transaction serving hook must never
        re-probe the store or re-run the gate chain on every commit."""
        with self._lock:
            if reset_counter:
                self._since[key] = 0
            if len(self._known) > 65536:
                self._known.clear()
            self._known.add(key)

    def maybe_snapshot(self, key: tuple) -> bool:
        """The per-transaction policy hook (the serving drain calls it
        after each parity-clean append): write when due; a gate-failed
        attempt DEFERS the key until every_events more accumulate, so a
        key that can't snapshot (widened row, below the age floor)
        costs at most one gate chain per policy window, never one per
        commit."""
        if not self.due(key):
            return False
        self._scope().inc(m.M_SNAP_GATE_CHAINS)
        with tracing.span("serving.snapshot-gate-chain"):
            written = self.snapshot_key(key)
        if written:
            return True
        self._defer(key, reset_counter=True)
        return False

    def snapshot_key(self, key: tuple, force: bool = False) -> bool:
        """Write one snapshot record if every gate passes:

        1. a base-rung resident entry exists and sits at the store's
           single-lineage tip (count + tail CRC — never snapshot a
           state that lags or leads the history);
        2. the policy says it's due (total events >= min_events, and
           due() unless `force`);
        3. the CHECKSUM GATE: the resident payload row and branch equal
           the oracle's live mutable state byte for byte — a mismatch is
           counted (`checksum-skips`) and nothing is written.
        """
        if not enabled():
            return False
        entry = self.resident.entry_for(key)
        if entry is None or entry.rung != 0:
            return False
        hs = self.stores.history
        try:
            if hs.branch_count(*key) > 1 or hs.get_current_branch(*key) != 0:
                return False
            total = hs.batch_count(*key)
            if total == 0 or entry.address.batch_count != total:
                return False
            boundary = hs.as_history_batches_range(
                *key, from_batch=total - 1)
        except Exception:
            return False
        from .cache import batch_crc
        if not boundary \
                or batch_crc(boundary[0]) != entry.address.last_batch_crc:
            return False  # resident not at the stored tip
        events = (self.pack_cache.events_for(key, entry.address)
                  if self.pack_cache is not None else None)
        if not force:
            if not self.due(key):
                return False
            if events is not None and events < self.min_events:
                return False
        # checksum gate against the oracle's live mutable state
        try:
            from ..core.checksum import STICKY_ROW_INDEX, payload_row
            ms = self.stores.execution.get_workflow(*key)
            live = payload_row(ms, self.layout)
            live[STICKY_ROW_INDEX] = 0
            live_branch = int(ms.version_histories.current_index)
        except Exception:
            return False
        if not (entry.payload == live).all() \
                or int(entry.branch) != live_branch:
            self._scope().inc(m.M_SNAP_CHECKSUM_SKIPS)
            return False
        interner = (self.pack_cache.interner_for(key, entry.address)
                    if self.pack_cache is not None else None)
        if interner is None or events is None:
            # no pack entry at this address: pay ONE full pack at write
            # time (the write path may; cold READ paths never do) to
            # recover the interner snapshot + event count
            if self.pack_cache is None:
                return False
            batches = hs.as_history_batches(*key)
            self.pack_cache.encode(key, batches)
            interner = self.pack_cache.interner_for(key, entry.address)
            events = self.pack_cache.events_for(key, entry.address)
            if interner is None or events is None:
                return False
        if not force and events < self.min_events:
            return False
        blob = pack_state_row(entry.state)
        # the persisted history-size accounting (lazily cached on the
        # store, O(appended) warm): a warm restart recovers it in
        # O(suffix) instead of re-serializing the prefix
        try:
            history_size = hs.serialized_size(*key)
        except Exception:
            return False
        rec = SnapshotRecord(
            key=key, batch_count=entry.address.batch_count,
            last_batch_crc=entry.address.last_batch_crc,
            events=int(events), history_size=int(history_size),
            branch=int(entry.branch),
            payload=np.asarray(entry.payload, dtype=np.int64),
            state_blob=blob, blob_crc=zlib.crc32(blob),
            interner=dict(interner),
            layout=layout_signature(self.layout))
        self.stores.snapshot.put(rec)
        if self.shipper is not None:
            try:
                self.shipper(rec)
            except Exception:
                # shipping is an optimization for the OTHER region's warm
                # start; a publish failure must never fail the local write
                pass
        self._defer(key, reset_counter=True)
        self._scope().inc(m.M_SNAP_WRITES)
        return True

    def refresh_gauges(self) -> None:
        """Set the entry and byte gauges from the store's `stats()`, the
        one call a local `SnapshotStore` and a service host's remote
        proxy both answer. A sweep calls it, and the registry before
        each render (`_collect_gauges`); the policy's write path does
        not, so a serving flush pays no round trip for it."""
        stats = self.stores.snapshot.stats()
        self.metrics.gauge(m.SCOPE_TPU_SNAPSHOT, m.M_SNAP_ENTRIES,
                           float(stats["entries"]))
        self.metrics.gauge(m.SCOPE_TPU_SNAPSHOT, m.M_SNAP_BYTES,
                           float(stats["bytes"]))

    def _collect_gauges(self) -> None:
        """The registry's collector: the gauges read fresh before every
        render, whichever path scrapes. A store that does not answer
        leaves them as they were and says so on the flight recorder; a
        scrape never fails for it."""
        try:
            self.refresh_gauges()
        except Exception as exc:
            flightrecorder.emit("snapshot-gauges-unread",
                                error=f"{type(exc).__name__}: {exc}")

    def sweep(self, keys=None, force: bool = False) -> SweepReport:
        """Snapshot every resident key (or `keys`); the admin verb and
        deploy warm-up path. `force` bypasses the due/min-events policy
        (never the tip or checksum gates)."""
        report = SweepReport()
        for key in (keys if keys is not None else self.resident.keys()):
            report.considered += 1
            pre = self.metrics.counter(m.SCOPE_TPU_SNAPSHOT,
                                       m.M_SNAP_CHECKSUM_SKIPS)
            if self.snapshot_key(key, force=force):
                report.written += 1
                report.keys_written.append(key)
            elif self.metrics.counter(m.SCOPE_TPU_SNAPSHOT,
                                      m.M_SNAP_CHECKSUM_SKIPS) > pre:
                report.skipped_checksum += 1
            elif not force and not self.due(key):
                report.skipped_policy += 1
            else:
                report.skipped_not_at_tip += 1
        self.refresh_gauges()
        return report
