"""HBM-resident mutable-state cache: O(new-events) append replay.

The reference never replays a live workflow from event 0 on the hot
path: the history engine's execution/context LRU cache
(service/history/execution/cache.go) keeps each open workflow's mutable
state warm, and a decision transaction applies only its new events.
Before this module the device path had no analogue — every verify or
rebuild replayed the FULL history, so per-transaction cost was
O(history) and long-lived workflows set the p99 floor for decision hot
loops.

ResidentStateCache is the device twin of that execution cache:

- per-workflow final `ReplayState` rows stay RESIDENT in HBM between
  calls, LRU-bounded by a configurable HBM byte budget. A row that
  arrives alone (a serving flush's cold admit, a hydrated snapshot, an
  append's row at a widened rung) is pinned as a W=1 slice of the
  batched scan state, one pytree of device arrays per workflow
  (`admit`). Rows that arrive together, the verified rows of a bulk
  chunk (`admit_chunk`) and the base-rung rows of an append chunk, are
  pinned as VIEWS of the chunk's own state: (chunk state, row index),
  no program launched and no device buffer made until somebody reads
  the row's `state`; an exact hit never does, and a bulk reader reads
  the chunk whole (`host_rows`);
- entries are content-addressed by the same (workflow key, batch count,
  last-batch CRC32) scheme the pack cache uses — the shared helper in
  engine/cache.py, so the two caches can never drift on invalidation
  semantics. A tail overwrite, reset rewrite, or NDC branch switch
  changes the address (or the lineage shape) and the stale entry is
  dropped, counted, never served;
- an append replays ONLY the new batches: suffix lanes (packed through
  the pack cache's suffix path) scan against the resident state via
  ops/replay.replay_from_state — the kernel generalized to take a
  carried initial state instead of the zero state;
- capacity overflow during an append stays on device: the escalation
  ladder widens the PRE-append resident state (K→2K→4K) and re-replays
  just the suffix (engine/ladder.escalate_resident); resolved rows
  remain resident at the widened layout and re-narrow to base once
  their pending load drains (ops/state.narrow_ok) — the widen/re-narrow
  round trip that keeps escalated rows out of the full-replay path;
- under a serving mesh (set_mesh) the pool SHARDS across the devices:
  each workflow's pinned state lives on the device its key hashes to
  (parallel/mesh.workflow_shard — the same stable key→shard assignment
  the mesh-aware executor lays chunks out by), the HBM budget splits
  into equal per-device slices with per-device LRU eviction, and append
  replays group by owning device so the from-state launch — and any
  ladder widen/re-narrow it escalates into — runs on the device already
  holding the state, never dragging a resident row across the mesh.

Correctness gate: the mutable-state checksum is the oracle, same as
always — resident incremental replay must produce byte-identical
canonical payloads (and CRCs) to a full-history replay, for every
workload suite, after every invalidation path. Appends are batched
through the pipelined bulk executor (engine/executor.py), so suffix
packing overlaps device replay exactly like the cold path's chunks.

Counters land under `tpu.resident/*` (hits, suffix-hits, misses,
invalidations, evictions, events-appended, widened/renarrowed rows,
view-rows, views-materialised, host-stacked-rows, row-slices: one a
W=1 `slice_row` launch the pool makes, in `extract_row` and in a view's
first read) and the resident-bytes/entries/budget gauges —
pre-registered on /metrics by ServiceHost so scrapes always expose the
names.

An append's legs are spans, each ONCE A CHUNK and under a fixed name:
`resident.launch` (the launch state stacked, the suffix lanes put on the
device, the from-state scan dispatched), `resident.device-wait` (the
blocking wait and readback) and `resident.readmit` (the chunk's
successful rows re-pinned: as views of its final state at the base
rung, sliced and narrowed where they may be at a widened one; its error
rows invalidated; rows the ladder escalates are not in it). No
span a row, and no prefix argument: the caller's span (`serving.flush`,
`rebuild.suffix-replay`, `verify.suffix-replay`) says which path ran
them.
"""
from __future__ import annotations

import os
import threading
import weakref
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.checksum import DEFAULT_LAYOUT, PayloadLayout
from ..ops.encode import NUM_LANES, history_length
from ..utils import metrics as m
from ..utils import tracing
from .cache import ContentAddress, address_relation, content_address

#: HBM byte budget for resident states (LRU evicts past it); the default
#: holds ~4k base-layout rows — sized for the serving tier, overridable
#: per deployment
BUDGET_ENV = "CADENCE_TPU_RESIDENT_HBM_BUDGET"
DEFAULT_BUDGET = 256 << 20
#: workflows per append-replay chunk through the bulk executor
CHUNK_ENV = "CADENCE_TPU_RESIDENT_CHUNK"
DEFAULT_CHUNK = 2048
#: kill switch (CADENCE_TPU_RESIDENT=0 forces every call down the
#: full-replay path; the parity-audit configuration)
ENABLE_ENV = "CADENCE_TPU_RESIDENT"

#: live caches (tests reset them between cases: entries hold device
#: buffers that must not leak across test boundaries)
_LIVE: "weakref.WeakSet[ResidentStateCache]" = weakref.WeakSet()


def reset_all() -> None:
    """Clear every live cache's entries (conftest isolation seam)."""
    for cache in list(_LIVE):
        cache.clear()


def enabled() -> bool:
    return os.environ.get(ENABLE_ENV, "1") not in ("0", "false", "off")


def _bucket(n: int, floor: int) -> int:
    return max(floor, 1 << (max(1, int(n)) - 1).bit_length())


def _tree_nbytes(tree) -> int:
    """Device bytes of a pytree's leaves."""
    return int(sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree)))


class _ChunkPin:
    """What the views of one chunk (bulk-verified, or appended to) share
    on ONE device: the chunk's [W, ...] state there (all of it under an
    unsharded pool, the device's own rows under a sharded one), held
    whole, padding rows and rows no longer viewed included, until its
    last view lets go."""

    __slots__ = ("state", "nbytes", "shard", "live", "lock", "pool",
                 "__weakref__")

    def __init__(self, state, shard: int, pool) -> None:
        self.state = state
        self.nbytes = _tree_nbytes(state)
        self.shard = shard
        #: views charged to the pool's slice (guarded by the pool's lock)
        self.live = 0
        #: one materialisation of this chunk at a time
        self.lock = threading.Lock()
        self.pool = weakref.ref(pool)


@dataclass(eq=False)
class ResidentEntry:
    """One workflow's pinned state + the host-side row that serves exact
    hits without touching the device. The state is either a W=1 row or,
    for a row admitted out of a chunk, a VIEW (chunk, row index) that
    the first read of `state` turns into the W=1 row."""

    payload: np.ndarray      # [base_width] canonical payload row
    branch: int              # device-chosen current branch
    address: ContentAddress
    rung: int                # 0 = base layout; r > 0 = widened 2**r
    nbytes: int              # what the entry itself counts in its slice
    _state: object = field(default=None, repr=False)  # W=1 device arrays
    #: a view's chunk and row, until the row is materialised (the pin's
    #: lock); an evicted view keeps them, so a caller still holding the
    #: entry can read its state
    _chunk: Optional[_ChunkPin] = field(default=None, repr=False)
    _row: int = 0
    #: the pin this entry is counted a live view of, while it is in the
    #: pool and not materialised (the pool's lock)
    _charge: Optional[_ChunkPin] = field(default=None, repr=False)

    @property
    def state(self):
        """The W=1 ReplayState. Reading it materialises a view: one
        `slice_row` launch on the device that holds the chunk, after
        which the entry keeps the row and lets go of the chunk."""
        pin = self._chunk
        if pin is not None:
            _materialise(self, pin)
        return self._state

    @property
    def is_view(self) -> bool:
        return self._chunk is not None


def _materialise(entry: ResidentEntry, pin: _ChunkPin) -> None:
    with pin.lock:
        if entry._chunk is None:  # another reader got there first
            return
        entry._state = _slice_row(pin.state, entry._row)
        entry._chunk = None
    pool = pin.pool()
    if pool is not None:
        pool._view_materialised(entry)


@dataclass
class AppendResult:
    """Outcome of one append transaction (aligned with replay_append's
    items): resolved rows carry the post-append canonical payload;
    unresolved ones name the kernel error and fall to the caller's
    oracle arbitration (their entry is already invalidated)."""

    ok: bool
    payload: Optional[np.ndarray] = None
    branch: int = 0
    error: int = 0
    rung: int = 0
    escalated: bool = False


@dataclass
class AppendReport:
    """Per-call accounting of `replay_append_report`."""

    transactions: int = 0
    events_appended: int = 0
    escalated_rows: int = 0
    #: (workflows, suffix event axis) per launched chunk — the
    #: O(new-events) seam: equal suffixes launch equal shapes no matter
    #: how long the underlying histories are
    chunk_shapes: List[Tuple[int, int]] = field(default_factory=list)


class ResidentStateCache:
    """Content-addressed LRU of HBM-resident per-workflow ReplayStates.

    What an entry pins, and the budget rule. `resident_bytes` (and each
    device's slice of it) is what the pool keeps alive, and eviction
    holds it at or under the budget after every admission and every
    materialisation:

    - a W=1 row (`admit`, or a view once its `state` was read) counts
      one row: its device leaves plus the host payload row;
    - a view (`admit_chunk`, an append's base-rung rows) pins the WHOLE
      of its chunk's state on its device — padding rows, rows that
      failed the verify or the append, rows whose
      views were evicted, invalidated or materialised since — so the
      chunk counts whole, once, in that device's slice for as long as
      one view of it is live there, and each view adds only its host
      payload row. When the chunk's last view in the slice is evicted,
      invalidated, replaced or materialised, the chunk's bytes leave the
      count and the pool's last reference to the state goes with them.

    Views of a chunk are admitted together, so they sit together in the
    LRU and eviction takes them in a run; a view that was looked up
    since sits later, and the chunk stays counted until eviction reaches
    it too. A chunk that cannot fit its slice of the budget beside its
    views' payload rows is admitted row by row instead.
    """

    def __init__(self, layout: PayloadLayout = DEFAULT_LAYOUT,
                 budget_bytes: Optional[int] = None,
                 registry=None, ladder=None,
                 chunk_workflows: Optional[int] = None,
                 pipeline_depth: Optional[int] = None,
                 mesh=None) -> None:
        self.layout = layout
        self.budget_bytes = (budget_bytes if budget_bytes is not None
                             else int(os.environ.get(BUDGET_ENV,
                                                     str(DEFAULT_BUDGET))))
        self.metrics = registry if registry is not None else m.DEFAULT_REGISTRY
        #: widened-K escalation for appends that overflow the resident
        #: layout (engine/ladder.py); None disables escalation (flagged
        #: appends fail to the caller's oracle path)
        self.ladder = ladder
        self.chunk_workflows = (chunk_workflows if chunk_workflows
                                else int(os.environ.get(CHUNK_ENV,
                                                        str(DEFAULT_CHUNK))))
        self.pipeline_depth = pipeline_depth
        self._lock = threading.Lock()
        #: serving mesh (None = unsharded single-device pool); entries
        #: live per shard slice — OrderedDict per mesh position, each
        #: with its own byte count and LRU order
        self._mesh = mesh
        n = int(mesh.devices.size) if mesh is not None else 1
        self._slices: List["OrderedDict[tuple, ResidentEntry]"] = [
            OrderedDict() for _ in range(n)]
        self._slice_bytes: List[int] = [0] * n
        self._row_bytes_cache: Dict[PayloadLayout, int] = {}
        _LIVE.add(self)
        self._gauges()

    # -- mesh sharding ------------------------------------------------------

    def set_mesh(self, mesh) -> None:
        """(Re)bind the pool to a serving mesh: per-device slices keyed
        by workflow_shard, HBM budget split per device. Rebinding to a
        different width — or to the SAME width over different/permuted
        devices — drops every entry: states pinned under the old
        key→device assignment would otherwise serve from (and widen on)
        the wrong device, handing one jit inputs committed to two
        devices. An unsharded pool (width 1) never pins placement, so
        device identity is irrelevant there."""
        n = int(mesh.devices.size) if mesh is not None else 1
        new_devs = (tuple(mesh.devices.flat)
                    if mesh is not None and n > 1 else ())
        with self._lock:
            old_n = len(self._slices)
            old_devs = (tuple(self._mesh.devices.flat)
                        if self._mesh is not None and old_n > 1 else ())
            self._mesh = mesh
            if n == old_n and new_devs == old_devs:
                return
            self._clear_locked()
            # zero the outgoing width's per-device gauges BEFORE the
            # slices shrink: a dashboard keyed on resident-bytes-dev{d}
            # must not keep reporting phantom occupancy
            if old_n > 1:
                for d in range(old_n):
                    self.metrics.gauge(
                        m.SCOPE_TPU_RESIDENT,
                        m.device_metric(m.M_RESIDENT_BYTES, d), 0.0)
            self._slices = [OrderedDict() for _ in range(n)]
            self._slice_bytes = [0] * n
            self._gauges_locked()

    @property
    def n_shards(self) -> int:
        return len(self._slices)

    def shard_of(self, key: tuple) -> int:
        from ..parallel.mesh import workflow_shard
        return workflow_shard(key, len(self._slices))

    def device_of(self, key: tuple):
        """The mesh device owning this key's resident slice (None when
        the pool is unsharded — placement is wherever the state already
        lives, today's single-device behavior)."""
        if self._mesh is None or len(self._slices) <= 1:
            return None
        return self._mesh.devices.flat[self.shard_of(key)]

    @property
    def slice_budget(self) -> int:
        return max(1, self.budget_bytes // len(self._slices))

    # -- bookkeeping --------------------------------------------------------

    def _scope(self):
        return self.metrics.scope(m.SCOPE_TPU_RESIDENT)

    def _gauges(self) -> None:
        self._gauges_locked()

    def _gauges_locked(self) -> None:
        self.metrics.gauge(m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_BYTES,
                           float(sum(self._slice_bytes)))
        self.metrics.gauge(m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_ENTRIES,
                           float(sum(len(s) for s in self._slices)))
        self.metrics.gauge(m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_BUDGET_BYTES,
                           float(self.budget_bytes))
        if len(self._slices) > 1:
            # per-device occupancy of the sharded pool, next to the
            # executor's per-device series
            for d, nbytes in enumerate(self._slice_bytes):
                self.metrics.gauge(
                    m.SCOPE_TPU_RESIDENT,
                    m.device_metric(m.M_RESIDENT_BYTES, d), float(nbytes))

    def _row_nbytes(self, layout: PayloadLayout) -> int:
        """HBM bytes of one W=1 state row at `layout` (+ the host payload
        row); computed once per layout from the leaf dtypes/shapes."""
        cached = self._row_bytes_cache.get(layout)
        if cached is None:
            from ..ops.state import init_state
            cached = _tree_nbytes(init_state(1, layout))
            cached += self.layout.width * 8
            self._row_bytes_cache[layout] = cached
        return cached

    def __len__(self) -> int:
        with self._lock:
            return sum(len(s) for s in self._slices)

    def keys(self) -> List[tuple]:
        """Every pinned workflow key across the shard slices (the
        snapshot sweep's iteration seam, engine/snapshot.Snapshotter)."""
        with self._lock:
            return [k for sl in self._slices for k in sl.keys()]

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return sum(self._slice_bytes)

    def stats(self) -> Dict[str, object]:
        """Occupancy / hit-rate / budget rollup (the `admin resident`
        CLI verb and scrape consumers)."""
        reg = self.metrics
        hits = reg.counter(m.SCOPE_TPU_RESIDENT, m.M_CACHE_HITS)
        suffix = reg.counter(m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_SUFFIX_HITS)
        misses = reg.counter(m.SCOPE_TPU_RESIDENT, m.M_CACHE_MISSES)
        looked = hits + suffix + misses
        with self._lock:
            entries = sum(len(s) for s in self._slices)
            resident = sum(self._slice_bytes)
            widened = sum(1 for s in self._slices
                          for e in s.values() if e.rung > 0)
            views = sum(1 for s in self._slices
                        for e in s.values() if e._charge is not None)
            per_device = list(self._slice_bytes)
        return {
            "entries": entries,
            "widened_entries": widened,
            "view_entries": views,
            "resident_bytes": resident,
            "mesh_shards": len(per_device),
            "per_device_bytes": per_device,
            "budget_bytes": self.budget_bytes,
            "budget_used": (resident / self.budget_bytes
                            if self.budget_bytes else 0.0),
            "hits": hits,
            "suffix_hits": suffix,
            "misses": misses,
            "hit_rate": ((hits + suffix) / looked) if looked else 0.0,
            "invalidations": reg.counter(m.SCOPE_TPU_RESIDENT,
                                         m.M_CACHE_INVALIDATIONS),
            "evictions": reg.counter(m.SCOPE_TPU_RESIDENT,
                                     m.M_CACHE_EVICTIONS),
            "events_appended": reg.counter(m.SCOPE_TPU_RESIDENT,
                                           m.M_RESIDENT_EVENTS_APPENDED),
            "view_rows": reg.counter(m.SCOPE_TPU_RESIDENT,
                                     m.M_RESIDENT_VIEW_ROWS),
            "views_materialised": reg.counter(
                m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_VIEWS_MATERIALISED),
            "host_stacked_rows": reg.counter(
                m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_HOST_STACKED_ROWS),
            "row_slices": reg.counter(m.SCOPE_TPU_RESIDENT,
                                      m.M_RESIDENT_ROW_SLICES),
        }

    # -- lookup / admit / invalidate ----------------------------------------

    def lookup(self, key: tuple, batches,
               authoritative: bool = True) -> Optional[Tuple[str,
                                                             ResidentEntry]]:
        """("exact"|"suffix", entry) or None (miss).

        `batches` must be the key's CURRENT single-lineage history when
        `authoritative` (verify/serving paths): a stale entry — tail
        overwrite, reset rewrite — is then invalidated on sight. Pass
        authoritative=False when batches may be a deliberate prefix of
        the stored history (rebuild replaying up to a reset point): the
        entry stays, the call just misses."""
        scope = self._scope()
        with self._lock:
            sl = self._slices[self.shard_of(key)]
            entry = sl.get(key)
            if entry is not None:
                sl.move_to_end(key)
        if entry is not None:
            relation = address_relation(entry.address, batches)
            if relation == "exact":
                scope.inc(m.M_CACHE_HITS)
                return ("exact", entry)
            if relation == "prefix":
                scope.inc(m.M_RESIDENT_SUFFIX_HITS)
                return ("suffix", entry)
            if authoritative:
                self.invalidate(key)
        scope.inc(m.M_CACHE_MISSES)
        return None

    def entry_for(self, key: tuple) -> Optional[ResidentEntry]:
        """The key's current entry, recency-refreshed, with NO address
        validation and NO hit/miss accounting — the serving tier's
        chain probe (engine/serving.py): it validates against its own
        committed-batch CRC chain instead of re-reading the store
        history, and falls back to lookup() when the chain breaks."""
        with self._lock:
            sl = self._slices[self.shard_of(key)]
            entry = sl.get(key)
            if entry is not None:
                sl.move_to_end(key)
            return entry

    def invalidate(self, key: tuple) -> bool:
        """Drop an entry (counted); the tail-overwrite / reset / NDC
        branch-switch seam — callers that detect a non-append mutation
        call this, and lookup() calls it itself on address mismatch."""
        with self._lock:
            shard = self.shard_of(key)
            entry = self._slices[shard].pop(key, None)
            if entry is not None:
                self._uncount_locked(shard, entry)
            self._gauges_locked()
        if entry is not None:
            self._scope().inc(m.M_CACHE_INVALIDATIONS)
        return entry is not None

    def clear(self) -> None:
        with self._lock:
            self._clear_locked()
            self._gauges_locked()

    def _clear_locked(self) -> None:
        for sl in self._slices:
            for entry in sl.values():
                entry._charge = None  # a held view must not count later
            sl.clear()
        self._slice_bytes = [0] * len(self._slices)

    def _count_locked(self, shard: int, key: tuple,
                      entry: ResidentEntry) -> None:
        """Put `entry` at the recent end of its slice and count it; a
        view's chunk is counted with its first live view."""
        sl = self._slices[shard]
        old = sl.pop(key, None)
        if old is not None:
            self._uncount_locked(shard, old)
        sl[key] = entry
        self._slice_bytes[shard] += entry.nbytes
        pin = entry._charge
        if pin is not None:
            if pin.live == 0:
                self._slice_bytes[shard] += pin.nbytes
            pin.live += 1

    def _uncount_locked(self, shard: int, entry: ResidentEntry) -> None:
        """Take an entry that left its slice out of the count; the chunk
        of a view goes with its last live view."""
        self._slice_bytes[shard] -= entry.nbytes
        pin, entry._charge = entry._charge, None
        if pin is not None:
            pin.live -= 1
            if pin.live == 0:
                self._slice_bytes[shard] -= pin.nbytes

    def _evict_locked(self, shard: int) -> int:
        """LRU-evict the slice back under its budget; the count evicted."""
        sl = self._slices[shard]
        evicted = 0
        while self._slice_bytes[shard] > self.slice_budget and len(sl) > 1:
            _, dropped = sl.popitem(last=False)
            self._uncount_locked(shard, dropped)
            evicted += 1
        return evicted

    def admit(self, key: tuple, address: ContentAddress, state_row,
              payload: np.ndarray, branch: int, rung: int = 0) -> bool:
        """Pin one workflow's W=1 state row; LRU-evicts past the owning
        device's slice of the HBM budget. `state_row` must already be a
        W=1 slice (extract_row); under a sharded pool it is PLACED on
        the key's owning device before pinning, so every later suffix
        replay / ladder widen of this row runs there. Returns False when
        the row alone exceeds the slice budget (never admitted — a
        budget of 0 disables residency entirely)."""
        from ..ops.state import layout_of

        nbytes = self._row_nbytes(layout_of(state_row))
        if nbytes > self.slice_budget or nbytes > self.budget_bytes:
            return False
        device = self.device_of(key)
        if device is not None:
            state_row = jax.device_put(state_row, device)
        entry = ResidentEntry(payload=np.asarray(payload, dtype=np.int64),
                              branch=int(branch), address=address,
                              rung=int(rung), nbytes=nbytes,
                              _state=state_row)
        with self._lock:
            shard = self.shard_of(key)
            self._count_locked(shard, key, entry)
            evicted = self._evict_locked(shard)
            self._gauges_locked()
        if evicted:
            self.metrics.inc(m.SCOPE_TPU_RESIDENT, m.M_CACHE_EVICTIONS,
                             evicted)
        return True

    def admit_chunk(self, state,
                    rows: Sequence[Tuple[tuple, ContentAddress, int,
                                         np.ndarray, int]]) -> int:
        """Pin the verified rows of one bulk chunk as VIEWS of `state`,
        the chunk's own [W, ...] device state: `rows` holds (key,
        address, row index in `state`, canonical payload row, branch) of
        each. No program is launched and no buffer made a row: a view's
        W=1 row comes into being when its `state` is first read (a
        suffix append, a snapshot write, a rebuild from it), and an
        exact hit never reads it. One lock, one gauge update a chunk.

        Under a sharded pool `state` must be laid out as the engine's
        chunks are (parallel/mesh: W = n x P rows, rows [s P, (s+1) P)
        on mesh position s) with each key's row on its owning device:
        a view then pins, and later slices, its own device's rows alone.

        What the views pin and count is the class's budget rule; a
        chunk too large for it is admitted row by row, as `admit` would.
        Returns the number of rows now resident."""
        rows = list(rows)
        if not rows:
            return 0
        n = len(self._slices)
        parts = _device_parts(state, self._mesh) if n > 1 else [state]
        per = jax.tree_util.tree_leaves(state)[0].shape[0] // n
        by_shard: Dict[int, list] = {}
        for key, address, row, payload, branch in rows:
            shard = self.shard_of(key)
            if not shard * per <= row < (shard + 1) * per:
                raise ValueError(
                    f"row {row} of a chunk of {n} x {per} rows does "
                    f"not lie on shard {shard}, which owns {key}")
            by_shard.setdefault(shard, []).append(
                (key, address, row - shard * per, payload, branch))
        return self._pin_views([
            (_ChunkPin(parts[shard], shard, self), group)
            for shard, group in sorted(by_shard.items())])

    def _pin_views(self, groups: Sequence[Tuple[_ChunkPin, list]]) -> int:
        """Pin each group's rows as views of its pin at the base rung:
        (key, address, row index in `pin.state`, canonical payload row,
        branch) each, counted by the class's budget rule under one lock
        and one gauge update for all of them. A pin that cannot fit its
        slice beside its rows' payload rows has its rows sliced and
        admitted one by one instead. Returns the number of rows now
        resident."""
        payload_nbytes = self.layout.width * 8
        viewed = evicted = 0
        alone = []
        with self._lock:
            for pin, group in groups:
                if (pin.nbytes + len(group) * payload_nbytes
                        > min(self.slice_budget, self.budget_bytes)):
                    alone += [(pin, item) for item in group]
                    continue
                for key, address, row, payload, branch in group:
                    self._count_locked(pin.shard, key, ResidentEntry(
                        payload=np.asarray(payload, dtype=np.int64),
                        branch=int(branch), address=address, rung=0,
                        nbytes=payload_nbytes, _chunk=pin, _row=int(row),
                        _charge=pin))
                viewed += len(group)
                evicted += self._evict_locked(pin.shard)
            self._gauges_locked()
        scope = self._scope()
        if viewed:
            scope.inc(m.M_RESIDENT_VIEW_ROWS, viewed)
        if evicted:
            scope.inc(m.M_CACHE_EVICTIONS, evicted)
        return viewed + sum(
            self.admit(key, address, self.extract_row(pin.state, row),
                       payload, branch)
            for pin, (key, address, row, payload, branch) in alone)

    def _view_materialised(self, entry: ResidentEntry) -> None:
        """A view's `state` was read: it is a W=1 row from here on.
        Still in the pool, it now counts as a row and no longer as a
        live view of its chunk; an entry evicted meanwhile counts
        nothing."""
        from ..ops.state import layout_of

        scope = self._scope()
        scope.inc(m.M_RESIDENT_VIEWS_MATERIALISED)
        scope.inc(m.M_RESIDENT_ROW_SLICES)
        nbytes = self._row_nbytes(layout_of(entry._state))
        with self._lock:
            pin = entry._charge
            if pin is None:
                return
            shard = pin.shard
            self._uncount_locked(shard, entry)
            entry.nbytes = nbytes
            self._slice_bytes[shard] += nbytes
            evicted = self._evict_locked(shard)
            self._gauges_locked()
        if evicted:
            self._scope().inc(m.M_CACHE_EVICTIONS, evicted)

    # -- device helpers -----------------------------------------------------

    def extract_row(self, state, index: int):
        """W=1 device slice of row `index` from a batched ReplayState
        (one dynamic-slice launch per leaf; jit-cached per shape),
        counted under `row-slices`."""
        self._scope().inc(m.M_RESIDENT_ROW_SLICES)
        return _slice_row(state, index)

    def host_rows(self, entries: Sequence[ResidentEntry]
                  ) -> List[Tuple[object, int]]:
        """Each entry's state on the host, as (host tree, row index in
        it), for a reader of many rows at once (a rebuild's hydration).
        A view is read from its chunk and stays a view: the views of one
        chunk share ONE `device_get` of the chunk's state. Rows whose
        leaves are on the host already (hydrated from snapshot records)
        are read where they are; W=1 device rows at the base rung are
        stacked STACK_BLOCK at a time and read with one `device_get` a
        stack, a widened row alone (its leaves have other shapes)."""
        out: List[Optional[Tuple[object, int]]] = [None] * len(entries)
        by_pin: Dict[int, Tuple[_ChunkPin, list]] = {}
        base: List[int] = []
        for i, entry in enumerate(entries):
            # a concurrent read may materialise the view meanwhile: the
            # pin keeps the chunk and `_row` never changes, and a view
            # lets go of its chunk only once its `_state` is set
            pin = entry._chunk
            if pin is not None:
                by_pin.setdefault(id(pin), (pin, []))[1].append(
                    (i, entry._row))
            elif _host_leaves([entry._state]) is not None:
                out[i] = (entry._state, 0)
            elif entry.rung == 0:
                base.append(i)
            else:
                out[i] = (jax.device_get(entry._state), 0)
        for pin, rows in by_pin.values():
            tree = jax.device_get(pin.state)
            for i, row in rows:
                out[i] = (tree, row)
        for lo in range(0, len(base), STACK_BLOCK):
            group = base[lo:lo + STACK_BLOCK]
            if len(group) == 1:
                out[group[0]] = (jax.device_get(entries[group[0]]._state), 0)
                continue
            tree = jax.device_get(_stack_padded(
                [entries[i]._state for i in group], _bucket(len(group), 8)))
            for j, i in enumerate(group):
                out[i] = (tree, j)
        return out

    # -- the append transaction ---------------------------------------------

    def replay_append(self, items: Sequence[Tuple[tuple, ResidentEntry,
                                                  Sequence]],
                      encode_suffix: Optional[Callable] = None,
                      address_of: Callable = content_address
                      ) -> List[AppendResult]:
        """Replay ONLY the appended batches of each item against its
        resident state; items are (key, entry, full current batches)
        from suffix-hit lookups.

        Chunked through the pipelined bulk executor: suffix packing of
        chunk N+1 overlaps the device replay of chunk N (depth ≥ 2), the
        same discipline as the cold path — but each chunk's corpus is
        sized by its longest SUFFIX, not its longest history, which is
        the whole point. Entries sharing a widened rung batch together
        (states in one launch must share a layout).

        On success the entry is re-addressed in place (state, payload,
        branch, address); capacity overflow escalates through the ladder
        from the PRE-append state and the row stays resident widened
        (re-narrowing to base once narrow_ok holds); any other failure
        invalidates the entry and returns ok=False for oracle
        arbitration.

        `address_of` maps each item's third element to the post-append
        ContentAddress (default: content_address over real batch lists).
        The serving tier passes opaque (suffix rows, address) tokens
        instead — its encode_suffix/address_of unwrap them — so chained
        appends never materialize the full history on the host."""
        return self.replay_append_report(items, encode_suffix,
                                         address_of)[0]

    def replay_append_report(self, items: Sequence[Tuple[tuple,
                                                         ResidentEntry,
                                                         Sequence]],
                             encode_suffix: Optional[Callable] = None,
                             address_of: Callable = content_address
                             ) -> Tuple[List[AppendResult], AppendReport]:
        """`replay_append` plus THIS call's AppendReport: a per-call
        object, so a concurrent append on the shared cache can never
        swap the numbers out from under the caller."""
        if encode_suffix is None:
            encode_suffix = _encode_suffix_cold
        results: List[Optional[AppendResult]] = [None] * len(items)
        report = AppendReport(transactions=len(items))
        # group by (rung, owning shard): states in one launch must share
        # a layout, and under a sharded pool the from-state replay (plus
        # any ladder widen it escalates into) runs on the device that
        # already holds the group's states
        by_group: Dict[tuple, List[int]] = {}
        for i, (key, entry, _batches) in enumerate(items):
            by_group.setdefault((entry.rung, self.shard_of(key)),
                                []).append(i)
        for (rung, shard), idxs in sorted(by_group.items()):
            self._append_group(items, idxs, rung, encode_suffix, results,
                               report, shard=shard, address_of=address_of)
        return ([r if r is not None else AppendResult(ok=False)
                 for r in results], report)

    def _append_group(self, items, idxs: List[int], rung: int,
                      encode_suffix, results: List, report: AppendReport,
                      shard: int = 0,
                      address_of: Callable = content_address) -> None:
        from ..ops.encode import assemble_corpus
        from ..ops.replay import replay_from_state_to_payload
        from .executor import BulkReplayExecutor

        chunk = max(1, self.chunk_workflows)
        spans = [(lo, min(lo + chunk, len(idxs)))
                 for lo in range(0, len(idxs), chunk)]
        executor = BulkReplayExecutor(depth=self.pipeline_depth,
                                      registry=self.metrics,
                                      scope=m.SCOPE_TPU_RESIDENT)
        scope = self._scope()

        def pack(ci):
            lo, hi = spans[ci]
            rows_list = []
            for i in idxs[lo:hi]:
                key, entry, batches = items[i]
                rows_list.append(encode_suffix(
                    key, batches, entry.address.batch_count))
            E = _bucket(max((r.shape[0] for r in rows_list), default=1), 16)
            Wp = _bucket(len(rows_list), 8)
            corpus = assemble_corpus(rows_list, E)
            if corpus.shape[0] < Wp:
                pad = np.zeros((Wp - corpus.shape[0], E, NUM_LANES),
                               dtype=np.int64)
                pad[:, :, 1] = -1  # LANE_EVENT_TYPE: no-op padding rows
                corpus = np.concatenate([corpus, pad])
            return corpus

        device = (self._mesh.devices.flat[shard]
                  if self._mesh is not None and len(self._slices) > 1
                  else None)

        def launch(ci, corpus):
            lo, hi = spans[ci]
            with tracing.span("resident.launch"):
                s0 = _stack_padded([items[i][1].state
                                    for i in idxs[lo:hi]],
                                   corpus.shape[0], device, scope)
                report.chunk_shapes.append(
                    (corpus.shape[0], corpus.shape[1]))
                events = int((corpus[:, :, 0] > 0).sum())  # LANE_EVENT_ID
                report.events_appended += events
                scope.inc(m.M_RESIDENT_EVENTS_APPENDED, events)
                # the suffix lanes ship to the OWNING device: the group's
                # resident states already live there, so the whole
                # from-state append is device-local
                corpus_dev = (jax.device_put(corpus, device)
                              if device is not None
                              else jax.device_put(jnp.asarray(corpus)))
                outs = replay_from_state_to_payload(corpus_dev, s0,
                                                    self.layout)
            return corpus, outs

        def consume(ci, packed):
            corpus, (s_fin, rows_dev, err_dev, ovf_dev) = packed
            # the blocking wait and the readback: what a flush of the
            # serving tier spends on the device, not on the host
            with tracing.span("resident.device-wait"):
                jax.block_until_ready(rows_dev)
                return (corpus, s_fin, np.asarray(rows_dev),
                        np.asarray(err_dev), np.asarray(ovf_dev),
                        np.asarray(s_fin.current_branch))

        chunk_outs, _report = executor.run(len(spans), pack, launch, consume)

        from ..ops.state import CAPACITY_ERRORS
        for (lo, hi), (corpus, s_fin, rows, err, ovf, branch) in zip(
                spans, chunk_outs):
            group = idxs[lo:hi]
            flagged = [j for j in range(len(group))
                       if err[j] in CAPACITY_ERRORS
                       or (err[j] == 0 and ovf[j])]
            with tracing.span("resident.readmit"):
                narrow_mask = self._narrow_mask(s_fin, rung)
                #: base-rung rows, re-pinned together as views of s_fin
                viewed = []
                for j, i in enumerate(group):
                    if j in flagged:
                        continue
                    key, entry, batches = items[i]
                    if err[j] != 0:
                        # genuine history error no capacity fixes: drop
                        # the entry, let the caller's oracle arbitrate
                        self.invalidate(key)
                        results[i] = AppendResult(ok=False,
                                                  error=int(err[j]))
                        continue
                    if rung == 0:
                        viewed.append((key, address_of(batches), j,
                                       rows[j], int(branch[j])))
                        results[i] = AppendResult(
                            ok=True, payload=np.asarray(rows[j]),
                            branch=int(branch[j]), rung=0)
                        continue
                    results[i] = self._readmit(
                        key, address_of(batches), s_fin, j, rows[j],
                        int(branch[j]), rung,
                        bool(narrow_mask[j]) if narrow_mask is not None
                        else False)
                if viewed:
                    # the group lives on one device (its shard's): one
                    # pin over the chunk's final state, no launch a row
                    self._pin_views([(_ChunkPin(s_fin, shard, self),
                                      viewed)])
            if flagged:
                self._escalate(items, [group[j] for j in flagged],
                               corpus[[j for j in flagged]], rung, results,
                               report, address_of=address_of)

    def _narrow_mask(self, s_fin, rung: int):
        """[W] bool of rows that can re-narrow to base, None at base."""
        if rung == 0:
            return None
        from ..ops.state import narrow_ok
        return np.asarray(narrow_ok(s_fin, self.layout))

    def _readmit(self, key, address: ContentAddress, s_fin, row: int,
                 payload, branch: int, rung: int,
                 narrowable: bool) -> AppendResult:
        """Re-pin one successfully appended row of a widened rung as a
        W=1 row (re-narrowed when its load drained back under base
        capacities); base-rung rows are re-pinned as views instead."""
        state_row = self.extract_row(s_fin, row)
        if rung > 0 and narrowable:
            from ..ops.state import narrow_state
            state_row = narrow_state(state_row, self.layout)
            rung = 0
            self._scope().inc(m.M_RESIDENT_NARROWED)
        self.admit(key, address, state_row, payload, branch, rung)
        return AppendResult(ok=True, payload=np.asarray(payload),
                            branch=branch, rung=rung)

    def _escalate(self, items, flat_idxs: List[int], sub: np.ndarray,
                  rung: int, results: List, report: AppendReport,
                  address_of: Callable = content_address) -> None:
        """Widened re-replay of capacity-flagged appends from their
        PRE-append resident states (the entries still hold them — they
        only re-admit on success)."""
        from ..ops.encode import gather_subcorpus

        if self.ladder is None:
            for i in flat_idxs:
                self.invalidate(items[i][0])
                results[i] = AppendResult(ok=False, error=-1)
            return
        scope = self._scope()
        scope.inc(m.M_RESIDENT_WIDENED, len(flat_idxs))
        report.escalated_rows += len(flat_idxs)
        pre_states = _stack_states([items[i][1].state
                                    for i in flat_idxs])
        trimmed = gather_subcorpus(sub, np.arange(sub.shape[0]))
        outcome, states_out = self.ladder.escalate_resident(
            trimmed, pre_states, base_rung=rung)
        #: (id of rung state, rung) -> narrow mask, computed ONCE per
        #: distinct rung state (all rows resolved at a rung share it)
        masks: Dict[tuple, object] = {}
        for k, i in enumerate(flat_idxs):
            key, entry, batches = items[i]
            if not outcome.resolved[k]:
                from ..ops.state import ErrorCode
                # a zero ladder error here means the FINAL state exceeds
                # the base canonical payload (narrow overflow) — report
                # it as the overflow it is, never as "no error"
                err = int(outcome.errors[k]) or ErrorCode.TABLE_OVERFLOW
                self.invalidate(key)
                results[i] = AppendResult(ok=False, error=err,
                                          escalated=True)
                continue
            s_fin, local, got_rung = states_out[k]
            mkey = (id(s_fin), got_rung)
            if mkey not in masks:
                masks[mkey] = self._narrow_mask(s_fin, got_rung)
            narrow_mask = masks[mkey]
            res = self._readmit(
                key, address_of(batches), s_fin, local, outcome.rows[k],
                int(outcome.branch[k]), got_rung,
                bool(narrow_mask[local]) if narrow_mask is not None
                else False)
            res.escalated = True
            results[i] = res


def _encode_suffix_cold(key, batches, from_batch: int) -> np.ndarray:
    """Pack-cache-free suffix encoder (standalone consumers: tests): a
    full resumable encode sliced at the prefix row count —
    byte-identical to the pack cache's suffix path, just without the
    O(suffix) warm cost."""
    from ..ops.encode import encode_batches_resumable

    rows, _ = encode_batches_resumable(batches)
    return rows[history_length(batches[:from_batch]):]


_STACK_FN = None


def _stack_states(states):
    """Jitted whole-pytree stack of state rows (a list of states IS a
    pytree argument): one trace per row count + leaf shapes, then a
    single cached dispatch per call — an eager per-leaf concatenate
    paid ~66 dispatch round-trips per flush."""
    global _STACK_FN
    if _STACK_FN is None:
        def stack(ss):
            return jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=0), *ss)

        _STACK_FN = jax.jit(stack)
    return _STACK_FN(states)


#: the widest launch state ONE `stack` program builds: a serving flush at
#: its default `max_batch`. The program takes rows x 66 leaves as
#: operands and the TPU's compiler pays for them super-linearly (AOT for
#: a v5e: 28 s at 64 rows, 90 s at 128, 308 s at 256, so hours at the
#: 2,048 rows of an append chunk: PERF.md, PR 36)
STACK_BLOCK = 64


def _host_leaves(rows) -> Optional[List[list]]:
    """Each row's leaves where every leaf of every row is a numpy array
    (rows hydrated from snapshot records), else None: stops at the first
    leaf on a device."""
    out = []
    for row in rows:
        leaves = jax.tree_util.tree_leaves(row)
        if not all(isinstance(leaf, np.ndarray) for leaf in leaves):
            return None
        out.append(leaves)
    return out


def _stack_padded(rows, width: int, device=None, scope=None):
    """Stack k W=1 state rows into one [width, ...] launch state, the
    tail filled with initial-state rows (their corpus rows carry no
    events). The filler must be W=1 rows, not one [width - k] block:
    the jitted stack then takes `width` operands whatever k is — one
    program per flush width, not one per ROW COUNT, and the TPU's
    compiler pays for each super-linearly in its operand count (a cold
    host's boot warm-up; PERF.md, PR 22). A launch wider than
    STACK_BLOCK (a warm restart's append chunk: the power of two over
    its rows, up to `chunk_workflows`) is stacked STACK_BLOCK rows at a
    time by that one program and the blocks joined by a second of
    width / STACK_BLOCK operands a leaf.

    Rows whose leaves all live on the host (hydrated from snapshot
    records) are stacked there instead and the launch state put on the
    device once, one transfer a leaf: handed to the jitted stack, each of
    their width x 66 leaves would be a host-to-device copy of its own
    (84 us apiece on a v5e: PERF.md, PR 36). `scope` counts their rows."""
    from ..ops.state import init_state, layout_of
    from .snapshot import _row_template

    rows = list(rows)
    host = _host_leaves(rows) if rows else None
    if host is not None:
        treedef, _fields, _total, fill = _row_template(layout_of(rows[0]))
        k = len(rows)
        leaves = []
        for i, pad in enumerate(fill):
            leaf = np.empty((width,) + pad.shape[1:], pad.dtype)
            np.concatenate([r[i] for r in host], axis=0, out=leaf[:k])
            leaf[k:] = pad
            leaves.append(leaf)
        if scope is not None:
            scope.inc(m.M_RESIDENT_HOST_STACKED_ROWS, k)
        return jax.device_put(treedef.unflatten(leaves), device)
    if len(rows) < width:
        filler = init_state(1, layout_of(rows[0]))
        if device is not None:
            filler = jax.device_put(filler, device)
        rows += [filler] * (width - len(rows))
    if len(rows) <= STACK_BLOCK:
        return _stack_states(rows)
    return _stack_states([_stack_states(rows[lo:lo + STACK_BLOCK])
                          for lo in range(0, len(rows), STACK_BLOCK)])


def _device_parts(state, mesh) -> list:
    """A chunk's [W, ...] state as one local state a mesh position: each
    leaf's own buffer on that device, rows [s W/n, (s+1) W/n). A leaf
    the compiler laid out otherwise is re-placed first; the others are
    shared, not copied, so dropping one part frees that device's rows
    whatever becomes of the rest."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.mesh import SHARD_AXIS

    state = jax.device_put(
        state, NamedSharding(mesh, PartitionSpec(SHARD_AXIS)))
    leaves, treedef = jax.tree_util.tree_flatten(state)
    local = [{s.device: s.data for s in leaf.addressable_shards}
             for leaf in leaves]
    return [treedef.unflatten([by_dev[device] for by_dev in local])
            for device in mesh.devices.flat]


_SLICE_FN = None


def _slice_row(state, index: int):
    """Jitted per-leaf dynamic slice (index traced: one compile per
    state shape, not per row index)."""
    global _SLICE_FN
    if _SLICE_FN is None:
        def slice_row(s, i):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, i, 1, axis=0), s)

        _SLICE_FN = jax.jit(slice_row)
    return _SLICE_FN(state, index)
