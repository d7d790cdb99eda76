"""Metrics: counters / timers / gauges / histograms behind named scopes.

Reference: common/metrics (Client/Scope at metrics/interfaces.go:31,:53;
every scope and metric name enumerated in metrics/defs.go). The reference
emits through tally to m3/statsd/prometheus; here the registry keeps the
aggregates in-process and exposes two emitter seams: snapshot() (the
structured dump tests and the bench assert on, now with percentiles) and
to_prometheus() (text exposition format 0.0.4, served by the /metrics
scrape surface in utils/scrape.py and rpc/server.py). A series read on
demand from elsewhere (a store in another process) is kept by a
collector, which both seams run before they read, so every scrape path
sees it fresh.

Timers feed fixed-bucket histograms on every record(), so each latency
metric carries a full distribution (bucket counts + interpolated
percentiles), not just count/total/max.

Thread-safe; scopes are cheap handles over the shared registry.
"""
from __future__ import annotations

import bisect
import re
import threading
import time
import weakref
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple


# -- scope names (metrics/defs.go analog; the subset the engine emits) ------

SCOPE_HISTORY_START_WORKFLOW = "history.start-workflow-execution"
SCOPE_HISTORY_DECISION_COMPLETED = "history.respond-decision-task-completed"
SCOPE_HISTORY_ACTIVITY_RESPOND = "history.respond-activity-task"
SCOPE_HISTORY_SIGNAL = "history.signal-workflow-execution"
SCOPE_HISTORY_RESET = "history.reset-workflow-execution"
SCOPE_FRONTEND_START = "frontend.start-workflow-execution"
SCOPE_FRONTEND_SIGNAL = "frontend.signal-workflow-execution"
SCOPE_FRONTEND_SIGNAL_WITH_START = (
    "frontend.signal-with-start-workflow-execution")
SCOPE_QUEUE_TRANSFER = "queue.transfer"
SCOPE_QUEUE_TIMER = "queue.timer"
SCOPE_REPLICATION = "replication.task-processor"
SCOPE_TPU_REPLAY = "tpu.replay-engine"
SCOPE_REBUILD = "tpu.device-rebuilder"
SCOPE_PACK_CACHE = "tpu.pack-cache"
SCOPE_TPU_FALLBACK = "tpu.fallback"
SCOPE_TPU_RESIDENT = "tpu.resident"
#: the mesh-aware bulk executor's own scope (engine/executor.py):
#: chunks-dispatched / pack-queue-wait / launches-in-flight, with PER-DEVICE
#: series (device_metric) when the executor runs over a mesh
SCOPE_TPU_EXECUTOR = "tpu.executor"
#: the native (C++) host-packing seam (native/packing.py + native/
#: wirec.py): the `available` gauge says whether the compiled .so is
#: loadable in THIS process (1) or every pack silently took the pure-
#: Python path (0); native-packs / python-packs count which encoder
#: actually served each wirec pack, so a scrape settles "which path ran"
SCOPE_TPU_NATIVE = "tpu.native"
#: the micro-batching device-serving transaction tier (engine/serving.py
#: ServingScheduler): committed decision transactions coalesce into one
#: from-state launch per owning mesh device; counters below under
#: M_SERVING_*
SCOPE_TPU_SERVING = "tpu.serving"
#: M_SNAP_* (engine/snapshot.py — the persisted mutable-state tier)
SCOPE_TPU_SNAPSHOT = "tpu.snapshot"
#: live HBM state migration across the host cluster (engine/migration.py
#: MigrationManager): shard movement snapshots resident rows out of the
#: losing host and hydrates them on the gaining host; counters below
#: under M_MIG_*
SCOPE_TPU_MIGRATION = "tpu.migration"
#: the columnar device visibility tier (engine/visibility_device.py +
#: ops/scan.py): List/Scan/Count served as vectorized mask kernels over
#: device-resident columns; counters below under M_VIS_*
SCOPE_TPU_VISIBILITY = "tpu.visibility"
#: crash recovery (engine/durability.recover_stores): what one call read
#: from the log and what its two device passes were handed; counters
#: below under M_RECOVER_*
SCOPE_TPU_RECOVER = "tpu.recover"
SCOPE_WORKER_RETENTION = "worker.retention"
SCOPE_WORKER_SCAVENGER = "worker.scavenger"
SCOPE_WORKER_SCANNER = "worker.scanner"
SCOPE_HISTORY_RECORD_STARTED = "history.record-decision-task-started"
SCOPE_FRONTEND_POLL_DECISION = "frontend.poll-for-decision-task"
SCOPE_FRONTEND_RESET = "frontend.reset-workflow-execution"
SCOPE_FRONTEND_QUERY = "frontend.query-workflow"
SCOPE_FRONTEND_READ = "frontend.read"
SCOPE_MATCHING_POLL_DECISION = "matching.poll-decision-task"
SCOPE_MATCHING_ADD_DECISION = "matching.add-decision-task"
#: the admission-control seat (common/quotas, PAPER §1 layer 5): every
#: frontend API charged against the multi-stage limiter counts here —
#: `admitted`/`shed` totals plus per-domain series (domain_metric), so a
#: scrape shows WHICH domain is being shed while the others hold
SCOPE_QUOTAS = "quotas"
#: the open-loop load generator's own scopes ride "loadgen.<op-kind>"
#: (cadence_tpu/loadgen/generator.py); per-domain latency series use the
#: same domain_metric labeling as the quota counters
SCOPE_LOADGEN_PREFIX = "loadgen"
#: host-runtime attribution (utils/hostprof.py HostProfiler): gauges for
#: per-subsystem wall/CPU shares (wall-share-<subsystem>,
#: cpu-seconds-<subsystem>), the GIL-contention estimate, and the
#: attributed-share acceptance gate — the sampling-profiler mirror of
#: the `admin hostprof` rollup
SCOPE_HOSTPROF = "host.prof"
#: ring-buffer sampler health (utils/timeseries.py TimeSeriesSampler):
#: windows retained, samples taken, last-window utilization — the flat
#: /metrics mirror of the windowed GET /timeseries surface
SCOPE_TIMESERIES = "timeseries"
#: flight-recorder ring (utils/flightrecorder.py): wide events recorded
#: and JSONL dumps written by THIS process's black box
SCOPE_FLIGHTREC = "flightrec"
#: continuous SLO burn rates (loadgen/slo.py BurnRateEvaluator over the
#: ring-buffer windows): burn-rate-<op>-<metric>-<horizon>s gauges — 1.0
#: means the error budget is being consumed exactly at its sustainable
#: rate; multi-window alerting fires when the SHORT and LONG horizons
#: both exceed the threshold
SCOPE_SLO = "slo"
#: hashring membership as observed by THIS host (rpc/server.py
#: refresh_membership): drop/join counters plus the ring-generation
#: gauge — the witnesses chaos campaigns read to prove a membership
#: flap propagated fleet-wide (gen/cluster_chaos.py)
SCOPE_MEMBERSHIP = "membership"
#: shard controller (engine/controller.py): fenced-engine evictions — a
#: deposed context discarded and re-acquired after a flap-back
SCOPE_CONTROLLER = "controller"

# -- metric names -----------------------------------------------------------

M_REQUESTS = "requests"
M_ERRORS = "errors"
M_LATENCY = "latency"
M_TASKS_PROCESSED = "tasks-processed"
M_TASKS_DROPPED_NOT_EXISTS = "tasks-dropped-entity-not-exists"
#: executor dropped a task whose workflow a PEER cluster's promotion
#: already owns (version arbitration rejected the local mutation)
M_TASKS_DROPPED_STALE = "tasks-dropped-stale-version"
M_REPL_APPLIED = "replication-applied"
M_REPL_DEDUPED = "replication-deduped"
M_REPL_RESENT = "replication-resends"
M_REPL_DLQ = "replication-dlq"
#: replication DLQ depth gauge: current quarantined-entry count on the
#: target store (maintained at every enqueue/redrive/purge touch point)
M_REPL_DLQ_DEPTH = "dlq-depth"
#: DLQ redrive: entries re-applied through the resender by the
#: `admin dlq` redrive arm / processor.redrive_dlq
M_REPL_REDRIVEN = "replication-redriven"
#: device standby apply (engine/replication.py _DeviceApplier): applied
#: histories streamed through the resident tier at the bulk-ingest rate,
#: host-parity gated per apply — divergence counted, never served
M_REPL_DEVICE_APPLIED = "device-applied"
M_REPL_DEVICE_SUFFIX_EVENTS = "device-suffix-events"
M_REPL_DEVICE_COLD = "device-skipped-cold"
M_REPL_DEVICE_STALE = "device-skipped-stale"
M_REPL_DEVICE_DIVERGENCE = "device-parity-divergence"
M_REPL_DEVICE_UNSTABLE = "device-parity-skipped-unstable"
#: snapshot-shipping replication: checksum-gated SnapshotRecords riding
#: the wire replication stream so a standby's cold admits and promotion
#: are seed_caches + suffix replay, never full replay
M_REPL_SNAP_SHIPPED = "snapshots-shipped"
M_REPL_SNAP_INSTALLED = "snapshots-installed"
M_REPL_SNAP_IGNORED_TORN = "snapshots-ignored-torn"
M_REPL_SNAP_IGNORED_STALE = "snapshots-ignored-stale"
M_REPL_SNAP_IGNORED_FOREIGN = "snapshots-ignored-foreign"
#: per-domain replication backpressure (engine/replication.py): a drain
#: pass stops (typed ReplicationBackpressureShed) once one domain has
#: consumed its per-pass apply budget, so a partition-heal flood on one
#: domain cannot starve the pump tick for every other domain; -deferred
#: counts the tasks the shed pass left for the next tick
M_REPL_BP_SHED = "backpressure-shed"
M_REPL_BP_DEFERRED = "backpressure-deferred"
#: domain-metadata failover-version arbitration (engine/domainrepl.py):
#: applied mutations vs stale ones rejected (lower failover version than
#: the local record — the split-brain loser's update) vs duplicate
#: notification replays at the same failover version
M_DOMREPL_APPLIED = "domain-applied"
M_DOMREPL_STALE_REJECTED = "domain-stale-rejected"
M_DOMREPL_DUPLICATE = "domain-duplicate"
#: membership-flap witnesses (SCOPE_MEMBERSHIP)
M_RING_DROPS = "ring-drops"
M_RING_JOINS = "ring-joins"
M_RING_GENERATION = "ring-generation"
#: fenced-engine evictions (SCOPE_CONTROLLER)
M_FENCED_EVICTIONS = "fenced-evictions"
M_KERNEL_LAUNCHES = "kernel-launches"
M_EVENTS_REPLAYED = "events-replayed"
M_REPLAY_THROUGHPUT = "replay-events-per-sec"
M_DEVICE_REBUILDS = "device-rebuilds"
M_ORACLE_FALLBACKS = "oracle-fallbacks"
M_FALLBACK_RATE = "fallback-rate"
M_BUFFERED_FLUSHED = "buffered-events-flushed"
M_RATE_LIMITED = "requests-rate-limited"
M_RUNS_DELETED = "runs-deleted"
M_RUNS_ARCHIVED = "runs-archived"
M_EXECUTIONS_SCANNED = "executions-scanned"
M_INVARIANT_VIOLATIONS = "invariant-violations"
#: replay-profiler legs (utils/profiler.py): per-kernel-launch host cost
M_PROFILE_PACK = "pack"
M_PROFILE_H2D = "h2d"
M_PROFILE_KERNEL = "kernel"
M_PROFILE_READBACK = "readback"
#: time the device consumer spends waiting on the pack producer pipeline
#: (engine/executor.py): non-zero p50 here means the host packers are
#: starving the device; a near-zero leg means the device is the bottleneck
M_PROFILE_PACK_WAIT = "pack-queue-wait"
#: capacity-escalation leg (engine/ladder.py): gather + widened-K
#: re-replay of flagged rows; replaces the per-workflow oracle leg on
#: capacity overflow, so this leg growing while oracle fallbacks stay
#: flat is the ladder working as intended
M_PROFILE_FALLBACK = "fallback"
#: device-serving leg (engine/serving.py): the micro-batched flush of
#: committed transactions — suffix from-state launches plus cold admits
#: — per drain cycle; this leg next to pack/kernel says how much of a
#: launch window the serving tier occupies
M_PROFILE_SERVING = "serving"
M_H2D_BYTES = "h2d-bytes"
#: pack-cache counters (engine/cache.py PackCache, SCOPE_PACK_CACHE)
M_CACHE_HITS = "hits"
M_CACHE_MISSES = "misses"
M_CACHE_EVICTIONS = "evictions"
M_CACHE_SUFFIX_PACKS = "suffix-packs"
#: resident-state cache counters (engine/resident.py ResidentStateCache,
#: SCOPE_TPU_RESIDENT): exact hits reuse the cached payload with zero
#: device work, suffix hits replay only appended batches against the
#: HBM-resident state, invalidations count stale entries dropped on tail
#: overwrite / reset / NDC branch switch; the resident-bytes gauge is
#: the cache's HBM footprint against its configured budget; view-rows
#: counts rows a bulk chunk seeded, or an append chunk re-pinned, as
#: views of its own state (no launch, no buffer), views-materialised the
#: views whose W=1 row was then read; host-stacked-rows the real rows of
#: each append launch state built on the host (rows hydrated from
#: snapshot records) and put on the device once a leaf; row-slices one a
#: W=1 `slice_row` launch the pool makes (`extract_row`: a cold admit,
#: an append's re-admit at a widened rung; a view's first read). Beside
#: them an append's spans, once a chunk under fixed names
#: (the caller's span says which path): resident.launch,
#: resident.device-wait, resident.readmit
M_CACHE_INVALIDATIONS = "invalidations"
M_RESIDENT_SUFFIX_HITS = "suffix-hits"
M_RESIDENT_BYTES = "resident-bytes"
M_RESIDENT_ENTRIES = "resident-entries"
M_RESIDENT_BUDGET_BYTES = "budget-bytes"
M_RESIDENT_EVENTS_APPENDED = "events-appended"
M_RESIDENT_WIDENED = "widened-rows"
M_RESIDENT_NARROWED = "renarrowed-rows"
M_RESIDENT_VIEW_ROWS = "view-rows"
M_RESIDENT_HOST_STACKED_ROWS = "host-stacked-rows"
M_RESIDENT_VIEWS_MATERIALISED = "views-materialised"
M_RESIDENT_ROW_SLICES = "row-slices"
#: capacity-escalation ladder counters (engine/ladder.py,
#: SCOPE_TPU_FALLBACK): rows entering the ladder, rows re-replayed at
#: each rung (metric name ladder_rung_rows(r)), rows resolved on device,
#: rows left for oracle arbitration, widened-kernel compiles, and the
#: kernel-variant cache hits/misses that prove a warm run recompiled
#: nothing (utils/compile_cache.KernelVariantCache)
M_LADDER_FLAGGED = "flagged-rows"
M_LADDER_RESOLVED = "resolved-rows"
M_LADDER_RESIDUAL = "residual-oracle-rows"
M_LADDER_COMPILES = "rung-compiles"
M_LADDER_CACHE_HITS = "compile-cache-hits"
M_LADDER_CACHE_MISSES = "compile-cache-misses"
#: mesh-aware executor counters (engine/executor.py, SCOPE_TPU_EXECUTOR):
#: chunks dispatched to the device mesh (plus a device_metric series per
#: mesh position) and the per-device in-flight gauge — chunks launched and
#: not yet read back whose shard slice occupies that device; rows-dispatched counts REAL workflow
#: rows per device slice (padding excluded), so skewed shard population
#: is visible on a scrape
M_EXEC_CHUNKS = "chunks-dispatched"
M_EXEC_ROWS = "rows-dispatched"
#: launches-in-flight: the HOST's count of chunks launched and not yet read
#: back. Not a device share: the device's busy time is on its own trace
M_EXEC_IN_FLIGHT = "launches-in-flight"
#: admission-control counters (SCOPE_QUOTAS): requests the multi-stage
#: limiter admitted vs shed (typed ServiceBusyError with retry-after)
M_QUOTA_ADMITTED = "admitted"
M_QUOTA_SHED = "shed"
#: native-seam observability (SCOPE_TPU_NATIVE)
M_NATIVE_AVAILABLE = "available"
M_NATIVE_PACKS = "native-packs"
M_NATIVE_PY_PACKS = "python-packs"
#: whole decodes of a chunk's wire blobs (feeder: 1 a pinned chunk, 2 for
#: chunk 0 and for each refit on the native encoder, which measures by
#: decoding again instead of keeping a lane tensor)
M_NATIVE_DECODE_PASSES = "decode-passes"
#: device-serving transaction tier (engine/serving.py ServingScheduler,
#: SCOPE_TPU_SERVING): committed history-engine transactions enqueue
#: into a per-shard coalescing queue and flush as ONE from-state launch
#: per owning mesh device — `transactions`/`batched-launches` give the
#: coalescing factor, `coalesced-appends` counts same-workflow
#: transactions folded into one pending append, `batch-size` and
#: `queue-wait` are the micro-batching histograms, and
#: `parity-divergence` counts device payloads that disagreed with the
#: oracle's committed state (the entry is invalidated, never served)
M_SERVING_TXNS = "transactions"
M_SERVING_LAUNCHES = "batched-launches"
M_SERVING_COALESCED = "coalesced-appends"
M_SERVING_BATCH_SIZE = "batch-size"
M_SERVING_QUEUE_WAIT = "queue-wait"
M_SERVING_DIVERGENCE = "parity-divergence"
M_SERVING_EXACT = "exact-serves"
M_SERVING_SUFFIX = "suffix-appends"
M_SERVING_COLD = "cold-admits"
M_SERVING_BYPASSED = "bypassed"
M_SERVING_REQUEUED = "requeued"
M_SERVING_REJECTED = "busy-rejections"
M_SERVING_QUEUE_DEPTH = "queue-depth"
#: how every handed transaction ENDED: one ticket per submit (folded
#: ones included), resolved ok — device state maintained and equal to
#: the oracle's row — or not. The oracle commits first, so an RPC can
#: succeed while its device flush failed; these two are what says so.
M_SERVING_TICKETS_OK = "tickets-ok"
M_SERVING_TICKETS_FAILED = "tickets-failed"
#: committed transactions the engine could NOT hand to the tier for a
#: reason other than a full queue (that one is `busy-rejections`)
M_SERVING_HANDOFF_FAILED = "handoff-failures"
#: persisted mutable-state snapshot tier (engine/snapshot.py,
#: SCOPE_TPU_SNAPSHOT): `writes` counts checksum-gated snapshot records
#: appended to the WAL, `checksum-skips` counts writes refused because
#: the resident payload disagreed with the oracle's live state (never
#: persisted), `hydrates` counts snapshot→resident seeds on a cold path
#: (restart, chain break, cold admit), `ignored-stale`/`ignored-torn`
#: count snapshots detected invalid and skipped — fallen back to full
#: replay, never served; the gauges mirror the store's occupancy, read
#: through the store's `stats()` on a sweep and, as the writer's
#: registry collector, on every render of the registry (snapshot(),
#: to_prometheus(): the `admin_metrics` op and GET /metrics alike).
#: `gate-chains` counts each gate chain the serving policy enters (a
#: due key's `snapshot_key`, written or not, each inside the span
#: `serving.snapshot-gate-chain`); `write-errors` counts the
#: policy's writes that raised on a serving flush (the flush's tickets
#: are resolved already: the failure costs a record, never a ticket)
M_SNAP_WRITES = "writes"
M_SNAP_CHECKSUM_SKIPS = "checksum-skips"
M_SNAP_HYDRATES = "hydrates"
M_SNAP_IGNORED_STALE = "ignored-stale"
M_SNAP_IGNORED_TORN = "ignored-torn"
M_SNAP_BYTES = "snapshot-bytes"
M_SNAP_ENTRIES = "snapshot-entries"
M_SNAP_GATE_CHAINS = "gate-chains"
M_SNAP_WRITE_ERRORS = "write-errors"

#: live HBM state migration (engine/migration.py, SCOPE_TPU_MIGRATION):
#: on shard RELEASE the losing host writes checksum-gated snapshot
#: records for its moving resident rows (`migrated-out`; gate-refused
#: writes count `migrate-out-skipped`) and drops the local entries
#: (`evicted-resident`); on shard ACQUIRE the gaining host hydrates the
#: stolen shards' open workflows from the shared snapshot store —
#: `migrated-in` counts snapshot-hydrated admits (suffix catch-up
#: events under `suffix-events`), `cold-steals` keys with no usable
#: record (full replay on first touch), `stale-snapshots` records whose
#: address no longer prefixes the stored bytes. `parity-divergence`
#: counts hydrated rows whose payload disagreed with the oracle's live
#: state over a STABLE store (dropped, never served — gated at 0);
#: `parity-skipped-unstable` counts comparisons skipped because a
#: foreign commit moved the tail mid-hydration (not divergence).
M_MIG_OUT = "migrated-out"
M_MIG_OUT_SKIPPED = "migrate-out-skipped"
M_MIG_EVICTED = "evicted-resident"
M_MIG_IN = "migrated-in"
M_MIG_COLD = "cold-steals"
#: record-less keys at/under the young floor (migration.YOUNG_BATCHES):
#: expected-cold per the snapshot policy's own min_events floor, kept
#: out of the warm-failover ratio
M_MIG_YOUNG = "young-steals"
M_MIG_STALE = "stale-snapshots"
M_MIG_SUFFIX_EVENTS = "suffix-events"
M_MIG_DIVERGENCE = "parity-divergence"
M_MIG_UNSTABLE = "parity-skipped-unstable"

#: columnar device visibility tier (engine/visibility_device.py,
#: SCOPE_TPU_VISIBILITY): `queries` counts every routed List/Scan/Count,
#: split into `device-served` (mask kernel answered) vs `host-fallbacks`
#: (evaluated on the host instead — `fallback-predicate` the query uses
#: an op/column the kernels can't express (e.g. string ordering),
#: `fallback-column` a search-attribute column past the intern budget or
#: type-poisoned). `parity-divergence` counts device answers that
#: disagreed with the host oracle (served the HOST answer, gated at 0);
#: `topk-serves` vs `bitmap-scans` splits paged readback strategies,
#: `topk-escalations` counts pages that re-ran through the bitmap path
#: (boundary tie / truncation). `deltas-applied`/`drains` meter the
#: coalescing appender; `staleness-pending` is the backlog a query
#: observed before its flush (the recorded staleness gauge), and
#: `rows`/`attr-columns`/`interned-strings` mirror column occupancy.
M_VIS_QUERIES = "queries"
M_VIS_DEVICE_SERVED = "device-served"
M_VIS_HOST_FALLBACKS = "host-fallbacks"
M_VIS_FALLBACK_PREDICATE = "fallback-predicate"
M_VIS_FALLBACK_COLUMN = "fallback-column"
M_VIS_PARITY_CHECKS = "parity-checks"
M_VIS_DIVERGENCE = "parity-divergence"
M_VIS_TOPK = "topk-serves"
M_VIS_BITMAP = "bitmap-scans"
M_VIS_TOPK_ESCALATIONS = "topk-escalations"
M_VIS_DELTAS = "deltas-applied"
M_VIS_DRAINS = "drains"
M_VIS_STALENESS = "staleness-pending"
M_VIS_ROWS = "rows"
M_VIS_ATTR_COLUMNS = "attr-columns"
M_VIS_INTERNED = "interned-strings"
M_VIS_SCAN_LATENCY = "scan-latency"
#: LFU attr-column swaps: an over-budget search attribute out-demanded
#: the least-queried resident column and took its slot — queries on it
#: stop permanently falling back (visibility_device._maybe_replace_attr)
M_VIS_ATTR_REPLACEMENTS = "attr-column-replacements"
#: crash recovery (engine/durability.recover_stores, SCOPE_TPU_RECOVER),
#: added once a call: the log's records (all, and a series a record type:
#: recover_records("h") = log-records-h) and the bytes of the file read;
#: the history batches, their events and their serialized bytes (the
#: blobs before base64: what any recovery must read once); the runs, events
#: and chunks the device rebuild was handed; the dense int64 bytes both
#: device passes shipped (rebuild + verify); the rows the verify held
#: against the rebuilt states on the device and the events its full
#: replay took for them. A warm restart (a log with `snap` records): the
#: records the log replay installed and their decoded bytes (state blob +
#: payload row); then, summed over both device passes, the runs hydrated
#: from a record, the rows served as exact and as suffix hits of a
#: resident pool, and the events those suffixes replayed. The batches the
#: log replay held as their bytes (engine/persistence.HistoryStore.
#: append_serialized), and those of them the call decoded, once each:
#: their quotient is how much of the history the call read
M_RECOVER_LOG_RECORDS = "log-records"
M_RECOVER_LOG_BYTES = "log-bytes"
M_RECOVER_HISTORY_BATCHES = "history-batches"
M_RECOVER_HISTORY_EVENTS = "history-events"
M_RECOVER_HISTORY_BYTES = "history-bytes"
M_RECOVER_EXECUTIONS = "executions-rebuilt"
M_RECOVER_REBUILD_EVENTS = "events-rebuilt"
M_RECOVER_REBUILD_CHUNKS = "chunks-rebuilt"
M_RECOVER_DENSE_BYTES = "dense-bytes"
M_RECOVER_ROWS_VERIFIED = "rows-verified"
M_RECOVER_VERIFY_EVENTS = "events-verified"
M_RECOVER_SNAPSHOT_RECORDS = "snapshot-records"
M_RECOVER_SNAPSHOT_BYTES = "snapshot-bytes"
M_RECOVER_RUNS_HYDRATED = "runs-hydrated"
M_RECOVER_EXACT_ROWS = "exact-rows"
M_RECOVER_SUFFIX_ROWS = "suffix-rows"
M_RECOVER_SUFFIX_EVENTS = "suffix-events"
M_RECOVER_BATCHES_HELD = "batches-held-serialized"
M_RECOVER_BATCHES_DECODED = "batches-decoded"


def recover_records(record_type: str) -> str:
    """Per-record-type log counter name: log-records-h, log-records-cur, ..."""
    return f"{M_RECOVER_LOG_RECORDS}-{record_type}"


def ladder_rung_rows(rung: int) -> str:
    """Per-rung row counter name: rows-rung1, rows-rung2, ..."""
    return f"rows-rung{rung}"


def device_metric(name: str, device: int) -> str:
    """Per-device series name: chunks-dispatched-dev0, launches-in-flight-dev3,
    ... — the device label of the mesh-aware executor's metrics (the
    registry keys on flat (scope, name), so the label rides the name the
    same way ladder_rung_rows carries the rung)."""
    return f"{name}-dev{device}"


def domain_metric(name: str, domain: str) -> str:
    """Per-domain series name: shed-domain-hot, latency-domain-payments,
    ... — the domain label of the quota/loadgen metrics, riding the flat
    (scope, name) key exactly like device_metric's device label
    (to_prometheus sanitizes the domain into the metric grammar)."""
    return f"{name}-domain-{domain}"


#: latency buckets (seconds): sub-ms sync paths through multi-second
#: device compiles — tally's default histogram ladder, trimmed
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


class HistogramStat:
    """Fixed-bucket histogram (prometheus `le` semantics: bucket i counts
    values <= bounds[i]; the last slot is +Inf)."""

    __slots__ = ("bounds", "bucket_counts", "count", "total")

    def __init__(self, bounds: Sequence[float] = DEFAULT_BUCKETS) -> None:
        self.bounds: Tuple[float, ...] = tuple(bounds)
        self.bucket_counts: List[int] = [0] * (len(self.bounds) + 1)
        self.count = 0
        self.total = 0.0

    def observe(self, value: float) -> None:
        self.count += 1
        self.total += value
        self.bucket_counts[bisect.bisect_left(self.bounds, value)] += 1

    def cumulative(self) -> List[Tuple[str, int]]:
        """[(le_label, cumulative_count)] ending with ("+Inf", count)."""
        out: List[Tuple[str, int]] = []
        running = 0
        for bound, n in zip(self.bounds, self.bucket_counts):
            running += n
            out.append((str(bound), running))
        out.append(("+Inf", self.count))
        return out

    def percentile(self, q: float) -> float:
        """q in [0, 1]; linear interpolation inside the covering bucket.
        Values in the +Inf bucket clamp to the top finite bound."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        running = 0
        lo = 0.0
        for bound, n in zip(self.bounds, self.bucket_counts):
            if n and running + n >= target:
                return lo + (bound - lo) * ((target - running) / n)
            running += n
            lo = bound
        return self.bounds[-1]


@dataclass
class _TimerStat:
    count: int = 0
    total_s: float = 0.0
    max_s: float = 0.0

    def record(self, seconds: float) -> None:
        self.count += 1
        self.total_s += seconds
        self.max_s = max(self.max_s, seconds)


class MetricsRegistry:
    """The tally-registry analog; one per cluster."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, str], int] = {}
        self._timers: Dict[Tuple[str, str], _TimerStat] = {}
        self._gauges: Dict[Tuple[str, str], float] = {}
        self._histograms: Dict[Tuple[str, str], HistogramStat] = {}
        self._collectors: List[weakref.WeakMethod] = []

    def scope(self, name: str) -> "Scope":
        return Scope(self, name)

    # raw ops (scopes call these)

    def inc(self, scope: str, name: str, delta: int = 1) -> None:
        with self._lock:
            self._counters[(scope, name)] = (
                self._counters.get((scope, name), 0) + delta)

    def record(self, scope: str, name: str, seconds: float) -> None:
        """Timer + latency histogram: every record() feeds both, so each
        latency metric carries a full distribution."""
        with self._lock:
            self._timers.setdefault((scope, name), _TimerStat()).record(seconds)
            hist = self._histograms.get((scope, name))
            if hist is None:
                hist = self._histograms[(scope, name)] = HistogramStat()
            hist.observe(seconds)

    def observe(self, scope: str, name: str, value: float,
                buckets: Optional[Sequence[float]] = None) -> None:
        """Histogram-only observation (sizes, per-leg timings); `buckets`
        applies on first touch of the (scope, name) series."""
        with self._lock:
            hist = self._histograms.get((scope, name))
            if hist is None:
                hist = self._histograms[(scope, name)] = HistogramStat(
                    buckets if buckets is not None else DEFAULT_BUCKETS)
            hist.observe(value)

    def gauge(self, scope: str, name: str, value: float) -> None:
        with self._lock:
            self._gauges[(scope, name)] = value

    def add_collector(self, method) -> None:
        """Have `method`, a bound method, set its series before every
        render (`snapshot()`, `to_prometheus()`): the seam for a series
        read on demand from elsewhere, such as the occupancy of a store
        in another process, so that each scrape path reads it fresh and
        none has to ask. Held weakly: a collector goes with its object."""
        with self._lock:
            self._collectors.append(weakref.WeakMethod(method))

    def _collect(self) -> None:
        with self._lock:
            live = [c() for c in self._collectors]
            self._collectors = [c for c, method in zip(self._collectors, live)
                                if method is not None]
        for method in live:
            if method is not None:
                method()

    # reads

    def counter(self, scope: str, name: str) -> int:
        with self._lock:
            return self._counters.get((scope, name), 0)

    def timer(self, scope: str, name: str) -> _TimerStat:
        with self._lock:
            return self._timers.get((scope, name), _TimerStat())

    def gauge_value(self, scope: str, name: str,
                    default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get((scope, name), default)

    def histogram(self, scope: str, name: str) -> HistogramStat:
        with self._lock:
            return self._histograms.get((scope, name), HistogramStat())

    def percentiles(self, scope: str, name: str,
                    qs: Sequence[float] = (0.5, 0.95, 0.99)
                    ) -> Dict[str, float]:
        hist = self.histogram(scope, name)
        return {f"p{round(q * 100):d}": hist.percentile(q) for q in qs}

    def reset(self) -> None:
        """Drop every series (the per-test isolation seam: components hold
        the registry by reference, so clearing in place is the only reset
        that reaches them all)."""
        with self._lock:
            self._counters.clear()
            self._timers.clear()
            self._gauges.clear()
            self._histograms.clear()

    def raw_series(self) -> Tuple[Dict, Dict, Dict]:
        """Consistent point-in-time copy of every series, taken under ONE
        lock hold: (counters, gauges, histograms) where each histogram
        value is (count, total, bounds, bucket_counts-tuple). The
        time-series sampler's delta math and the prometheus renderer
        both ride this so a concurrent observe()/reset() can never yield
        a half-updated view."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            histograms = {
                k: (h.count, h.total, h.bounds, tuple(h.bucket_counts))
                for k, h in self._histograms.items()}
        return counters, gauges, histograms

    def snapshot(self) -> Dict[str, Dict[str, object]]:
        """Full dump, grouped by scope — the structured emitter seam."""
        self._collect()
        out: Dict[str, Dict[str, object]] = {}
        with self._lock:
            for (scope, name), v in self._counters.items():
                out.setdefault(scope, {})[name] = v
            for (scope, name), t in self._timers.items():
                out.setdefault(scope, {})[name + ".count"] = t.count
                out.setdefault(scope, {})[name + ".total_s"] = round(t.total_s, 6)
                out.setdefault(scope, {})[name + ".max_s"] = round(t.max_s, 6)
            for (scope, name), h in self._histograms.items():
                for q in (0.5, 0.95, 0.99):
                    out.setdefault(scope, {})[
                        f"{name}.p{round(q * 100):d}"] = round(
                            h.percentile(q), 6)
                if (scope, name) not in self._timers:
                    out.setdefault(scope, {})[name + ".count"] = h.count
                    out.setdefault(scope, {})[name + ".sum"] = round(h.total, 6)
            for (scope, name), v in self._gauges.items():
                out.setdefault(scope, {})[name] = v
        return out

    # -- prometheus exposition (text format 0.0.4) --------------------------

    def to_prometheus(self, prefix: str = "cadence") -> str:
        """Render every series in prometheus text format. Scope stays a
        label (the tally-tagged-scope shape), the metric name is
        sanitized into the prometheus grammar: counters get `_total`,
        histograms emit `_bucket`/`_sum`/`_count` with `le` labels.

        Renders from raw_series()'s deep copy: the old shallow copy kept
        live HistogramStat references, so a concurrent observe() could
        land between the `_bucket` walk and the `_count` line and the
        exposition's +Inf bucket would disagree with its own count."""
        self._collect()
        counters, gauges, histograms = self.raw_series()

        def metric_name(name: str) -> str:
            return prefix + "_" + re.sub(r"[^a-zA-Z0-9_:]", "_", name)

        def fmt(value: float) -> str:
            return str(int(value)) if float(value).is_integer() else str(value)

        lines: List[str] = []
        typed: set = set()

        def header(mname: str, kind: str) -> None:
            if mname not in typed:
                typed.add(mname)
                lines.append(f"# TYPE {mname} {kind}")

        def by_family(items):
            # all samples of one metric family must be contiguous
            # (exposition-format requirement), so sort name-first
            return sorted(items, key=lambda kv: (kv[0][1], kv[0][0]))

        for (scope, name), v in by_family(counters.items()):
            mname = metric_name(name) + "_total"
            header(mname, "counter")
            lines.append(f'{mname}{{scope="{scope}"}} {v}')
        for (scope, name), v in by_family(gauges.items()):
            mname = metric_name(name)
            header(mname, "gauge")
            lines.append(f'{mname}{{scope="{scope}"}} {fmt(v)}')
        for (scope, name), (count, total, bounds, buckets) in by_family(
                histograms.items()):
            mname = metric_name(name)
            header(mname, "histogram")
            running = 0
            for bound, n in zip(bounds, buckets):
                running += n
                lines.append(f'{mname}_bucket{{scope="{scope}",'
                             f'le="{bound}"}} {running}')
            lines.append(
                f'{mname}_bucket{{scope="{scope}",le="+Inf"}} {count}')
            lines.append(
                f'{mname}_sum{{scope="{scope}"}} {fmt(round(total, 9))}')
            lines.append(f'{mname}_count{{scope="{scope}"}} {count}')
        return "\n".join(lines) + ("\n" if lines else "")


class Scope:
    """One named scope (metrics.Scope analog)."""

    def __init__(self, registry: MetricsRegistry, name: str) -> None:
        self._r = registry
        self.name = name

    def inc(self, metric: str, delta: int = 1) -> None:
        self._r.inc(self.name, metric, delta)

    def record(self, metric: str, seconds: float) -> None:
        self._r.record(self.name, metric, seconds)

    def gauge(self, metric: str, value: float) -> None:
        self._r.gauge(self.name, metric, value)

    @contextmanager
    def timed(self, metric: str = M_LATENCY):
        start = time.perf_counter()
        try:
            yield
        finally:
            self._r.record(self.name, metric, time.perf_counter() - start)


#: fallback registry for components constructed without explicit wiring
#: (a cluster passes its own; the default keeps standalone use observable)
DEFAULT_REGISTRY = MetricsRegistry()
