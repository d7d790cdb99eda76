"""Dense replay state: the TPU-resident twin of the oracle's MutableState.

The reference keeps per-workflow mutable state as Go maps and structs
(mutable_state_builder.go:83-172). Here every field is a struct-of-arrays
tensor over the workflow axis W, so one transition step updates all W
workflows in lockstep:

- scalars:        [W]       (execution info + decision state + version)
- pending tables: [W, K]    (activities, timers, children, cancels, signals)
- version history:[W, Kv]   (event id / version item pairs + count)

Capacities K are fixed (PayloadLayout); overflow sets the per-workflow
error flag — measured and reported by the caller, never silent (the host
engine falls back to the oracle replayer for flagged workflows, the analog
of the reference's per-workflow Go path).

The error flag is sticky: a workflow whose history is invalid freezes its
state at the first bad event, mirroring the reference's error return from
ApplyEvents (which aborts that workflow's replay transaction).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.checksum import DEFAULT_LAYOUT, PAD, PayloadLayout
from ..core.enums import EMPTY_EVENT_ID, EMPTY_VERSION, FIRST_EVENT_ID, WorkflowState

I64 = jnp.int64
I32 = jnp.int32
BOOL = jnp.bool_


class ActivityTable(NamedTuple):
    """Pending activities; fields mirror oracle ActivityInfo
    (persistence ActivityInfo, dataManagerInterfaces.go:752)."""

    occ: jnp.ndarray            # [W, K] bool
    schedule_id: jnp.ndarray    # [W, K] i64
    started_id: jnp.ndarray     # [W, K] i64
    version: jnp.ndarray        # [W, K] i64
    activity_key: jnp.ndarray   # [W, K] i64 (interned ActivityID)
    scheduled_time: jnp.ndarray # [W, K] i64 nanos
    started_time: jnp.ndarray   # [W, K] i64 nanos
    last_heartbeat: jnp.ndarray # [W, K] i64 nanos
    sched_to_start: jnp.ndarray # [W, K] i64 seconds
    sched_to_close: jnp.ndarray # [W, K] i64 seconds
    start_to_close: jnp.ndarray # [W, K] i64 seconds
    heartbeat: jnp.ndarray      # [W, K] i64 seconds
    cancel_requested: jnp.ndarray  # [W, K] bool
    cancel_request_id: jnp.ndarray # [W, K] i64
    attempt: jnp.ndarray        # [W, K] i64
    timer_status: jnp.ndarray   # [W, K] i32 (TIMER_TASK_STATUS_* bitmask)
    has_retry: jnp.ndarray      # [W, K] bool
    batch_id: jnp.ndarray       # [W, K] i64 (ScheduledEventBatchID)


class TimerTable(NamedTuple):
    """Pending user timers (TimerInfo, dataManagerInterfaces.go:792)."""

    occ: jnp.ndarray          # [W, K] bool
    timer_key: jnp.ndarray    # [W, K] i64 (interned TimerID)
    started_id: jnp.ndarray   # [W, K] i64
    expiry_time: jnp.ndarray  # [W, K] i64 nanos
    task_status: jnp.ndarray  # [W, K] i32
    version: jnp.ndarray      # [W, K] i64


class ChildTable(NamedTuple):
    """Pending child workflows (ChildExecutionInfo, dataManagerInterfaces.go:801)."""

    occ: jnp.ndarray          # [W, K] bool
    initiated_id: jnp.ndarray # [W, K] i64
    started_id: jnp.ndarray   # [W, K] i64
    version: jnp.ndarray      # [W, K] i64
    batch_id: jnp.ndarray     # [W, K] i64


class InitiatedTable(NamedTuple):
    """Pending external request-cancels / signals (RequestCancelInfo /
    SignalInfo, dataManagerInterfaces.go:818,:826)."""

    occ: jnp.ndarray          # [W, K] bool
    initiated_id: jnp.ndarray # [W, K] i64
    version: jnp.ndarray      # [W, K] i64
    batch_id: jnp.ndarray     # [W, K] i64


class ReplayState(NamedTuple):
    """All per-workflow state carried through the event scan."""

    # execution info scalars (checksum-relevant first)
    state: jnp.ndarray                 # [W] i32 WorkflowState
    close_status: jnp.ndarray          # [W] i32 CloseStatus
    cancel_requested: jnp.ndarray      # [W] bool
    last_first_event_id: jnp.ndarray   # [W] i64
    next_event_id: jnp.ndarray         # [W] i64
    last_processed_event: jnp.ndarray  # [W] i64
    signal_count: jnp.ndarray          # [W] i64
    # decision state (mutable_state_decision_task_manager.go)
    decision_version: jnp.ndarray      # [W] i64
    decision_schedule_id: jnp.ndarray  # [W] i64
    decision_started_id: jnp.ndarray   # [W] i64
    decision_attempt: jnp.ndarray      # [W] i64
    decision_timeout: jnp.ndarray      # [W] i64 seconds
    decision_scheduled_ts: jnp.ndarray # [W] i64 nanos
    decision_started_ts: jnp.ndarray   # [W] i64 nanos
    decision_original_scheduled_ts: jnp.ndarray  # [W] i64 nanos
    # other execution info
    workflow_timeout: jnp.ndarray      # [W] i64 seconds
    decision_sts_timeout: jnp.ndarray  # [W] i64 seconds (DecisionStartToCloseTimeout)
    start_timestamp: jnp.ndarray       # [W] i64 nanos
    completion_event_batch_id: jnp.ndarray  # [W] i64
    last_event_task_id: jnp.ndarray    # [W] i64
    workflow_attempt: jnp.ndarray      # [W] i64
    expiration_time: jnp.ndarray       # [W] i64 nanos
    has_parent: jnp.ndarray            # [W] bool
    # version bookkeeping: per-branch item tables (versionHistories.go) —
    # branch axis B supports NDC divergent histories on device; linear
    # histories use branch 0 only
    current_version: jnp.ndarray       # [W] i64
    vh_event_ids: jnp.ndarray          # [W, B, Kv] i64 (PAD-filled)
    vh_versions: jnp.ndarray           # [W, B, Kv] i64 (PAD-filled)
    vh_count: jnp.ndarray              # [W, B] i32
    current_branch: jnp.ndarray        # [W] i32 (versionHistories.current_index)
    # pending tables
    activities: ActivityTable
    timers: TimerTable
    children: ChildTable
    cancels: InitiatedTable
    signals: InitiatedTable
    # sticky error flag (0 = healthy; else ErrorCode of first failure)
    error: jnp.ndarray                 # [W] i32


class ErrorCode:
    """First-failure codes recorded in ReplayState.error."""

    NONE = 0
    INVALID_STATE_TRANSITION = 1
    VERSION_HISTORY_ORDER = 2
    VERSION_HISTORY_OVERFLOW = 3
    MISSING_DECISION = 4
    MISSING_ACTIVITY = 5
    MISSING_TIMER = 6
    MISSING_CHILD = 7
    MISSING_REQUEST_CANCEL = 8
    MISSING_SIGNAL = 9
    TABLE_OVERFLOW = 10
    UNKNOWN_EVENT_TYPE = 11
    INVALID_BACKOFF_INITIATOR = 12
    BRANCH_OVERFLOW = 13
    BAD_FORK = 14


#: error codes a widened-K re-replay can clear (engine/ladder.py): the
#: history is valid, the kernel's fixed capacities just weren't enough.
#: Every other code is a genuine history error no capacity would fix —
#: those go straight to oracle arbitration.
CAPACITY_ERRORS = (
    ErrorCode.VERSION_HISTORY_OVERFLOW,
    ErrorCode.TABLE_OVERFLOW,
    ErrorCode.BRANCH_OVERFLOW,
)


def pick_branch(arr: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Each workflow's row `idx[w]` of a version-history table: `arr`
    [W, B, ...] → [W, ...]. The one way the `vh_*` tables are indexed by
    branch (ops/transitions.step, ops/payload).

    B is static, so the pick is a chain of B - 1 selects over slices of
    axis 1 and never a gather. On the v5e a gather here costs twice: the
    chip serialises it, and it pins the tables' layout with Kv = 8 on the
    128 lanes, so every other op over them runs lane-sparse too; selects
    fuse into their consumers and leave W on the lanes (PERF.md §6,
    PR 28: the replay kernel 95.7 → 4.1 ns an event). `idx` must lie in
    [0, B - 1] — `step` clips the event's lanes, and `current_branch` is
    only ever written from a clipped index; row 0 is the chain's default."""
    idx = idx.reshape(idx.shape + (1,) * (arr.ndim - 2))
    out = arr[:, 0]
    for j in range(1, arr.shape[1]):
        out = jnp.where(idx == j, arr[:, j], out)
    return out


def widen_layout(layout: PayloadLayout, factor: int) -> PayloadLayout:
    """The escalation-rung layout: every kernel capacity multiplied by
    `factor` (the reference's pending maps are unbounded Go maps —
    mutable_state_builder.go — so capacity pressure is purely a device
    artifact; doubling K per rung keeps flagged rows on device instead
    of falling off to the per-workflow Python oracle)."""
    return PayloadLayout(
        max_version_history_items=layout.max_version_history_items * factor,
        max_activities=layout.max_activities * factor,
        max_timers=layout.max_timers * factor,
        max_children=layout.max_children * factor,
        max_request_cancels=layout.max_request_cancels * factor,
        max_signals=layout.max_signals * factor,
        max_branches=layout.max_branches * factor,
    )


@partial(jax.jit, static_argnames=("num_workflows", "layout"))
def init_state(num_workflows: int, layout: PayloadLayout = DEFAULT_LAYOUT) -> ReplayState:
    """Fresh state for W workflows, matching the oracle's ExecutionInfo
    defaults (oracle/mutable_state.py ExecutionInfo / NewMutableStateBuilder).

    Jitted as ONE program per (W, layout): called eagerly — the serving
    tier's pad rows, warm-up and cold admits do — every `jnp.zeros` /
    `jnp.full` below would otherwise be an XLA executable of its own per
    distinct (shape, dtype, value), and on the TPU each of those tiny
    compiles costs seconds (measured on a v5e: 330 of them in one host's
    boot warm-up). Inside another jit this inlines, as before."""
    W = num_workflows

    def full(shape, value, dtype=I64):
        return jnp.full(shape, value, dtype=dtype)

    def zeros(shape, dtype=I64):
        return jnp.zeros(shape, dtype=dtype)

    Ka, Kt = layout.max_activities, layout.max_timers
    Kc, Kr, Ks = layout.max_children, layout.max_request_cancels, layout.max_signals
    Kv = layout.max_version_history_items
    B = layout.max_branches

    activities = ActivityTable(
        occ=zeros((W, Ka), BOOL),
        schedule_id=zeros((W, Ka)), started_id=zeros((W, Ka)),
        version=zeros((W, Ka)), activity_key=zeros((W, Ka)),
        scheduled_time=zeros((W, Ka)), started_time=zeros((W, Ka)),
        last_heartbeat=zeros((W, Ka)),
        sched_to_start=zeros((W, Ka)), sched_to_close=zeros((W, Ka)),
        start_to_close=zeros((W, Ka)), heartbeat=zeros((W, Ka)),
        cancel_requested=zeros((W, Ka), BOOL), cancel_request_id=zeros((W, Ka)),
        attempt=zeros((W, Ka)), timer_status=zeros((W, Ka), I32),
        has_retry=zeros((W, Ka), BOOL), batch_id=zeros((W, Ka)),
    )
    timers = TimerTable(
        occ=zeros((W, Kt), BOOL), timer_key=zeros((W, Kt)),
        started_id=zeros((W, Kt)), expiry_time=zeros((W, Kt)),
        task_status=zeros((W, Kt), I32), version=zeros((W, Kt)),
    )
    children = ChildTable(
        occ=zeros((W, Kc), BOOL), initiated_id=zeros((W, Kc)),
        started_id=zeros((W, Kc)), version=zeros((W, Kc)),
        batch_id=zeros((W, Kc)),
    )
    cancels = InitiatedTable(
        occ=zeros((W, Kr), BOOL), initiated_id=zeros((W, Kr)),
        version=zeros((W, Kr)), batch_id=zeros((W, Kr)),
    )
    signals = InitiatedTable(
        occ=zeros((W, Ks), BOOL), initiated_id=zeros((W, Ks)),
        version=zeros((W, Ks)), batch_id=zeros((W, Ks)),
    )

    return ReplayState(
        state=full((W,), WorkflowState.Created, I32),
        close_status=zeros((W,), I32),
        cancel_requested=zeros((W,), BOOL),
        last_first_event_id=full((W,), FIRST_EVENT_ID),
        next_event_id=full((W,), FIRST_EVENT_ID),
        last_processed_event=full((W,), EMPTY_EVENT_ID),
        signal_count=zeros((W,)),
        decision_version=full((W,), EMPTY_VERSION),
        decision_schedule_id=full((W,), EMPTY_EVENT_ID),
        decision_started_id=full((W,), EMPTY_EVENT_ID),
        decision_attempt=zeros((W,)),
        decision_timeout=zeros((W,)),
        decision_scheduled_ts=zeros((W,)),
        decision_started_ts=zeros((W,)),
        decision_original_scheduled_ts=zeros((W,)),
        workflow_timeout=zeros((W,)),
        decision_sts_timeout=zeros((W,)),
        start_timestamp=zeros((W,)),
        completion_event_batch_id=full((W,), EMPTY_EVENT_ID),
        last_event_task_id=zeros((W,)),
        workflow_attempt=zeros((W,)),
        expiration_time=zeros((W,)),
        has_parent=zeros((W,), BOOL),
        current_version=full((W,), EMPTY_VERSION),
        vh_event_ids=full((W, B, Kv), PAD),
        vh_versions=full((W, B, Kv), PAD),
        vh_count=zeros((W, B), I32),
        current_branch=zeros((W,), I32),
        activities=activities,
        timers=timers,
        children=children,
        cancels=cancels,
        signals=signals,
        error=zeros((W,), I32),
    )


def layout_of(s: ReplayState) -> PayloadLayout:
    """Recover the PayloadLayout a state was built with (from array shapes)."""
    return PayloadLayout(
        max_version_history_items=s.vh_event_ids.shape[2],
        max_activities=s.activities.occ.shape[1],
        max_timers=s.timers.occ.shape[1],
        max_children=s.children.occ.shape[1],
        max_request_cancels=s.cancels.occ.shape[1],
        max_signals=s.signals.occ.shape[1],
        max_branches=s.vh_event_ids.shape[1],
    )


def widen_state(s: ReplayState, out_layout: PayloadLayout) -> ReplayState:
    """Re-home a carried state at a WIDER layout: every table keeps its
    occupied slots at their original indices and gains empty slots past
    the old capacity (occ False, PAD for version-history items) — so
    replaying appended events from the widened state is exactly replaying
    them with more headroom, never a different history. This is how the
    escalation ladder keeps capacity-flagged RESIDENT states on device
    (engine/resident.py): the pre-append state widens, the suffix
    re-replays at 2K/4K, and the row stays in HBM instead of falling
    back to a full-history re-replay."""
    import jax

    fresh = init_state(s.state.shape[0], out_layout)

    def widen(cur, new):
        if cur.shape == new.shape:
            return cur
        return new.at[tuple(slice(0, d) for d in cur.shape)].set(cur)

    return jax.tree_util.tree_map(widen, s, fresh)


def narrow_ok(s: ReplayState, out_layout: PayloadLayout) -> jnp.ndarray:
    """[W] bool: rows whose state fits `out_layout` EXACTLY — no occupied
    table slot, version-history item, or branch beyond the narrow
    capacities — so narrow_state() on them is lossless (the re-narrow
    half of the ladder's widen/re-narrow round trip: an escalated
    resident row whose pending load drained back under base K returns to
    base-width HBM footprint)."""
    Kv = out_layout.max_version_history_items
    B = out_layout.max_branches
    ok = s.current_branch < B
    if s.vh_count.shape[1] > B:
        ok &= (s.vh_count[:, B:] == 0).all(axis=1)
    ok &= (s.vh_count <= Kv).all(axis=1)
    for table, cap in ((s.activities, out_layout.max_activities),
                       (s.timers, out_layout.max_timers),
                       (s.children, out_layout.max_children),
                       (s.cancels, out_layout.max_request_cancels),
                       (s.signals, out_layout.max_signals)):
        if table.occ.shape[1] > cap:
            ok &= ~table.occ[:, cap:].any(axis=1)
    return ok


def narrow_state(s: ReplayState, out_layout: PayloadLayout) -> ReplayState:
    """Slice a widened state down to `out_layout`. Only valid for rows
    where narrow_ok() holds — slots past the narrow capacities are
    dropped, so an occupied one would silently vanish (callers gate on
    the mask; engine/resident.py keeps non-narrowable rows widened)."""
    import jax

    fresh = init_state(s.state.shape[0], out_layout)

    def narrow(cur, new):
        if cur.shape == new.shape:
            return cur
        return cur[tuple(slice(0, d) for d in new.shape)]

    return jax.tree_util.tree_map(narrow, s, fresh)


def reset_rows(s: ReplayState, mask: jnp.ndarray) -> ReplayState:
    """Blend fresh init values into the rows where `mask` holds — the
    continue-as-new run boundary (the reference builds a brand-new
    mutableStateBuilder for the new run). The sticky error flag survives:
    a chain whose earlier run corrupted stays flagged."""
    import jax

    fresh = init_state(s.state.shape[0], layout_of(s))

    def blend(cur, new):
        m = mask.reshape((-1,) + (1,) * (cur.ndim - 1))
        return jnp.where(m, new, cur)

    out = jax.tree_util.tree_map(blend, s, fresh)
    return out._replace(error=s.error)
