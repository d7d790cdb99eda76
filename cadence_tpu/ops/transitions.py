"""One lockstep transition step: apply event e to all W workflows.

This is the vectorized twin of the reference's per-event switch
(state_builder.go:131-631) plus the Replicate* mutations
(mutable_state_builder.go / mutable_state_decision_task_manager.go). Where
the Go code branches per workflow, here every branch's update is computed
for all workflows and blended by event-type masks — the SIMD formulation
that keeps the TPU VPU busy. Pending-map operations become masked
insert/delete/update on fixed-capacity [W, K] tables.

Error semantics: conditions that make the reference return an error
(missing infos, invalid state transitions, version-history order) set a
sticky per-workflow error code and freeze that workflow's row; healthy rows
are unaffected. See ops/state.py ErrorCode.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..core.checksum import PAD
from ..core.enums import (
    CLOSE_EVENT_STATUS,
    EMPTY_EVENT_ID,
    EMPTY_VERSION,
    NANOS_PER_SECOND,
    CloseStatus,
    EventType,
    TimeoutType,
    WorkflowState,
)
from .encode import (
    FLAG_RUN_RESET,
    FLAG_VH_ONLY,
    LANE_A0,
    LANE_BATCH_FIRST,
    LANE_BATCH_LAST,
    LANE_BRANCH,
    LANE_EVENT_ID,
    LANE_EVENT_TYPE,
    LANE_FLAGS,
    LANE_PARENT,
    LANE_TASK_ID,
    LANE_TIMESTAMP,
    LANE_VERSION,
)
from .state import ErrorCode, ReplayState, pick_branch, reset_rows

_I64 = jnp.int64


def _sel(mask, new, old):
    return jnp.where(mask, new, old)


def _set_err(error, cond, code):
    """Record `code` where cond holds and no earlier error exists (sticky)."""
    return jnp.where((error == 0) & cond, jnp.int32(code), error)


# ---------------------------------------------------------------------------
# Masked table primitives (the Go-map analog on dense [W, K] tables)
# ---------------------------------------------------------------------------


def table_insert_slot(occ: jnp.ndarray, mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """First-free-slot selection. Returns (onehot [W,K], new_occ, overflow [W])."""
    full = occ.all(axis=1)
    do = mask & ~full
    slot = jnp.argmin(occ, axis=1)  # first False
    K = occ.shape[1]
    onehot = (jnp.arange(K)[None, :] == slot[:, None]) & do[:, None]
    return onehot, occ | onehot, mask & full


def table_match(occ: jnp.ndarray, key_field: jnp.ndarray, key: jnp.ndarray,
                mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Equality lookup. Returns (sel [W,K] matching slots under mask,
    missing [W] = masked rows with no match)."""
    eq = occ & (key_field == key[:, None])
    found = eq.any(axis=1)
    return eq & mask[:, None], mask & ~found


def _scatter(field: jnp.ndarray, onehot: jnp.ndarray, value) -> jnp.ndarray:
    value = jnp.asarray(value)
    if value.ndim == 1:
        value = value[:, None]
    return jnp.where(onehot, value.astype(field.dtype), field)


# ---------------------------------------------------------------------------
# Workflow state/close-status transition guard
# (workflowExecutionInfo.go:44-165, vectorized)
# ---------------------------------------------------------------------------


def state_transition_valid(cur_state, cur_close, new_state, new_close):
    none = CloseStatus.Nothing
    to_created_running_zombie_ok = new_close == none
    from_created = (
        jnp.where(
            (new_state == WorkflowState.Created)
            | (new_state == WorkflowState.Running)
            | (new_state == WorkflowState.Zombie),
            to_created_running_zombie_ok,
            (new_state == WorkflowState.Completed)
            & ((new_close == CloseStatus.Terminated)
               | (new_close == CloseStatus.TimedOut)
               | (new_close == CloseStatus.ContinuedAsNew)),
        )
    )
    from_running = jnp.where(
        new_state == WorkflowState.Created,
        False,
        jnp.where(
            (new_state == WorkflowState.Running) | (new_state == WorkflowState.Zombie),
            to_created_running_zombie_ok,
            (new_state == WorkflowState.Completed) & (new_close != none),
        ),
    )
    from_completed = (new_state == WorkflowState.Completed) & (new_close == cur_close)
    from_zombie = jnp.where(
        (new_state == WorkflowState.Created) | (new_state == WorkflowState.Running),
        new_close == none,
        ((new_state == WorkflowState.Completed) | (new_state == WorkflowState.Zombie))
        & (new_close != none),
    )
    return jnp.where(
        cur_state == WorkflowState.Void,
        True,
        jnp.where(
            cur_state == WorkflowState.Created,
            from_created,
            jnp.where(
                cur_state == WorkflowState.Running,
                from_running,
                jnp.where(
                    cur_state == WorkflowState.Completed,
                    from_completed,
                    jnp.where(cur_state == WorkflowState.Zombie, from_zombie, False),
                ),
            ),
        ),
    )


# ---------------------------------------------------------------------------
# The step
# ---------------------------------------------------------------------------


@jax.named_scope("transition")   # metadata only: names the phase's ops
def step(s: ReplayState, ev: jnp.ndarray,
         enable_reset: bool = True) -> ReplayState:
    """Apply one event (lanes [W, L]) to all workflows. Returns new state.

    `enable_reset` statically compiles the continue-as-new run-boundary
    blend in or out: corpora that never set FLAG_RUN_RESET (e.g. the
    device-side generator) skip it entirely — and lax.cond's
    varying-manual-axes typing doesn't mix with shard_map, which the
    sharded fused kernel uses."""
    ev_id = ev[:, LANE_EVENT_ID]
    etype = ev[:, LANE_EVENT_TYPE]
    ev_version = ev[:, LANE_VERSION]
    ts = ev[:, LANE_TIMESTAMP]
    task_id = ev[:, LANE_TASK_ID]
    batch_first = ev[:, LANE_BATCH_FIRST]
    batch_last = ev[:, LANE_BATCH_LAST]
    branch = ev[:, LANE_BRANCH].astype(jnp.int32)
    parent = ev[:, LANE_PARENT].astype(jnp.int32)
    flags = ev[:, LANE_FLAGS]
    a = [ev[:, LANE_A0 + i] for i in range(8)]

    # --- 0. continue-as-new run boundary: a FLAG_RUN_RESET event starts a
    # fresh run in this row (the reference builds a brand-new
    # mutableStateBuilder for newRunHistory); sticky errors survive the
    # reset. lax.cond keeps the full-state blend off the hot path for the
    # (typical) steps where no workflow crosses a run boundary.
    if enable_reset:
        import jax

        do_reset = (ev_id > 0) & (s.error == 0) & ((flags & FLAG_RUN_RESET) != 0)
        s = jax.lax.cond(do_reset.any(), lambda st: reset_rows(st, do_reset),
                         lambda st: st, s)

    live = (ev_id > 0) & (s.error == 0)
    vh_only = (flags & FLAG_VH_ONLY) != 0
    error = s.error

    # --- 1. per-branch version-history bookkeeping (versionHistories.go).
    # The event targets branch `branch`; a branch receiving its FIRST item
    # with parent != branch fork-inherits the parent's items truncated at
    # this event's predecessor (DuplicateUntilLCAItem, versionHistory.go:136).
    B = s.vh_event_ids.shape[1]
    Kv = s.vh_event_ids.shape[2]
    branch_over = live & (branch >= B)
    error = _set_err(error, branch_over, ErrorCode.BRANCH_OVERFLOW)
    live = live & ~branch_over
    b = jnp.clip(branch, 0, B - 1)
    p = jnp.clip(parent, 0, B - 1)

    b_ids = pick_branch(s.vh_event_ids, b)        # [W, Kv]
    b_versions = pick_branch(s.vh_versions, b)    # [W, Kv]
    b_count = pick_branch(s.vh_count, b)          # [W]
    p_ids = pick_branch(s.vh_event_ids, p)
    p_versions = pick_branch(s.vh_versions, p)
    p_count = pick_branch(s.vh_count, p)

    # fork-inherit: copy the parent's item prefix covering events < ev_id,
    # capping the covering item at ev_id - 1 (the LCA event)
    inherit = live & (b_count == 0) & (p != b)
    lca_eid = ev_id - 1
    slot = jnp.arange(Kv)[None, :]
    prev_eid = jnp.concatenate(
        [jnp.zeros((p_ids.shape[0], 1), p_ids.dtype), p_ids[:, :-1]], axis=1)
    keep = (slot < p_count[:, None]) & (prev_eid < lca_eid[:, None])
    # a fork below the parent's first item is host packing corruption
    bad_fork = inherit & ((p_count == 0) | (lca_eid < 1))
    error = _set_err(error, bad_fork, ErrorCode.BAD_FORK)
    inherit = inherit & ~bad_fork
    inh_ids = jnp.where(keep, jnp.minimum(p_ids, lca_eid[:, None]),
                        jnp.int64(PAD))
    inh_versions = jnp.where(keep, p_versions, jnp.int64(PAD))
    inh_count = keep.sum(axis=1).astype(s.vh_count.dtype)
    b_ids = jnp.where(inherit[:, None], inh_ids, b_ids)
    b_versions = jnp.where(inherit[:, None], inh_versions, b_versions)
    b_count = jnp.where(inherit, inh_count, b_count)
    live = live & ~bad_fork

    has_items = b_count > 0
    last_idx = jnp.maximum(b_count - 1, 0)
    vh_last_onehot = jnp.arange(Kv)[None, :] == last_idx[:, None]
    vh_last_version = jnp.where(
        has_items,
        jnp.where(vh_last_onehot, b_versions, 0).sum(axis=1),
        jnp.int64(EMPTY_VERSION),
    )
    vh_last_event = jnp.where(
        has_items,
        jnp.where(vh_last_onehot, b_ids, 0).sum(axis=1),
        jnp.int64(EMPTY_EVENT_ID),
    )

    # current branch's last version (for UpdateCurrentVersion on completed)
    cur_versions = pick_branch(s.vh_versions, s.current_branch)
    cur_count = pick_branch(s.vh_count, s.current_branch)
    cur_last_idx = jnp.maximum(cur_count - 1, 0)
    cur_last_onehot = jnp.arange(Kv)[None, :] == cur_last_idx[:, None]
    cur_last_version = jnp.where(
        cur_count > 0,
        jnp.where(cur_last_onehot, cur_versions, 0).sum(axis=1),
        jnp.int64(EMPTY_VERSION),
    )

    # --- 2. version history AddOrUpdateItem(event.ID, event.Version)
    # (versionHistory.go:193-225; state_builder.go:115-128)
    vh_order_bad = live & has_items & (
        (ev_version < vh_last_version) | (ev_id <= vh_last_event)
    )
    error = _set_err(error, vh_order_bad, ErrorCode.VERSION_HISTORY_ORDER)
    vh_ok = live & ~vh_order_bad
    append = vh_ok & (~has_items | (ev_version > vh_last_version))
    vh_overflow = append & (b_count >= Kv)
    error = _set_err(error, vh_overflow, ErrorCode.VERSION_HISTORY_OVERFLOW)
    append_ok = append & ~vh_overflow
    update_last = vh_ok & has_items & (ev_version == vh_last_version)
    onehot_append = (jnp.arange(Kv)[None, :] == b_count[:, None]) & append_ok[:, None]
    onehot_update = vh_last_onehot & update_last[:, None]
    write = onehot_append | onehot_update
    b_ids = jnp.where(write, ev_id[:, None], b_ids)
    b_versions = jnp.where(onehot_append, ev_version[:, None], b_versions)
    b_count = b_count + append_ok.astype(b_count.dtype)

    # scatter branch b's updated table back into [W, B, Kv]
    touched = live & (inherit | append_ok | update_last)
    bsel = (jnp.arange(B)[None, :] == b[:, None]) & touched[:, None]  # [W, B]
    vh_event_ids = jnp.where(bsel[:, :, None], b_ids[:, None, :], s.vh_event_ids)
    vh_versions = jnp.where(bsel[:, :, None], b_versions[:, None, :], s.vh_versions)
    vh_count = jnp.where(bsel, b_count[:, None], s.vh_count)

    # --- 3. current-branch arbitration (conflict_resolver.go: a non-current
    # branch whose head version overtakes the current branch's becomes
    # current; state application for the winner's events is host-scheduled
    # via FLAG_VH_ONLY, and this pointer is the device-side parity output)
    ok = vh_ok & ~vh_overflow
    switch = ok & (b != s.current_branch) & (ev_version > cur_last_version)
    current_branch = jnp.where(switch, b, s.current_branch)

    # --- 4. UpdateCurrentVersion(version, force=True)
    # (mutable_state_builder.go:495-533; state_builder.go:112)
    completed = s.state == WorkflowState.Completed
    current_version = _sel(live & ~vh_only,
                           jnp.where(completed, cur_last_version, ev_version),
                           s.current_version)

    # state transitions below apply only to non-VH-only events
    ok = ok & ~vh_only

    last_event_task_id = _sel(ok, task_id, s.last_event_task_id)

    def m(t: EventType) -> jnp.ndarray:
        return ok & (etype == int(t))

    # unknown event type (state_builder.go:629-630)
    error = _set_err(error, ok & ((etype < 0) | (etype > int(EventType.UpsertWorkflowSearchAttributes))),
                     ErrorCode.UNKNOWN_EVENT_TYPE)

    # ------------------------------------------------------------------
    # WorkflowExecutionStarted (mutable_state_builder.go:1751-1829)
    # ------------------------------------------------------------------
    m_started = m(EventType.WorkflowExecutionStarted)
    started_bad = m_started & ~state_transition_valid(
        s.state, s.close_status,
        jnp.int32(WorkflowState.Created), jnp.int32(CloseStatus.Nothing))
    error = _set_err(error, started_bad, ErrorCode.INVALID_STATE_TRANSITION)
    m_started = m_started & ~started_bad

    # a first-decision backoff with a Decider or unknown initiator is
    # rejected (task_generator.go:279-287); lane a7: -1 none, 1 retry, 2 cron
    bad_initiator = m_started & (a[2] > 0) & ((a[7] == 0) | (a[7] >= 3))
    error = _set_err(error, bad_initiator, ErrorCode.INVALID_BACKOFF_INITIATOR)
    m_started = m_started & ~bad_initiator

    workflow_timeout = _sel(m_started, a[0], s.workflow_timeout)
    decision_sts_timeout = _sel(m_started, a[1], s.decision_sts_timeout)
    start_timestamp = _sel(m_started, ts, s.start_timestamp)
    workflow_attempt = _sel(m_started, a[3], s.workflow_attempt)
    expiration_time = _sel(m_started & (a[4] != 0), a[4], s.expiration_time)
    has_parent = _sel(m_started, a[5] != 0, s.has_parent)
    state_v = _sel(m_started, jnp.int32(WorkflowState.Created), s.state)
    close_v = _sel(m_started, jnp.int32(CloseStatus.Nothing), s.close_status)
    last_processed = _sel(m_started, jnp.int64(EMPTY_EVENT_ID), s.last_processed_event)
    last_first = _sel(m_started, ev_id, s.last_first_event_id)

    # ------------------------------------------------------------------
    # Decision state machine (mutable_state_decision_task_manager.go)
    # ------------------------------------------------------------------
    d_version = s.decision_version
    d_sched = s.decision_schedule_id
    d_started = s.decision_started_id
    d_attempt = s.decision_attempt
    d_timeout = s.decision_timeout
    d_sched_ts = s.decision_scheduled_ts
    d_started_ts = s.decision_started_ts
    d_orig_ts = s.decision_original_scheduled_ts

    # started event resets decision fields (:1778-1782)
    d_version = _sel(m_started, jnp.int64(EMPTY_VERSION), d_version)
    d_sched = _sel(m_started, jnp.int64(EMPTY_EVENT_ID), d_sched)
    d_started = _sel(m_started, jnp.int64(EMPTY_EVENT_ID), d_started)
    d_timeout = _sel(m_started, jnp.int64(0), d_timeout)

    # DecisionTaskScheduled (:129-166)
    m_dsched = m(EventType.DecisionTaskScheduled)
    not_zombie = state_v != WorkflowState.Zombie
    dsched_trans = m_dsched & not_zombie
    dsched_bad = dsched_trans & ~state_transition_valid(
        state_v, close_v, jnp.int32(WorkflowState.Running), jnp.int32(CloseStatus.Nothing))
    error = _set_err(error, dsched_bad, ErrorCode.INVALID_STATE_TRANSITION)
    m_dsched = m_dsched & ~dsched_bad
    dsched_trans = dsched_trans & ~dsched_bad
    state_v = _sel(dsched_trans, jnp.int32(WorkflowState.Running), state_v)
    close_v = _sel(dsched_trans, jnp.int32(CloseStatus.Nothing), close_v)
    d_version = _sel(m_dsched, ev_version, d_version)
    d_sched = _sel(m_dsched, ev_id, d_sched)
    d_started = _sel(m_dsched, jnp.int64(EMPTY_EVENT_ID), d_started)
    d_attempt = _sel(m_dsched, a[1], d_attempt)
    d_timeout = _sel(m_dsched, a[0], d_timeout)
    d_sched_ts = _sel(m_dsched, ts, d_sched_ts)
    d_started_ts = _sel(m_dsched, jnp.int64(0), d_started_ts)
    d_orig_ts = _sel(m_dsched, ts, d_orig_ts)

    # DecisionTaskStarted (:199-242); attempt reset to 0 on replication
    m_dstart = m(EventType.DecisionTaskStarted)
    dstart_missing = m_dstart & (d_sched != a[0])
    error = _set_err(error, dstart_missing, ErrorCode.MISSING_DECISION)
    m_dstart = m_dstart & ~dstart_missing
    d_version = _sel(m_dstart, ev_version, d_version)
    d_started = _sel(m_dstart, ev_id, d_started)
    d_attempt = _sel(m_dstart, jnp.int64(0), d_attempt)
    d_started_ts = _sel(m_dstart, ts, d_started_ts)

    # DecisionTaskCompleted (:244-249, 679-694, 827-838)
    m_dcomp = m(EventType.DecisionTaskCompleted)
    d_version = _sel(m_dcomp, jnp.int64(EMPTY_VERSION), d_version)
    d_sched = _sel(m_dcomp, jnp.int64(EMPTY_EVENT_ID), d_sched)
    d_started = _sel(m_dcomp, jnp.int64(EMPTY_EVENT_ID), d_started)
    d_attempt = _sel(m_dcomp, jnp.int64(0), d_attempt)
    d_timeout = _sel(m_dcomp, jnp.int64(0), d_timeout)
    d_sched_ts = _sel(m_dcomp, jnp.int64(0), d_sched_ts)
    d_started_ts = _sel(m_dcomp, jnp.int64(0), d_started_ts)
    # original scheduled timestamp deliberately kept (:690-691)
    last_processed = _sel(m_dcomp, a[1], last_processed)

    # DecisionTaskFailed / TimedOut: FailDecision then transient decision
    # (:643-676, :168-197; state_builder.go:237-281). A SCHEDULE-TO-START
    # timeout (the sticky dispatch deadline, :256-271) does NOT increment
    # the attempt — decision state clears fully and no transient is
    # created (attempt 0); every other fail/timeout increments, and with
    # attempt >0 and no pending decision the transient is always created:
    # schedule ID = stale next_event_id (see :173-182).
    m_dtimeout = m(EventType.DecisionTaskTimedOut)
    m_noinc = m_dtimeout & (a[0] == int(TimeoutType.ScheduleToStart))
    m_dfail = (m(EventType.DecisionTaskFailed) | m_dtimeout) & ~m_noinc
    attempt_after_fail = d_attempt + 1
    d_version = _sel(m_dfail, current_version, d_version)
    d_version = _sel(m_noinc, jnp.int64(EMPTY_VERSION), d_version)
    d_sched = _sel(m_dfail, s.next_event_id, d_sched)
    d_sched = _sel(m_noinc, jnp.int64(EMPTY_EVENT_ID), d_sched)
    d_started = _sel(m_dfail | m_noinc, jnp.int64(EMPTY_EVENT_ID), d_started)
    d_attempt = _sel(m_dfail, attempt_after_fail, d_attempt)
    d_attempt = _sel(m_noinc, jnp.int64(0), d_attempt)
    d_timeout = _sel(m_dfail, decision_sts_timeout, d_timeout)
    d_timeout = _sel(m_noinc, jnp.int64(0), d_timeout)
    d_sched_ts = _sel(m_dfail, ts, d_sched_ts)
    d_sched_ts = _sel(m_noinc, jnp.int64(0), d_sched_ts)
    d_started_ts = _sel(m_dfail | m_noinc, jnp.int64(0), d_started_ts)
    d_orig_ts = _sel(m_dfail | m_noinc, jnp.int64(0), d_orig_ts)

    # ------------------------------------------------------------------
    # Activities
    # ------------------------------------------------------------------
    act = s.activities

    # ActivityTaskScheduled → insert (mutable_state_builder.go:2142-2197)
    m_asched = m(EventType.ActivityTaskScheduled)
    onehot, act_occ, act_over = table_insert_slot(act.occ, m_asched)
    error = _set_err(error, act_over, ErrorCode.TABLE_OVERFLOW)
    act = act._replace(
        occ=act_occ,
        schedule_id=_scatter(act.schedule_id, onehot, ev_id),
        started_id=_scatter(act.started_id, onehot, jnp.full_like(ev_id, EMPTY_EVENT_ID)),
        version=_scatter(act.version, onehot, ev_version),
        activity_key=_scatter(act.activity_key, onehot, a[0]),
        scheduled_time=_scatter(act.scheduled_time, onehot, ts),
        started_time=_scatter(act.started_time, onehot, jnp.zeros_like(ts)),
        last_heartbeat=_scatter(act.last_heartbeat, onehot, jnp.zeros_like(ts)),
        sched_to_start=_scatter(act.sched_to_start, onehot, a[1]),
        sched_to_close=_scatter(act.sched_to_close, onehot, a[2]),
        start_to_close=_scatter(act.start_to_close, onehot, a[3]),
        heartbeat=_scatter(act.heartbeat, onehot, a[4]),
        cancel_requested=jnp.where(onehot, False, act.cancel_requested),
        cancel_request_id=_scatter(act.cancel_request_id, onehot,
                                   jnp.full_like(ev_id, EMPTY_EVENT_ID)),
        attempt=_scatter(act.attempt, onehot, jnp.zeros_like(ev_id)),
        timer_status=jnp.where(onehot, jnp.int32(0), act.timer_status),
        has_retry=jnp.where(onehot, (a[5] != 0)[:, None], act.has_retry),
        batch_id=_scatter(act.batch_id, onehot, batch_first),
    )
    # NOTE: retry expiration (a[6]) participates only in active-side retry
    # (execution/retry.go), not in replay state; the active engine recomputes
    # it from scheduled_time + the retry policy when needed.

    # ActivityTaskStarted → update by schedule_id (:2254-2276)
    m_astart = m(EventType.ActivityTaskStarted)
    sel_slots, missing = table_match(act.occ, act.schedule_id, a[0], m_astart)
    error = _set_err(error, missing, ErrorCode.MISSING_ACTIVITY)
    act = act._replace(
        version=_scatter(act.version, sel_slots, ev_version),
        started_id=_scatter(act.started_id, sel_slots, ev_id),
        started_time=_scatter(act.started_time, sel_slots, ts),
        last_heartbeat=_scatter(act.last_heartbeat, sel_slots, ts),
    )

    # ActivityTask{Completed,Failed,TimedOut,Canceled} → delete (:2312-2536)
    m_aclose = (
        m(EventType.ActivityTaskCompleted) | m(EventType.ActivityTaskFailed)
        | m(EventType.ActivityTaskTimedOut) | m(EventType.ActivityTaskCanceled)
    )
    sel_slots, missing = table_match(act.occ, act.schedule_id, a[0], m_aclose)
    error = _set_err(error, missing, ErrorCode.MISSING_ACTIVITY)
    act = act._replace(occ=act.occ & ~sel_slots)

    # ActivityTaskCancelRequested → update by activity key; unknown IDs
    # tolerated on the passive side (:2444-2467)
    m_acreq = m(EventType.ActivityTaskCancelRequested)
    sel_slots, _ = table_match(act.occ, act.activity_key, a[0], m_acreq)
    act = act._replace(
        version=_scatter(act.version, sel_slots, ev_version),
        cancel_requested=jnp.where(sel_slots, True, act.cancel_requested),
        cancel_request_id=_scatter(act.cancel_request_id, sel_slots, ev_id),
    )

    # ------------------------------------------------------------------
    # User timers (:3057-3168)
    # ------------------------------------------------------------------
    tmr = s.timers
    m_tstart = m(EventType.TimerStarted)
    onehot, tmr_occ, tmr_over = table_insert_slot(tmr.occ, m_tstart)
    error = _set_err(error, tmr_over, ErrorCode.TABLE_OVERFLOW)
    tmr = tmr._replace(
        occ=tmr_occ,
        timer_key=_scatter(tmr.timer_key, onehot, a[0]),
        started_id=_scatter(tmr.started_id, onehot, ev_id),
        expiry_time=_scatter(tmr.expiry_time, onehot, ts + a[1] * NANOS_PER_SECOND),
        task_status=jnp.where(onehot, jnp.int32(0), tmr.task_status),
        version=_scatter(tmr.version, onehot, ev_version),
    )
    m_tdel = m(EventType.TimerFired) | m(EventType.TimerCanceled)
    sel_slots, missing = table_match(tmr.occ, tmr.timer_key, a[0], m_tdel)
    error = _set_err(error, missing, ErrorCode.MISSING_TIMER)
    tmr = tmr._replace(occ=tmr.occ & ~sel_slots)

    # ------------------------------------------------------------------
    # Child workflows (:3417-3810)
    # ------------------------------------------------------------------
    ch = s.children
    m_cinit = m(EventType.StartChildWorkflowExecutionInitiated)
    onehot, ch_occ, ch_over = table_insert_slot(ch.occ, m_cinit)
    error = _set_err(error, ch_over, ErrorCode.TABLE_OVERFLOW)
    ch = ch._replace(
        occ=ch_occ,
        initiated_id=_scatter(ch.initiated_id, onehot, ev_id),
        started_id=_scatter(ch.started_id, onehot, jnp.full_like(ev_id, EMPTY_EVENT_ID)),
        version=_scatter(ch.version, onehot, ev_version),
        batch_id=_scatter(ch.batch_id, onehot, batch_first),
    )
    m_cstart = m(EventType.ChildWorkflowExecutionStarted)
    sel_slots, missing = table_match(ch.occ, ch.initiated_id, a[0], m_cstart)
    error = _set_err(error, missing, ErrorCode.MISSING_CHILD)
    ch = ch._replace(started_id=_scatter(ch.started_id, sel_slots, ev_id))
    m_cdel = (
        m(EventType.StartChildWorkflowExecutionFailed)
        | m(EventType.ChildWorkflowExecutionCompleted)
        | m(EventType.ChildWorkflowExecutionFailed)
        | m(EventType.ChildWorkflowExecutionCanceled)
        | m(EventType.ChildWorkflowExecutionTimedOut)
        | m(EventType.ChildWorkflowExecutionTerminated)
    )
    sel_slots, missing = table_match(ch.occ, ch.initiated_id, a[0], m_cdel)
    error = _set_err(error, missing, ErrorCode.MISSING_CHILD)
    ch = ch._replace(occ=ch.occ & ~sel_slots)

    # ------------------------------------------------------------------
    # External request-cancels / signals (:2760-2816, :2883-3027)
    # ------------------------------------------------------------------
    rc = s.cancels
    m_rcinit = m(EventType.RequestCancelExternalWorkflowExecutionInitiated)
    onehot, rc_occ, rc_over = table_insert_slot(rc.occ, m_rcinit)
    error = _set_err(error, rc_over, ErrorCode.TABLE_OVERFLOW)
    rc = rc._replace(
        occ=rc_occ,
        initiated_id=_scatter(rc.initiated_id, onehot, ev_id),
        version=_scatter(rc.version, onehot, ev_version),
        batch_id=_scatter(rc.batch_id, onehot, batch_first),
    )
    m_rcdel = (
        m(EventType.RequestCancelExternalWorkflowExecutionFailed)
        | m(EventType.ExternalWorkflowExecutionCancelRequested)
    )
    sel_slots, missing = table_match(rc.occ, rc.initiated_id, a[0], m_rcdel)
    error = _set_err(error, missing, ErrorCode.MISSING_REQUEST_CANCEL)
    rc = rc._replace(occ=rc.occ & ~sel_slots)

    sg = s.signals
    m_sginit = m(EventType.SignalExternalWorkflowExecutionInitiated)
    onehot, sg_occ, sg_over = table_insert_slot(sg.occ, m_sginit)
    error = _set_err(error, sg_over, ErrorCode.TABLE_OVERFLOW)
    sg = sg._replace(
        occ=sg_occ,
        initiated_id=_scatter(sg.initiated_id, onehot, ev_id),
        version=_scatter(sg.version, onehot, ev_version),
        batch_id=_scatter(sg.batch_id, onehot, batch_first),
    )
    m_sgdel = (
        m(EventType.SignalExternalWorkflowExecutionFailed)
        | m(EventType.ExternalWorkflowExecutionSignaled)
    )
    sel_slots, missing = table_match(sg.occ, sg.initiated_id, a[0], m_sgdel)
    error = _set_err(error, missing, ErrorCode.MISSING_SIGNAL)
    sg = sg._replace(occ=sg.occ & ~sel_slots)

    # ------------------------------------------------------------------
    # Workflow-level scalars
    # ------------------------------------------------------------------
    signal_count = s.signal_count + m(EventType.WorkflowExecutionSignaled).astype(_I64)
    cancel_requested = s.cancel_requested | m(EventType.WorkflowExecutionCancelRequested)

    # Close events (:2561-2655, :2719-2733, :3225-3240, :3366-3382)
    m_close = jnp.zeros_like(live)
    close_val = jnp.zeros_like(s.close_status)
    for et, cs in CLOSE_EVENT_STATUS:
        mm = m(et)
        m_close = m_close | mm
        close_val = jnp.where(mm, jnp.int32(cs), close_val)
    close_bad = m_close & ~state_transition_valid(
        state_v, close_v, jnp.int32(WorkflowState.Completed), close_val)
    error = _set_err(error, close_bad, ErrorCode.INVALID_STATE_TRANSITION)
    m_close = m_close & ~close_bad
    state_v = _sel(m_close, jnp.int32(WorkflowState.Completed), state_v)
    close_v = _sel(m_close, close_val, close_v)
    completion_batch = _sel(m_close, batch_first, s.completion_event_batch_id)

    # ------------------------------------------------------------------
    # Batch-end bookkeeping (state_builder.go:642-643); only when this
    # event applied cleanly
    # ------------------------------------------------------------------
    end_ok = ok & (batch_last == 1) & (error == 0)
    last_first = _sel(end_ok, batch_first, last_first)
    next_event_id = _sel(end_ok, ev_id + 1, s.next_event_id)

    return s._replace(
        state=state_v,
        close_status=close_v,
        cancel_requested=cancel_requested,
        last_first_event_id=last_first,
        next_event_id=next_event_id,
        last_processed_event=last_processed,
        signal_count=signal_count,
        decision_version=d_version,
        decision_schedule_id=d_sched,
        decision_started_id=d_started,
        decision_attempt=d_attempt,
        decision_timeout=d_timeout,
        decision_scheduled_ts=d_sched_ts,
        decision_started_ts=d_started_ts,
        decision_original_scheduled_ts=d_orig_ts,
        workflow_timeout=workflow_timeout,
        decision_sts_timeout=decision_sts_timeout,
        start_timestamp=start_timestamp,
        completion_event_batch_id=completion_batch,
        last_event_task_id=last_event_task_id,
        workflow_attempt=workflow_attempt,
        expiration_time=expiration_time,
        has_parent=has_parent,
        current_version=current_version,
        vh_event_ids=vh_event_ids,
        vh_versions=vh_versions,
        vh_count=vh_count,
        current_branch=current_branch,
        activities=act,
        timers=tmr,
        children=ch,
        cancels=rc,
        signals=sg,
        error=error,
    )
