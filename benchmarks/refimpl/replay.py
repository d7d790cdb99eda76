"""The plain reference: what one workflow's mutable state is once its history
has been replayed, as far as the checksum's canonical payload sees it, and
the CRC32 of that payload.

Written from upstream Cadence's rules (service/history/execution:
state_builder.go's per-event switch, mutable_state_decision_task_manager.go,
common/persistence/versionHistory.go, checksum.go's payload) as one table of
event types and five small functions for the decision task. It shares no
code with the program: not its state builder, its mutable state, its payload
row or its CRC. It keeps only what the payload holds, so it has no tasks,
timestamps, timeouts or retry policies; a history that refers to something
that is not pending raises `KeyError`, an event type that is not in the
table raises `LookupError`.

Input is plain data: a history is a list of transactions (batches), a
transaction a list of events, an event `(id, type name, version, attrs)`.
`plain()` makes that from history objects (the generator's or what was read
back from a store) without importing their classes.

The payload (upstream checksum.go:58-113, field for field) as a row of
little-endian int64: cancel requested, workflow state, last first event id,
next event id, last processed event, signal count, decision attempt /
schedule id / started id / version, sticky task list (0: a replayed
workflow is never sticky), then six count-prefixed lists padded with `PAD`
to fixed capacities: the current branch's version-history items as (event
id, version) pairs, and the sorted ids of the pending timers (started ids),
activities (schedule ids), children, external signals and external cancel
requests (initiated ids). The answer is zlib's CRC32 of those bytes.
"""
from __future__ import annotations

import struct
import zlib
from typing import Any, Dict, Iterable, List, Sequence, Tuple

Event = Tuple[int, str, int, Dict[str, Any]]

EMPTY_EVENT_ID = -23      # upstream common.EmptyEventID
EMPTY_VERSION = -24       # upstream common.EmptyVersion
CREATED, RUNNING, COMPLETED = 0, 1, 2   # persistence.WorkflowState*
SCHEDULE_TO_START = 1     # types.TimeoutTypeScheduleToStart

PAD = 1 << 62
VERSION_ITEMS = 8
#: the pending lists in payload order, with their capacities
PENDING = (("timers", 16), ("activities", 16), ("children", 8),
           ("signals", 8), ("cancels", 8))
ROW_WIDTH = 11 + 1 + 2 * VERSION_ITEMS + sum(1 + cap for _n, cap in PENDING)

# -- the table ---------------------------------------------------------------
# event type -> (what it does, the pending list, the attribute that names
# the entry). "open" files the event under its own id; "close" takes the
# entry the attribute names away, and fails if it is not there.

TABLE: Dict[str, tuple] = {
    "ActivityTaskScheduled": ("open", "activities", None),
    "ActivityTaskCompleted": ("close", "activities", "scheduled_event_id"),
    "ActivityTaskFailed": ("close", "activities", "scheduled_event_id"),
    "ActivityTaskTimedOut": ("close", "activities", "scheduled_event_id"),
    "ActivityTaskCanceled": ("close", "activities", "scheduled_event_id"),
    "TimerStarted": ("open", "timers", "timer_id"),
    "TimerFired": ("close", "timers", "timer_id"),
    "TimerCanceled": ("close", "timers", "timer_id"),
    "StartChildWorkflowExecutionInitiated": ("open", "children", None),
    "StartChildWorkflowExecutionFailed":
        ("close", "children", "initiated_event_id"),
    "ChildWorkflowExecutionCompleted":
        ("close", "children", "initiated_event_id"),
    "ChildWorkflowExecutionFailed":
        ("close", "children", "initiated_event_id"),
    "ChildWorkflowExecutionCanceled":
        ("close", "children", "initiated_event_id"),
    "ChildWorkflowExecutionTimedOut":
        ("close", "children", "initiated_event_id"),
    "ChildWorkflowExecutionTerminated":
        ("close", "children", "initiated_event_id"),
    "SignalExternalWorkflowExecutionInitiated": ("open", "signals", None),
    "SignalExternalWorkflowExecutionFailed":
        ("close", "signals", "initiated_event_id"),
    "ExternalWorkflowExecutionSignaled":
        ("close", "signals", "initiated_event_id"),
    "RequestCancelExternalWorkflowExecutionInitiated":
        ("open", "cancels", None),
    "RequestCancelExternalWorkflowExecutionFailed":
        ("close", "cancels", "initiated_event_id"),
    "ExternalWorkflowExecutionCancelRequested":
        ("close", "cancels", "initiated_event_id"),
    # the entry must be pending, and stays
    "ActivityTaskStarted": ("touch", "activities", "scheduled_event_id"),
    "ChildWorkflowExecutionStarted":
        ("touch", "children", "initiated_event_id"),
    # nothing the payload holds
    "ActivityTaskCancelRequested": ("nothing",),
    "RequestCancelActivityTaskFailed": ("nothing",),
    "CancelTimerFailed": ("nothing",),
    "MarkerRecorded": ("nothing",),
    "UpsertWorkflowSearchAttributes": ("nothing",),
    # the workflow itself
    "WorkflowExecutionStarted": ("started",),
    "WorkflowExecutionSignaled": ("signaled",),
    "WorkflowExecutionCancelRequested": ("cancel-requested",),
    "WorkflowExecutionCompleted": ("closed",),
    "WorkflowExecutionFailed": ("closed",),
    "WorkflowExecutionTimedOut": ("closed",),
    "WorkflowExecutionCanceled": ("closed",),
    "WorkflowExecutionTerminated": ("closed",),
    "WorkflowExecutionContinuedAsNew": ("closed",),
    # the decision task
    "DecisionTaskScheduled": ("decision-scheduled",),
    "DecisionTaskStarted": ("decision-started",),
    "DecisionTaskCompleted": ("decision-completed",),
    "DecisionTaskTimedOut": ("decision-timed-out",),
    "DecisionTaskFailed": ("decision-failed",),
}


def new_state() -> Dict[str, Any]:
    return {
        "cancel_requested": 0, "state": CREATED, "last_first_event_id": 1,
        "next_event_id": 1, "last_processed_event": EMPTY_EVENT_ID,
        "signal_count": 0,
        # the decision task: (attempt, schedule id, started id, version)
        "decision": (0, EMPTY_EVENT_ID, EMPTY_EVENT_ID, EMPTY_VERSION),
        "version_items": [],       # [[event id, version], ...]
        "timers": {}, "activities": {}, "children": {}, "signals": {},
        "cancels": {},
    }


def _no_decision(attempt: int) -> tuple:
    return (attempt, EMPTY_EVENT_ID, EMPTY_EVENT_ID, EMPTY_VERSION)


def _transient_decision(st: Dict[str, Any], version: int) -> None:
    """After a failed or timed-out decision that raised the attempt, the
    next decision exists without an event of its own; upstream gives it the
    state's next event id, which a replay moves on only at the end of a
    transaction (mutable_state_decision_task_manager.go:168-197)."""
    attempt, schedule_id, _started, _version = st["decision"]
    if schedule_id == EMPTY_EVENT_ID and attempt != 0:
        st["decision"] = (attempt, st["next_event_id"], EMPTY_EVENT_ID,
                          version)


def apply_event(st: Dict[str, Any], event: Event) -> None:
    event_id, kind, version, attrs = event
    if kind not in TABLE:
        raise LookupError(f"event type {kind!r} is not in the table")
    # the current branch's version history follows every event
    items = st["version_items"]
    if items and items[-1][1] == version:
        items[-1][0] = event_id
    elif items and (version < items[-1][1] or event_id <= items[-1][0]):
        raise ValueError(f"event {event_id} v{version} behind {items[-1]}")
    else:
        items.append([event_id, version])

    what = TABLE[kind]
    action = what[0]
    if action == "open":
        _a, table, key_attr = what
        st[table][attrs[key_attr] if key_attr else event_id] = event_id
    elif action == "close":
        del st[what[1]][attrs[what[2]]]
    elif action == "touch":
        st[what[1]][attrs[what[2]]]
    elif action == "nothing":
        pass
    elif action == "started":
        st["state"] = CREATED
        st["last_processed_event"] = EMPTY_EVENT_ID
        st["decision"] = _no_decision(st["decision"][0])
    elif action == "signaled":
        st["signal_count"] += 1
    elif action == "cancel-requested":
        st["cancel_requested"] = 1
    elif action == "closed":
        st["state"] = COMPLETED
    elif action == "decision-scheduled":
        if st["state"] == COMPLETED:
            raise ValueError(f"decision {event_id} scheduled after the close")
        st["state"] = RUNNING
        st["decision"] = (int(attrs.get("attempt") or 0), event_id,
                          EMPTY_EVENT_ID, version)
    elif action == "decision-started":
        if st["decision"][1] != attrs["scheduled_event_id"]:
            raise KeyError(f"decision {attrs['scheduled_event_id']} is not "
                           f"the one scheduled: {st['decision']}")
        # a replay forgets the attempt once the decision has started
        st["decision"] = (0, st["decision"][1], event_id, version)
    elif action == "decision-completed":
        st["decision"] = _no_decision(0)
        st["last_processed_event"] = attrs["started_event_id"]
    elif action == "decision-timed-out":
        # one that never reached a worker (schedule-to-start) is scheduled
        # anew as a real event; any other raises the attempt
        waited = int(attrs.get("timeout_type") or 0) == SCHEDULE_TO_START
        st["decision"] = _no_decision(0 if waited else st["decision"][0] + 1)
        _transient_decision(st, version)
    elif action == "decision-failed":
        st["decision"] = _no_decision(st["decision"][0] + 1)
        _transient_decision(st, version)
    else:  # pragma: no cover - the table names no other action
        raise AssertionError(action)


def replay(history: Sequence[Sequence[Event]]) -> Dict[str, Any]:
    """The state after every transaction of `history`, applied in order."""
    st = new_state()
    for batch in history:
        if not batch:
            raise ValueError("a transaction with no event")
        for event in batch:
            apply_event(st, event)
        st["last_first_event_id"] = batch[0][0]
        st["next_event_id"] = batch[-1][0] + 1
    return st


def payload_row(st: Dict[str, Any]) -> List[int]:
    attempt, schedule_id, started_id, version = st["decision"]
    row = [st["cancel_requested"], st["state"], st["last_first_event_id"],
           st["next_event_id"], st["last_processed_event"],
           st["signal_count"], attempt, schedule_id, started_id, version, 0]

    def put(values: List[int], capacity: int, count: int) -> None:
        if len(values) > capacity:
            raise OverflowError(f"{len(values)} values for {capacity} slots")
        row.append(count)
        row.extend(values)
        row.extend([PAD] * (capacity - len(values)))

    items = st["version_items"]
    put([x for item in items for x in item], 2 * VERSION_ITEMS, len(items))
    for table, capacity in PENDING:
        ids = sorted(st[table].values())
        put(ids, capacity, len(ids))
    assert len(row) == ROW_WIDTH
    return row


def crc32(row: Iterable[int]) -> int:
    row = list(row)
    return zlib.crc32(struct.pack(f"<{len(row)}q", *row)) & 0xFFFFFFFF


def plain(batches: Iterable[Any]) -> List[List[Event]]:
    """History objects (anything with `.events`, each with `.id`,
    `.event_type.name`, `.version`, `.attrs`) as plain data."""
    return [[(int(e.id), e.event_type.name, int(e.version), dict(e.attrs))
             for e in batch.events] for batch in batches]


def crc_of_history(history: Sequence[Sequence[Event]], control: str = "") \
        -> int:
    """The reference's answer for one history. `control` names a guarantee
    of the configuration to break: `drop-last-batch` leaves the history's
    last transaction unreplayed, the stale state that a replay which loses
    its tail, or a twin that lags its store, would hand back."""
    if control == "drop-last-batch":
        history = history[:-1]
    elif control:
        raise ValueError(f"unknown control {control!r}")
    return crc32(payload_row(replay(history)))
