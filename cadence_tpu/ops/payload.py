"""Device-side canonical checksum payload assembly.

Produces, from the dense ReplayState, exactly the same [W, width] int64
payload matrix as the oracle's core/checksum.payload_row (field order per
reference checksum.go:56-113). Pending-ID lists are sorted on device with
jnp.sort — the PAD sentinel is positive-huge, so unoccupied slots sort to
the tail, matching the oracle's [sorted reals..., PAD...] layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.checksum import DEFAULT_LAYOUT, PAD, PayloadLayout
from ..core.checksum import fnv64 as _fnv64  # noqa: F401 (sticky always empty → 0)
from .state import ReplayState, pick_branch


def _sorted_ids(occ: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    return jnp.sort(jnp.where(occ, ids, jnp.int64(PAD)), axis=1)


def _count(occ: jnp.ndarray) -> jnp.ndarray:
    return occ.sum(axis=1).astype(jnp.int64)


def payload_rows(s: ReplayState, layout: PayloadLayout = DEFAULT_LAYOUT) -> jnp.ndarray:
    """[W, layout.width] int64 canonical payload, comparable elementwise with
    the oracle's payload_row output. One implementation serves both this
    and the escalation ladder's narrowing (the canonical field order must
    never fork): at the state's own layout the projection slices are
    no-ops and XLA dead-code-eliminates the unused overflow mask."""
    rows, _overflow = payload_rows_narrow(s, layout)
    return rows


@jax.named_scope("payload")
def payload_rows_narrow(s: ReplayState, out_layout: PayloadLayout
                        ) -> "tuple[jnp.ndarray, jnp.ndarray]":
    """Project a (possibly widened-K) state's canonical payload down to
    `out_layout`'s width — the escalation ladder's readback (engine/
    ladder.py): a flagged row re-replayed at 2K/4K must still hash to the
    BASE payload the oracle and stored checksums use.

    Returns (rows [W, out_layout.width], overflow [W] bool). Sorted
    pending lists put PAD past the occupied count, and the version-history
    tables are PAD-filled past vh_count, so truncating each block to the
    out capacity is exact whenever the FINAL counts fit. Rows whose final
    counts exceed an out capacity are unrepresentable in the canonical
    payload (the oracle's payload_row raises OverflowError on them too)
    and come back with `overflow` set — widening further never fixes
    those, only oracle arbitration can.

    With out_layout equal to the state's own layout this is elementwise
    identical to payload_rows (tests assert)."""
    W = s.state.shape[0]
    Kv = out_layout.max_version_history_items
    scalars = jnp.stack(
        [
            s.cancel_requested.astype(jnp.int64),
            s.state.astype(jnp.int64),
            s.last_first_event_id,
            s.next_event_id,
            s.last_processed_event,
            s.signal_count,
            s.decision_attempt,
            s.decision_schedule_id,
            s.decision_started_id,
            s.decision_version,
            jnp.zeros((W,), jnp.int64),  # sticky cleared on replay → hash 0
        ],
        axis=1,
    )
    vh_event_ids = pick_branch(s.vh_event_ids, s.current_branch)
    vh_versions = pick_branch(s.vh_versions, s.current_branch)
    vh_count = pick_branch(s.vh_count, s.current_branch)
    overflow = vh_count.astype(jnp.int64) > Kv
    vh_pairs = jnp.stack(
        [vh_event_ids[:, :Kv], vh_versions[:, :Kv]], axis=2
    ).reshape(W, 2 * Kv)

    def narrowed(occ, ids, cap):
        nonlocal overflow
        cnt = _count(occ)
        overflow = overflow | (cnt > cap)
        return cnt[:, None], _sorted_ids(occ, ids)[:, :cap]

    t_cnt, t_ids = narrowed(s.timers.occ, s.timers.started_id,
                            out_layout.max_timers)
    a_cnt, a_ids = narrowed(s.activities.occ, s.activities.schedule_id,
                            out_layout.max_activities)
    c_cnt, c_ids = narrowed(s.children.occ, s.children.initiated_id,
                            out_layout.max_children)
    sg_cnt, sg_ids = narrowed(s.signals.occ, s.signals.initiated_id,
                              out_layout.max_signals)
    rc_cnt, rc_ids = narrowed(s.cancels.occ, s.cancels.initiated_id,
                              out_layout.max_request_cancels)
    rows = jnp.concatenate([
        scalars, vh_count.astype(jnp.int64)[:, None], vh_pairs,
        t_cnt, t_ids, a_cnt, a_ids, c_cnt, c_ids, sg_cnt, sg_ids,
        rc_cnt, rc_ids,
    ], axis=1)
    assert rows.shape[1] == out_layout.width, (rows.shape, out_layout.width)
    return rows, overflow
