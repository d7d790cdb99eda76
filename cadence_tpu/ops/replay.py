"""The batched replay kernel: scan the event axis, one lockstep step per event.

This is the TPU reframing of the reference's replay call stack
(historyEngine.ReplicateEventsV2 → stateBuilder.ApplyEvents →
Replicate*Event; see SURVEY.md §3.5): instead of one Go goroutine replaying
one workflow's events in a loop, a single jitted `lax.scan` applies event i
of every workflow's (padded) history to all W workflows at once. Sequence
axis = scan (state transitions are inherently sequential per workflow);
workflow axis = vectorization + sharding (parallel/mesh.py).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.checksum import DEFAULT_LAYOUT, PayloadLayout, crc32_of_rows
from ..core.events import HistoryBatch
from .encode import encode_corpus
from .payload import payload_rows
from .state import ReplayState, init_state
from .transitions import step


def _scan_body(s: ReplayState, ev: jnp.ndarray) -> Tuple[ReplayState, None]:
    return step(s, ev), None


@partial(jax.jit, static_argnames=("layout", "max_transfer", "max_timer",
                                   "retention_days"))
def replay_events_with_tasks(events: jnp.ndarray,
                             layout: PayloadLayout = DEFAULT_LAYOUT,
                             max_transfer: int = 128,
                             max_timer: int = 128,
                             retention_days: int = 1):
    """Replay with task generation: returns (final state, TaskLog).

    The task-emitting variant of replay_events — the full stateBuilder
    analog (state also feeds the transfer/timer queues, SURVEY.md §3.5).
    """
    from .taskgen import init_task_log, step_tasks

    W = events.shape[0]
    s0 = init_state(W, layout)
    log0 = init_task_log(W, max_transfer, max_timer)

    def body(carry, ev):
        s, log = carry
        s_new = step(s, ev)
        s_new, log = step_tasks(s_new, ev, log, retention_days)
        return (s_new, log), None

    (s, log), _ = jax.lax.scan(body, (s0, log0), jnp.swapaxes(events, 0, 1))
    return s, log


@partial(jax.jit, static_argnames=("layout",))
def replay_events(events: jnp.ndarray,
                  layout: PayloadLayout = DEFAULT_LAYOUT) -> ReplayState:
    """Replay packed events [W, E, L] from a fresh state; returns final state."""
    s0 = init_state(events.shape[0], layout)
    # scan over the event axis: xs must be [E, W, L]
    s, _ = jax.lax.scan(_scan_body, s0, jnp.swapaxes(events, 0, 1))
    return s


@partial(jax.jit, static_argnames=("layout",))
def replay_to_payload(events: jnp.ndarray,
                      layout: PayloadLayout = DEFAULT_LAYOUT
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Replay and reduce to (canonical payload rows [W, width], error [W])."""
    s = replay_events(events, layout)
    return payload_rows(s, layout), s.error


@partial(jax.jit, static_argnames=("layout",))
def replay_to_payload_branch(events: jnp.ndarray,
                             layout: PayloadLayout = DEFAULT_LAYOUT
                             ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                        jnp.ndarray]:
    """replay_to_payload plus the device-chosen current branch: (rows
    [W, width], error [W], current_branch [W]) — the dense serving
    executor's chunk kernel (engine/executor.replay_corpus_mesh)."""
    s = replay_events(events, layout)
    return payload_rows(s, layout), s.error, s.current_branch


@partial(jax.jit, static_argnames=("profile", "layout"))
def replay_wirec(slab: jnp.ndarray, bases: jnp.ndarray,
                 n_events: jnp.ndarray, profile,
                 layout: PayloadLayout = DEFAULT_LAYOUT) -> ReplayState:
    """Replay a wirec-compressed corpus ([W, E, B] uint8 slab +
    per-workflow bases/counts, ops/wirec.py): each scan step decodes ONE
    event column in registers — delta lanes ride the scan carry, so the
    dense int64 tensor never materializes in HBM and only the compressed
    bytes ever cross the host link."""
    from .wirec import decode_step, delta_base_columns

    W, E, _ = slab.shape
    s0 = init_state(W, layout)
    cols = delta_base_columns(profile)
    prev0 = (bases[:, list(cols)] if cols
             else jnp.zeros((W, 0), dtype=jnp.int64))

    def body(carry, xs):
        s, prev = carry
        sl, e_idx = xs
        with jax.named_scope("wirec-decode"):
            ev, prev = decode_step(sl, prev, bases, n_events, e_idx,
                                   profile)
        return (step(s, ev), prev), None

    (s, _), _ = jax.lax.scan(
        body, (s0, prev0),
        (jnp.swapaxes(slab, 0, 1), jnp.arange(E, dtype=n_events.dtype)))
    return s


@partial(jax.jit, static_argnames=("profile", "layout"))
def replay_wirec_to_crc(slab: jnp.ndarray, bases: jnp.ndarray,
                        n_events: jnp.ndarray, profile,
                        layout: PayloadLayout = DEFAULT_LAYOUT
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """wirec replay reduced to (crc32 [W] uint32, error [W]): the
    minimal-transfer product path — ~10-18 compressed bytes/event up,
    4 bytes/workflow down."""
    from .crc import crc32_rows

    s = replay_wirec(slab, bases, n_events, profile, layout)
    return crc32_rows(payload_rows(s, layout)), s.error


# ---------------------------------------------------------------------------
# Incremental (from-state) replay: the O(new-events) append kernels.
#
# The existing kernels all start from init_state — O(history) per call.
# These take a CARRIED initial state instead (the HBM-resident
# per-workflow states engine/resident.py pins between calls), so an
# append-transaction replays only the new batches: the device analogue
# of the reference applying just the new events to the execution cache's
# warm mutable state (historyEngine + execution/cache.go) instead of
# rebuilding from event 0.
# ---------------------------------------------------------------------------


@jax.jit
def replay_from_state(events: jnp.ndarray, s0: ReplayState) -> ReplayState:
    """Replay packed suffix events [W, E, L] against carried state `s0`
    (whose shapes imply the layout — base or ladder-widened); returns the
    final state. With s0 = init_state this is exactly replay_events."""
    s, _ = jax.lax.scan(_scan_body, s0, jnp.swapaxes(events, 0, 1))
    return s


@partial(jax.jit, static_argnames=("out_layout",))
def replay_from_state_to_payload(events: jnp.ndarray, s0: ReplayState,
                                 out_layout: PayloadLayout = DEFAULT_LAYOUT):
    """From-state replay reduced to the serving shape: (final state,
    payload rows at `out_layout` width, error [W], narrow_overflow [W]).
    The state may be ladder-widened; the payload always projects to the
    BASE width the oracle and stored checksums use — same contract as
    replay_escalated."""
    from .payload import payload_rows_narrow

    s = replay_from_state(events, s0)
    rows, ovf = payload_rows_narrow(s, out_layout)
    return s, rows, s.error, ovf


@partial(jax.jit, static_argnames=("out_layout",))
def replay_from_state_to_crc(events: jnp.ndarray, s0: ReplayState,
                             out_layout: PayloadLayout = DEFAULT_LAYOUT
                             ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                        jnp.ndarray]:
    """From-state replay reduced to (crc32 [W] uint32, error [W],
    narrow_overflow [W]) — the minimal-readback append transaction:
    suffix lanes up, 4 bytes/workflow down."""
    from .crc import crc32_rows
    from .payload import payload_rows_narrow

    s = replay_from_state(events, s0)
    rows, ovf = payload_rows_narrow(s, out_layout)
    return crc32_rows(rows), s.error, ovf


@partial(jax.jit, static_argnames=("profile",))
def replay_wirec_from_state(slab: jnp.ndarray, bases: jnp.ndarray,
                            n_events: jnp.ndarray, profile,
                            s0: ReplayState) -> ReplayState:
    """From-state replay of a wirec-compressed SUFFIX corpus: the suffix
    packs as its own corpus (bases are its first-row values), so decode
    is self-contained and only the appended batches' compressed bytes
    ever cross the link."""
    from .wirec import decode_step, delta_base_columns

    W, E, _ = slab.shape
    cols = delta_base_columns(profile)
    prev0 = (bases[:, list(cols)] if cols
             else jnp.zeros((W, 0), dtype=jnp.int64))

    def body(carry, xs):
        s, prev = carry
        sl, e_idx = xs
        with jax.named_scope("wirec-decode"):
            ev, prev = decode_step(sl, prev, bases, n_events, e_idx,
                                   profile)
        return (step(s, ev), prev), None

    (s, _), _ = jax.lax.scan(
        body, (s0, prev0),
        (jnp.swapaxes(slab, 0, 1), jnp.arange(E, dtype=n_events.dtype)))
    return s


@partial(jax.jit, static_argnames=("profile", "out_layout"))
def replay_wirec_from_state_to_payload(slab: jnp.ndarray,
                                       bases: jnp.ndarray,
                                       n_events: jnp.ndarray, profile,
                                       s0: ReplayState,
                                       out_layout: PayloadLayout
                                       = DEFAULT_LAYOUT):
    """wirec from-state replay reduced to the serving shape: (final
    state, payload rows at `out_layout` width, error [W],
    narrow_overflow [W]) — the compressed-transfer twin of
    replay_from_state_to_payload, so the resident append path ships
    ~10-18 B/event of suffix instead of 144 dense bytes."""
    from .payload import payload_rows_narrow

    s = replay_wirec_from_state(slab, bases, n_events, profile, s0)
    rows, ovf = payload_rows_narrow(s, out_layout)
    return s, rows, s.error, ovf


@partial(jax.jit, static_argnames=("profile", "out_layout"))
def replay_wirec_from_state_to_crc(slab: jnp.ndarray, bases: jnp.ndarray,
                                   n_events: jnp.ndarray, profile,
                                   s0: ReplayState,
                                   out_layout: PayloadLayout = DEFAULT_LAYOUT
                                   ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                              jnp.ndarray]:
    """wirec from-state replay reduced to (crc32 [W] uint32, error [W],
    narrow_overflow [W])."""
    from .crc import crc32_rows
    from .payload import payload_rows_narrow

    s = replay_wirec_from_state(slab, bases, n_events, profile, s0)
    rows, ovf = payload_rows_narrow(s, out_layout)
    return crc32_rows(rows), s.error, ovf


@partial(jax.jit, static_argnames=("layout", "out_layout"))
def replay_escalated(events: jnp.ndarray, layout: PayloadLayout,
                     out_layout: PayloadLayout = DEFAULT_LAYOUT
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                jnp.ndarray]:
    """One escalation rung: re-replay a flagged sub-corpus [F, E, L] at a
    WIDENED capacity `layout` (engine/ladder.py doubles K per rung) and
    project the canonical payload back down to `out_layout` — the base
    width the oracle and stored checksums use. Returns (rows
    [F, out_width], error [F], narrow_overflow [F], current_branch [F]);
    a row is resolved when error == 0 and narrow_overflow is unset."""
    from .payload import payload_rows_narrow

    with jax.named_scope("ladder-rung"):
        s = replay_events(events, layout)
        rows, ovf = payload_rows_narrow(s, out_layout)
    return rows, s.error, ovf, s.current_branch


@partial(jax.jit, static_argnames=("layout", "out_layout"))
def replay_escalated_state(events: jnp.ndarray, layout: PayloadLayout,
                           out_layout: PayloadLayout = DEFAULT_LAYOUT):
    """Ladder rung variant that also returns the full widened ReplayState:
    the rebuild path (engine/rebuild.py) hydrates pending tables straight
    out of the widened state's occupied slots."""
    from .payload import payload_rows_narrow

    with jax.named_scope("ladder-rung"):
        s = replay_events(events, layout)
        rows, ovf = payload_rows_narrow(s, out_layout)
    return s, rows, s.error, ovf


@partial(jax.jit, static_argnames=("profile", "layout", "out_layout"))
def replay_wirec_escalated_crc(slab: jnp.ndarray, bases: jnp.ndarray,
                               n_events: jnp.ndarray, profile,
                               layout: PayloadLayout,
                               out_layout: PayloadLayout = DEFAULT_LAYOUT
                               ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray]:
    """Escalation rung over a wirec-compressed flagged sub-corpus: decode
    + widened replay + base-width payload + CRC32 all on device — the
    bulk-bench fallback leg's configuration (4 bytes/flagged-row back).
    Returns (crc32 [F] uint32, error [F], narrow_overflow [F])."""
    from .crc import crc32_rows
    from .payload import payload_rows_narrow

    with jax.named_scope("ladder-rung"):
        s = replay_wirec(slab, bases, n_events, profile, layout)
        rows, ovf = payload_rows_narrow(s, out_layout)
        return crc32_rows(rows), s.error, ovf


@jax.jit
def verify_rows(rows: jnp.ndarray, expected_rows: jnp.ndarray,
                branch: jnp.ndarray, expected_branch: jnp.ndarray
                ) -> jnp.ndarray:
    """Device-side verify_all compare: payload rows and the device-chosen
    current branch against the expected (live mutable-state) values, ON
    DEVICE — the host reads back one mismatch bit per workflow instead of
    the full [W, width] payload tensor. A set bit means row divergence OR
    branch-arbitration disagreement (verify_all treats both as
    divergent, so the OR loses nothing)."""
    row_mismatch = (rows != expected_rows).any(axis=1)
    return row_mismatch | (branch != expected_branch.astype(branch.dtype))


def replay_corpus(histories: Sequence[Sequence[HistoryBatch]],
                  layout: PayloadLayout = DEFAULT_LAYOUT,
                  max_events: int = 0,
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host helper: encode histories, replay on the default backend, and
    return (payload_rows, crc32s, errors) as numpy arrays. Legs land in
    the default registry's SCOPE_TPU_REPLAY histograms (utils/profiler)."""
    from ..utils import metrics as m
    from ..utils.profiler import ReplayProfiler

    prof = ReplayProfiler()
    with prof.leg(m.M_PROFILE_PACK):
        events = encode_corpus(histories, max_events)
    with prof.leg(m.M_PROFILE_H2D):
        device_events = jax.device_put(jnp.asarray(events))
        prof.h2d(events.nbytes)
    with prof.leg(m.M_PROFILE_KERNEL):
        rows, errors = replay_to_payload(device_events, layout)
        jax.block_until_ready(rows)
    with prof.leg(m.M_PROFILE_READBACK):
        rows_np = np.asarray(rows)
        errors_np = np.asarray(errors)
    return rows_np, crc32_of_rows(rows_np), errors_np
