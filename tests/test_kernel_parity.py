"""Differential tests: the JAX replay kernel must produce byte-identical
canonical checksum payloads to the Python oracle on every corpus suite —
the framework's analog of the north-star "zero mutable-state divergence vs
the Go stateBuilder" contract."""
import numpy as np
import pytest

from cadence_tpu.core.checksum import payload_row
from cadence_tpu.gen.corpus import SUITES, generate_corpus
from cadence_tpu.oracle.state_builder import StateBuilder
from cadence_tpu.ops.replay import replay_corpus


def oracle_rows(histories):
    return np.stack([
        payload_row(StateBuilder().replay_history(h)) for h in histories
    ])


@pytest.mark.parametrize("suite", SUITES)
def test_suite_parity(suite):
    histories = generate_corpus(suite, num_workflows=16, seed=11,
                                target_events=100)
    kernel, crcs, errors = replay_corpus(histories)
    assert (errors == 0).all(), f"kernel flagged errors: {errors}"
    expected = oracle_rows(histories)
    mismatch = np.nonzero((kernel != expected).any(axis=1))[0]
    if mismatch.size:
        w = int(mismatch[0])
        cols = np.nonzero(kernel[w] != expected[w])[0]
        raise AssertionError(
            f"suite={suite} workflow {w} diverges at payload cols {cols}: "
            f"kernel={kernel[w][cols]} oracle={expected[w][cols]}"
        )


def test_mixed_suites_one_batch():
    """Different suites padded into one ragged tensor replay correctly."""
    histories = []
    for suite in SUITES:
        histories.extend(generate_corpus(suite, num_workflows=3, seed=5,
                                         target_events=80))
    kernel, crcs, errors = replay_corpus(histories)
    assert (errors == 0).all()
    expected = oracle_rows(histories)
    assert (kernel == expected).all()
    # CRCs are per-row CRC32 of identical payloads
    from cadence_tpu.core.checksum import crc32_of_rows
    assert (crcs == crc32_of_rows(expected)).all()


def test_error_flag_on_corrupt_history():
    """A corrupted history freezes only that workflow; neighbors unaffected."""
    from cadence_tpu.core.enums import EventType
    histories = generate_corpus("basic", num_workflows=3, seed=2,
                                target_events=60)
    # corrupt workflow 1: point an activity completion at a bogus schedule id
    for b in histories[1]:
        for e in b.events:
            if e.event_type == EventType.ActivityTaskCompleted:
                e.attrs["scheduled_event_id"] = 9999
                break
    kernel, _, errors = replay_corpus(histories)
    assert errors[1] != 0
    assert errors[0] == 0 and errors[2] == 0
    expected0 = payload_row(StateBuilder().replay_history(histories[0]))
    assert (kernel[0] == expected0).all()


def test_ragged_lengths():
    """Histories of very different lengths in one padded batch."""
    histories = [
        generate_corpus("basic", 1, seed=s, target_events=n)[0]
        for s, n in [(1, 20), (2, 100), (3, 50), (4, 200)]
    ]
    kernel, _, errors = replay_corpus(histories)
    assert (errors == 0).all()
    expected = oracle_rows(histories)
    assert (kernel == expected).all()


class TestOverflowFallback:
    """The adversarial overflow suite (SURVEY §7 hard part 3): a planted
    fraction of workflows exceed the device pending tables; the device
    must FLAG exactly those (TABLE_OVERFLOW), replay the rest correctly,
    and the oracle leg must agree on every flagged workflow."""

    def test_device_flags_planted_overflows_and_oracle_covers(self):
        import jax.numpy as jnp
        import numpy as np

        from cadence_tpu.core.checksum import (
            DEFAULT_LAYOUT,
            STICKY_ROW_INDEX,
            crc32_of_row,
            payload_row,
        )
        from cadence_tpu.gen.corpus import generate_corpus
        from cadence_tpu.ops.encode import encode_corpus
        from cadence_tpu.ops.wirec import pack_wirec
        from cadence_tpu.ops.replay import replay_wirec_to_crc
        from cadence_tpu.oracle.state_builder import StateBuilder

        histories = generate_corpus("overflow", num_workflows=256, seed=3,
                                    target_events=100)
        ev = encode_corpus(histories)
        c = pack_wirec(ev)
        crc, errors = replay_wirec_to_crc(
            jnp.asarray(c.slab), jnp.asarray(c.bases),
            jnp.asarray(c.n_events), c.profile, DEFAULT_LAYOUT)
        crc, errors = (np.asarray(crc).astype(np.uint32),
                       np.asarray(errors))
        flagged = set(np.nonzero(errors != 0)[0].tolist())
        assert flagged, "no overflow planted — the suite is vacuous"
        assert len(flagged) < 256 // 4, "overflow fraction far too high"
        for i in range(256):
            ms = StateBuilder().replay_history(histories[i])
            row = payload_row(ms, DEFAULT_LAYOUT)
            row[STICKY_ROW_INDEX] = 0
            expect = np.uint32(crc32_of_row(row))
            if i in flagged:
                # flagged: the ORACLE leg is authoritative (and must
                # replay the over-capacity history fine — it has none)
                assert ms.execution_info.close_status != 0
            else:
                assert crc[i] == expect, f"unflagged workflow {i} diverged"
        # the planted shape is what got flagged: >capacity pending
        # activities at peak
        from cadence_tpu.core.enums import EventType
        for i in list(flagged)[:4]:
            pend = peak = 0
            for b in histories[i]:
                for e in b.events:
                    if e.event_type == EventType.ActivityTaskScheduled:
                        pend += 1
                        peak = max(peak, pend)
                    elif e.event_type == EventType.ActivityTaskCompleted:
                        pend -= 1
            assert peak > DEFAULT_LAYOUT.max_activities


# ---------------------------------------------------------------------------
# The version-history tables' one index: ops/state.pick_branch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("trailing", [(8,), (1,), ()],
                         ids=["W-Kv", "W-1", "W"])
@pytest.mark.parametrize("B", [1, 2, 4, 8])
def test_pick_branch_equals_take_along_axis(B, trailing):
    """The select chain picks the row `np.take_along_axis` picks, for
    every index in [0, B - 1] and every int64 the tables hold (PAD,
    negatives, the EMPTY sentinels), at the item tables' shape [W, B, Kv],
    at [W, B, 1] and at the count table's [W, B]."""
    import jax
    import jax.numpy as jnp

    from cadence_tpu.core.checksum import PAD
    from cadence_tpu.core.enums import EMPTY_EVENT_ID, EMPTY_VERSION
    from cadence_tpu.ops.state import pick_branch

    W = 6 * B
    rng = np.random.default_rng(B * 10 + len(trailing))
    special = np.asarray([int(PAD), EMPTY_VERSION, EMPTY_EVENT_ID, -1, 0,
                          -(1 << 62), (1 << 62) - 1, 1 << 33], np.int64)
    arr = rng.integers(-(1 << 40), 1 << 40, size=(W, B) + trailing,
                       dtype=np.int64)
    mask = rng.random(arr.shape) < 0.5
    arr = np.where(mask, rng.choice(special, size=arr.shape), arr)
    idx = np.arange(W, dtype=np.int32) % B          # every index, 6 times
    rng.shuffle(idx)
    want = np.take_along_axis(
        arr, idx.reshape((W, 1) + (1,) * len(trailing)), axis=1).squeeze(1)
    pick, args = jax.jit(pick_branch), (jnp.asarray(arr), jnp.asarray(idx))
    got = pick(*args)
    assert got.dtype == jnp.int64 and got.shape == (W,) + trailing
    assert (np.asarray(got) == want).all()
    assert " gather(" not in pick.lower(*args).compile().as_text()


def _fork_at_version_bump(history):
    """Segments of one `ndc` history laid over branches 2 and 3 of a
    widened layout: the prefix up to its first failover replays on branch
    2, a stale signal persists VH-only on branch 2 beyond the fork point,
    and the rest of the history arrives on branch 3 with parent 2 — the
    fork-inherit (p != b) at indexes only B = 4 has. None where the
    history never bumps its version."""
    from cadence_tpu.core.enums import EventType
    from cadence_tpu.core.events import HistoryBatch, HistoryEvent

    for k in range(1, len(history)):
        before, first = history[k - 1].events[-1], history[k].events[0]
        if first.version > before.version:
            stale = HistoryBatch(
                domain_id=history[0].domain_id,
                workflow_id=history[0].workflow_id,
                run_id=history[0].run_id,
                events=[HistoryEvent(
                    id=first.id,
                    event_type=EventType.WorkflowExecutionSignaled,
                    version=before.version, timestamp=before.timestamp + 1)])
            return [(history[:k], 2, 2, False), ([stale], 2, 2, True),
                    (history[k:], 3, 2, False)]
    return None


def test_ndc_suite_forked_at_widened_layout_matches_oracle():
    """The `ndc` suite at `widen_layout(DEFAULT_LAYOUT, 2)` (B = 4, the
    ladder's first rung) against the oracle's payload rows and checksums:
    a third of the histories linear on branch 0, the others forked at
    their first failover onto branches 2 → 3, so the pick of `b`, of
    `p != b` and of the current branch all land on rows that B = 2 does
    not have."""
    import jax.numpy as jnp

    from cadence_tpu.core.checksum import DEFAULT_LAYOUT, crc32_of_rows
    from cadence_tpu.ops.crc import crc32_rows
    from cadence_tpu.ops.encode import encode_segment_corpus
    from cadence_tpu.ops.payload import payload_rows_narrow
    from cadence_tpu.ops.replay import replay_events
    from cadence_tpu.ops.state import widen_layout

    histories = generate_corpus("ndc", num_workflows=24, seed=28,
                                target_events=100)
    trees = [_fork_at_version_bump(h) if i % 3 else None
             for i, h in enumerate(histories)]
    forked = np.asarray([t is not None for t in trees])
    assert forked.sum() >= 8 and (~forked).sum() >= 8, forked
    events = encode_segment_corpus(
        [t or [(h, 0, 0, False)] for t, h in zip(trees, histories)])
    wide = widen_layout(DEFAULT_LAYOUT, 2)
    assert wide.max_branches == 4
    state = replay_events(jnp.asarray(events), wide)
    assert (np.asarray(state.error) == 0).all(), np.asarray(state.error)
    assert (np.asarray(state.current_branch)
            == np.where(forked, 3, 0)).all()
    rows, narrow_overflow = payload_rows_narrow(state, DEFAULT_LAYOUT)
    assert not np.asarray(narrow_overflow).any()
    expected = oracle_rows(histories)
    assert (np.asarray(rows) == expected).all()
    assert (np.asarray(crc32_rows(rows)).astype(np.uint32)
            == crc32_of_rows(expected)).all()
    # branch 2 kept the stale suffix: its last item runs one event past
    # the fork point, which branch 3's inherited copy was capped below
    w = int(np.nonzero(forked)[0][0])
    fork_id = trees[w][1][0][0].events[0].id
    ids = np.asarray(state.vh_event_ids)[w]
    n2 = int(np.asarray(state.vh_count)[w, 2])
    assert ids[2, n2 - 1] == fork_id and ids[3, n2 - 1] == fork_id - 1
    assert (ids[3, :n2 - 1] == ids[2, :n2 - 1]).all()
