"""The capacity-escalation ladder on the serialized feeder's normal path
(ISSUE 27): `feed_serialized_wirec` resolves capacity-flagged rows itself.

Covers: oracle-identical CRCs and error 0 for EVERY row of a corpus with
fan-out histories in the first, a middle and the padded tail chunk, with
either encoder, with and without a mesh; flagged rows copied out of a ring
slot before anything packs over it; rung 2 and a row no rung resolves; a
warm call compiling nothing; a corpus that fits making no ladder call; the
one wirec ladder path (`escalate_wirec` = `finish([submit_wirec])`).
"""
import time

import numpy as np
import pytest

import jax

from cadence_tpu.core.checksum import crc32_of_row
from cadence_tpu.core.codec import serialize_corpus
from cadence_tpu.engine import ladder as ladder_mod
from cadence_tpu.engine.ladder import EscalationLadder
from cadence_tpu.gen.corpus import SUITES, generate_corpus, generate_history
from cadence_tpu.native import feeder
from cadence_tpu.native import wirec as nwirec
from cadence_tpu.ops.encode import encode_corpus, history_length
from cadence_tpu.ops.state import CAPACITY_ERRORS
from cadence_tpu.parallel.mesh import make_mesh
from cadence_tpu.utils import metrics as m

from .test_ladder import _flood_history, _oracle_row

#: 200 rows in chunks of 64 are three full chunks and a tail of 8 padded
#: to 64; a fan-out (24 pending activities, 1.5 x K) sits in the first
#: chunk, in both middle ones and in the tail
FAN_OUT_AT = (3, 70, 130, 199)


def _corpus(n=200, fan_out_at=FAN_OUT_AT, target_events=40):
    hists = [generate_history("basic", 27, i, target_events)
             for i in range(n)]
    for i in fan_out_at:
        hists[i] = _flood_history(16, wf=f"fan-out-{i}")
    return hists


def _oracle_crcs(hists):
    return np.asarray([crc32_of_row(_oracle_row(h)) for h in hists],
                      dtype=np.uint32)


def _feed(hists, **kw):
    return feeder.feed_serialized_wirec(
        serialize_corpus(hists), max(history_length(h) for h in hists), **kw)


@pytest.fixture(scope="module")
def corpus():
    hists = _corpus()
    return hists, _oracle_crcs(hists)


@pytest.mark.parametrize("mesh_devices", [0, 2, 4])
@pytest.mark.parametrize("encoder", ["native", "python"])
def test_every_row_oracle_identical_and_unflagged(corpus, encoder,
                                                  mesh_devices, monkeypatch):
    if encoder == "python":
        monkeypatch.setenv(nwirec.NATIVE_WIREC_ENV, "0")
    elif not nwirec.native_wirec_available():
        pytest.skip("no native encoder here")
    mesh = make_mesh(jax.devices()[:mesh_devices]) if mesh_devices else None
    hists, want = corpus
    crc, err, rep = _feed(hists, chunk_workflows=64, mesh=mesh)
    assert rep.native_wirec is (encoder == "native")
    assert (crc.astype(np.uint32) == want).all()
    assert not err.any()
    assert list(rep.ladder_indices) == list(FAN_OUT_AT)
    assert (rep.ladder_resolved, rep.ladder_residual) == (4, 0)
    # one rung-1 launch for each of the four chunks, a flagged row in
    # each, padded to the ladder's floor of 8 lanes
    assert (rep.ladder_rows, rep.ladder_lanes) == (4, 32)
    assert rep.ladder_events == sum(history_length(hists[i])
                                    for i in FAN_OUT_AT)
    assert rep.ladder_wire_bytes > 0 and rep.ladder_s > 0
    # a re-replayed event is not a second event
    assert rep.events == sum(history_length(h) for h in hists)


@pytest.mark.parametrize("chunk_workflows", [24, 256])
def test_chunk_widths_from_many_chunks_to_one_padded_chunk(corpus,
                                                           chunk_workflows):
    hists, want = corpus
    crc, err, rep = _feed(hists, chunk_workflows=chunk_workflows)
    assert rep.chunks == -(-len(hists) // chunk_workflows)
    assert (crc.astype(np.uint32) == want).all() and not err.any()
    assert sorted(rep.ladder_indices) == list(FAN_OUT_AT)
    assert rep.ladder_residual == 0


def test_flagged_rows_leave_their_ring_slot_before_it_is_packed_over(
        monkeypatch):
    """Eight chunks through a ring of two slots, a fan-out in each chunk,
    the consumer held back so that the packers run ahead of it, and every
    slot poisoned as its next pack begins: what the ladder replays was
    copied out before, and shares no memory with a slot."""
    if not nwirec.native_wirec_available():
        pytest.skip("the Python encoder packs into fresh arrays")
    hists = _corpus(n=128, fan_out_at=tuple(range(5, 128, 16)))
    want = _oracle_crcs(hists)
    slots, subs = [], []

    class Slot(nwirec.WirecBuffers):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            slots.append(self)

    real_pack = nwirec.pack_serialized_wirec

    def poison_then_pack(chunk, max_events, **kw):
        slot = kw["out"]
        if slot.slab is not None:
            slot.slab[:] = 0xFF
            slot.bases[:] = -1
            slot.n_events[:] = 0
        return real_pack(chunk, max_events, **kw)

    real_submit = EscalationLadder.submit

    def slow_submit(self, sub):
        subs.append(sub)
        time.sleep(0.05)
        return real_submit(self, sub)

    monkeypatch.setattr(nwirec, "WirecBuffers", Slot)
    monkeypatch.setattr(nwirec, "pack_serialized_wirec", poison_then_pack)
    monkeypatch.setattr(EscalationLadder, "submit", slow_submit)
    crc, err, rep = _feed(hists, chunk_workflows=16, depth=2)
    assert rep.chunks == 8 and len(slots) == 2 and len(subs) == 8
    # a ring slot is the wirec triple and nothing else: the native pack
    # keeps no [W, E, L] lane tensor
    assert all(not hasattr(slot, "lanes") for slot in slots)
    for sub in subs:
        for slot in slots:
            assert not np.shares_memory(sub.slab, slot.slab)
            assert not np.shares_memory(sub.bases, slot.bases)
            assert not np.shares_memory(sub.n_events, slot.n_events)
    assert (crc.astype(np.uint32) == want).all() and not err.any()
    assert rep.ladder_resolved == 8 and rep.ladder_residual == 0


@pytest.fixture(scope="module")
def deep_corpus():
    """40 pending activities need rung 2 (2K = 32 < 40 <= 4K = 64); 80
    outgrow the top rung."""
    hists = [generate_history("basic", 27, i, 40) for i in range(14)]
    hists[2] = _flood_history(32, wf="needs-rung-2")
    hists[9] = _flood_history(72, wf="outgrows-every-rung")
    return hists, _oracle_crcs(hists)


def _base_pass(hists):
    """The base kernel alone over the same histories: what a flagged row
    reads before any rung."""
    from cadence_tpu.native.wirec import pack_wirec_auto
    from cadence_tpu.ops.replay import replay_wirec_to_crc

    c = pack_wirec_auto(encode_corpus(hists))
    crc, err = replay_wirec_to_crc(c.slab, c.bases, c.n_events, c.profile)
    return np.asarray(crc).astype(np.uint32), np.asarray(err)


@pytest.mark.parametrize("rungs,resolved", [(2, {2}), (1, set())])
def test_rung_2_and_a_row_no_rung_resolves(deep_corpus, rungs, resolved,
                                           monkeypatch):
    monkeypatch.setenv(ladder_mod.RUNGS_ENV, str(rungs))
    hists, want = deep_corpus
    base_crc, base_err = _base_pass(hists)
    assert set(np.nonzero(base_err)[0]) == {2, 9}
    crc, err, rep = _feed(hists, chunk_workflows=16)
    crc = crc.astype(np.uint32)
    assert sorted(rep.ladder_indices) == [2, 9]
    assert rep.ladder_resolved == len(resolved)
    assert rep.ladder_residual == 2 - len(resolved)
    # both rows at every rung that ran
    assert rep.ladder_rows == 2 * rungs
    for i in range(len(hists)):
        if i in (2, 9) and i not in resolved:
            # kept flagged, counted, CRC untouched: never silent, and no
            # Python oracle inside the feeder
            assert err[i] in CAPACITY_ERRORS and err[i] == base_err[i]
            assert crc[i] == base_crc[i] and crc[i] != want[i]
        else:
            assert err[i] == 0 and crc[i] == want[i]


def test_a_second_call_compiles_nothing(corpus):
    from cadence_tpu.ops.replay import replay_wirec_escalated_crc

    hists, want = corpus
    reg = m.MetricsRegistry()
    _feed(hists, chunk_workflows=64, registry=reg)
    compiles = reg.counter(m.SCOPE_TPU_FALLBACK, m.M_LADDER_COMPILES)
    hits = reg.counter(m.SCOPE_TPU_FALLBACK, m.M_LADDER_CACHE_HITS)
    programs = replay_wirec_escalated_crc._cache_size()
    crc, err, rep = _feed(hists, chunk_workflows=64, registry=reg)
    assert (crc.astype(np.uint32) == want).all() and not err.any()
    assert reg.counter(m.SCOPE_TPU_FALLBACK, m.M_LADDER_COMPILES) == compiles
    assert reg.counter(m.SCOPE_TPU_FALLBACK, m.M_LADDER_CACHE_HITS) \
        == hits + 4
    assert replay_wirec_escalated_crc._cache_size() == programs
    # the ladder's wait is the profiler's `fallback` leg, its counters stay
    assert reg.histogram(m.SCOPE_TPU_FALLBACK,
                         m.M_PROFILE_FALLBACK).count == 2
    assert reg.counter(m.SCOPE_TPU_FALLBACK, m.M_LADDER_RESOLVED) == 8
    assert reg.counter(m.SCOPE_TPU_FALLBACK, m.ladder_rung_rows(1)) == 8


def test_a_corpus_that_fits_makes_no_ladder_call(monkeypatch):
    def never(*_a, **_kw):
        raise AssertionError("the ladder ran for a corpus that fits")

    for name in ("submit", "finish", "_launch"):
        monkeypatch.setattr(EscalationLadder, name, never)
    monkeypatch.setattr(ladder_mod, "gather_corpus", never)
    hists = [h for suite in SUITES
             for h in generate_corpus(suite, 12, seed=27, target_events=40)]
    crc, err, rep = _feed(hists, chunk_workflows=16)
    assert (crc.astype(np.uint32) == _oracle_crcs(hists)).all()
    assert not err.any()
    assert (rep.ladder_rows, rep.ladder_lanes, rep.ladder_events,
            rep.ladder_wire_bytes, rep.ladder_resolved,
            rep.ladder_residual, len(rep.ladder_indices)) == (0,) * 7


def test_escalate_wirec_is_finish_of_submit_wirec(corpus):
    from cadence_tpu.native.wirec import pack_wirec_auto

    hists, want = corpus
    packed = pack_wirec_auto(encode_corpus(hists))
    flagged = np.asarray(FAN_OUT_AT)
    ladder = EscalationLadder()
    crc, resolved, err = ladder.escalate_wirec(packed, flagged)
    outcome, = ladder.finish([ladder.submit_wirec(packed, flagged)])
    assert (crc == outcome.rows).all() and crc.dtype == np.uint32
    assert (resolved == outcome.resolved).all() and resolved.all()
    assert (err == outcome.errors).all() and not err.any()
    assert (crc == want[flagged]).all()
    assert [r["rung"] for r in outcome.rungs] == [1]
    assert outcome.rungs[0]["rows"] == 4 and outcome.rungs[0]["lanes"] == 8
