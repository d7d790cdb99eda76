"""Native packer parity: C++ decode+pack must be byte-identical to the
Python packer on every suite, and the codec must round-trip; the native
wirec encoder (native/wirec.cc, ISSUE 9) must be byte-identical to
ops/wirec.pack_wirec — corpus bytes, pinned-profile streaming chunks,
ProfileMisfit refit signal, and the PackCache suffix-repack path."""
import numpy as np
import pytest

from cadence_tpu.core.codec import deserialize_history, serialize_history
from cadence_tpu.gen.corpus import SUITES, generate_corpus, generate_history
from cadence_tpu.ops.encode import encode_corpus
from cadence_tpu.native import build as native_build
from cadence_tpu.core.codec import serialize_corpus
from cadence_tpu.native.packing import pack_serialized

native = pytest.mark.skipif(native_build.load() is None,
                            reason="no C++ toolchain")
native_wirec = pytest.mark.skipif(native_build.load_wirec() is None,
                                  reason="no C++ toolchain")


@native
@pytest.mark.parametrize("suite", SUITES)
def test_native_matches_python_packer(suite):
    histories = generate_corpus(suite, num_workflows=6, seed=31,
                                target_events=90)
    expected = encode_corpus(histories)
    got = pack_serialized(serialize_corpus(histories), expected.shape[1])
    mism = np.nonzero(got != expected)
    assert got.shape == expected.shape
    assert (got == expected).all(), (
        f"suite={suite}: first mismatches at {[m[:5] for m in mism]}"
    )


@native
def test_native_rejects_truncated_blob():
    histories = generate_corpus("basic", 2, seed=1, target_events=40)
    blobs = serialize_corpus(histories)
    blobs[1] = blobs[1][:len(blobs[1]) // 2]
    with pytest.raises(ValueError, match="workflow 1"):
        pack_serialized(blobs, max_events=64)


@native
def test_native_rejects_overlong_history():
    histories = generate_corpus("basic", 1, seed=1, target_events=60)
    with pytest.raises(ValueError, match="code 3"):
        pack_serialized(serialize_corpus(histories), max_events=8)


def test_codec_roundtrip():
    """serialize → deserialize preserves replay-relevant attributes: the
    round-tripped history replays to the same checksum payload."""
    from cadence_tpu.core.checksum import payload_row
    from cadence_tpu.oracle.state_builder import StateBuilder

    for suite in SUITES:
        h = generate_history(suite, seed=8, workflow_index=0, target_events=80)
        blob = serialize_history(h)
        h2 = deserialize_history(blob, h[0].domain_id, h[0].workflow_id,
                                 h[0].run_id)
        # request IDs differ (not serialized) but are checksum-irrelevant
        r1 = payload_row(StateBuilder().replay_history(h))
        r2 = payload_row(StateBuilder().replay_history(h2))
        assert (r1 == r2).all(), f"suite {suite} round-trip diverged"


def test_codec_roundtrip_parent_and_retry():
    """Parent linkage and retry policies survive the wire (regression:
    these used to decode to keys nothing read)."""
    from cadence_tpu.core.enums import EventType
    from cadence_tpu.core.events import HistoryBatch, HistoryEvent, RetryPolicy

    retry = RetryPolicy(initial_interval_seconds=2, backoff_coefficient=1.5,
                        maximum_interval_seconds=30, maximum_attempts=4,
                        expiration_interval_seconds=120)
    h = [HistoryBatch(domain_id="d", workflow_id="w", run_id="r", events=[
        HistoryEvent(id=1, event_type=EventType.WorkflowExecutionStarted,
                     timestamp=5, attrs=dict(
                         task_list="tl", workflow_type="wt",
                         execution_start_to_close_timeout_seconds=60,
                         task_start_to_close_timeout_seconds=10,
                         parent_workflow_id="papa", parent_run_id="papa-run",
                         parent_workflow_domain_id="papa-dom",
                         parent_initiated_event_id=7,
                         retry_policy=retry)),
    ])]
    h2 = deserialize_history(serialize_history(h), "d", "w", "r")
    ev = h2[0].events[0]
    assert ev.get("parent_workflow_id") == "papa"
    assert ev.get("parent_run_id") == "papa-run"
    assert ev.get("parent_workflow_domain_id") == "papa-dom"
    assert ev.get("parent_initiated_event_id") == 7
    rp = ev.get("retry_policy")
    assert rp is not None
    assert (rp.initial_interval_seconds, rp.backoff_coefficient,
            rp.maximum_interval_seconds, rp.maximum_attempts,
            rp.expiration_interval_seconds) == (2, 1.5, 30, 4, 120)


@native
def test_fully_loaded_start_event_packs():
    """A child-workflow Started event with retry policy + cron + parent
    linkage carries 20 wire attrs — the packer must accept it (the
    attr-list bound is kMaxAttrCode, not a smaller guess)."""
    from cadence_tpu.core.enums import ContinueAsNewInitiator, EventType
    from cadence_tpu.core.events import HistoryBatch, HistoryEvent, RetryPolicy

    start = HistoryEvent(
        id=1, event_type=EventType.WorkflowExecutionStarted,
        version=0, timestamp=1_700_000_000_000_000_000, task_id=1001,
        attrs=dict(
            execution_start_to_close_timeout_seconds=3600,
            task_start_to_close_timeout_seconds=10,
            first_decision_task_backoff_seconds=5,
            attempt=2,
            expiration_timestamp=1_700_000_900_000_000_000,
            task_list="tl", workflow_type="wt", cron_schedule="* * * * *",
            first_execution_run_id="r0",
            parent_workflow_id="pw", parent_run_id="pr",
            parent_domain_id="pd", parent_initiated_event_id=7,
            retry_policy=RetryPolicy(
                initial_interval_seconds=1, backoff_coefficient=2.0,
                maximum_interval_seconds=60, maximum_attempts=5,
                expiration_interval_seconds=900),
            initiator=int(ContinueAsNewInitiator.RetryPolicy),
        ))
    sched = HistoryEvent(
        id=2, event_type=EventType.DecisionTaskScheduled, version=0,
        timestamp=1_700_000_000_000_001_000, task_id=1002,
        attrs=dict(task_list="tl", start_to_close_timeout_seconds=10,
                   attempt=0))
    hist = [[HistoryBatch(domain_id="d", workflow_id="w", run_id="r",
                          events=[start, sched])]]
    ev = encode_corpus(hist)
    blobs = serialize_corpus(hist)
    got = pack_serialized(blobs, ev.shape[1])
    assert (got == ev).all()
    # and through the fused blobs → lanes → wirec call the feeder packs with
    from cadence_tpu.native.wirec import pack_serialized_wirec
    from cadence_tpu.ops.wirec import decode_wirec

    c, _ = pack_serialized_wirec(blobs, ev.shape[1])
    assert (np.asarray(decode_wirec(c.slab, c.bases, c.n_events,
                                    c.profile)) == ev).all()


def _assert_corpus_equal(a, b, ctx=""):
    assert a.profile == b.profile, f"{ctx}: profile drift"
    assert a.slab.shape == b.slab.shape, ctx
    assert (a.slab == b.slab).all(), f"{ctx}: slab bytes diverge"
    assert (a.bases == b.bases).all(), f"{ctx}: bases diverge"
    assert (a.n_events == b.n_events).all(), f"{ctx}: n_events diverge"


@native_wirec
class TestNativeWirec:
    """Byte-parity contract of the native wirec encoder (ISSUE 9): every
    slab byte, bases column, n_events entry, and the measured PROFILE
    itself must equal ops/wirec.pack_wirec's — profiles are static jit
    arguments, so profile drift would mean different executables (and a
    broken refit contract), not just different bytes."""

    @pytest.mark.parametrize("suite", SUITES)
    @pytest.mark.parametrize("seed", [31, 77])
    def test_byte_parity_fuzz_every_suite(self, suite, seed):
        from cadence_tpu.native.wirec import pack_wirec_native
        from cadence_tpu.ops.wirec import pack_wirec

        ev = encode_corpus(generate_corpus(suite, num_workflows=10,
                                           seed=seed, target_events=70))
        _assert_corpus_equal(pack_wirec(ev), pack_wirec_native(ev),
                             f"{suite}/{seed}")

    def test_measure_profile_matches_python(self):
        """The native plan (kind/width/scale/const per lane) is the exact
        decision procedure of _plan_lane — asserted standalone because a
        profile mismatch poisons every pinned-profile consumer."""
        from cadence_tpu.native.wirec import measure_profile_native
        from cadence_tpu.ops.wirec import pack_wirec

        for suite in SUITES:
            ev = encode_corpus(generate_corpus(suite, num_workflows=8,
                                               seed=13, target_events=50))
            assert measure_profile_native(ev) == pack_wirec(ev).profile

    def test_threaded_emit_byte_identical(self):
        """Multi-threaded native emit (workflow-row blocks) == serial."""
        from cadence_tpu.native.wirec import pack_wirec_native

        ev = encode_corpus(generate_corpus("timer_retry", num_workflows=96,
                                           seed=23, target_events=30))
        _assert_corpus_equal(pack_wirec_native(ev, num_threads=1),
                             pack_wirec_native(ev, num_threads=4),
                             "threaded")

    def test_adversarial_lanes_byte_parity(self):
        """Pathological lane values (wild 64-bit magnitudes, negatives,
        zero-escape TSREL shapes) — the degradation path must stay
        byte-identical, floor-division quotients included."""
        from cadence_tpu.native.wirec import pack_wirec_native
        from cadence_tpu.ops.encode import NUM_LANES
        from cadence_tpu.ops.wirec import decode_wirec, pack_wirec

        rng = np.random.default_rng(5)
        W, E = 12, 24
        ev = np.zeros((W, E, NUM_LANES), dtype=np.int64)
        n = rng.integers(3, E, size=W)
        for w in range(W):
            ev[w, :n[w], 0] = np.arange(1, n[w] + 1)
            ev[w, :n[w], 1] = rng.integers(0, 40, n[w])
            ev[w, :n[w], 3] = rng.integers(-2**62, 2**62, n[w])
            ev[w, :n[w], 7] = rng.integers(-2**31, 2**31, n[w])
            # sparse huge-absolute lane: the TSREL_NZ shape
            mask = rng.random(n[w]) < 0.5
            ev[w, :n[w], 8] = np.where(
                mask, 1_700_000_000_000_000_000
                + rng.integers(0, 1 << 40, n[w]), 0)
            ev[w, n[w]:, 1] = -1
        py = pack_wirec(ev)
        nat = pack_wirec_native(ev)
        _assert_corpus_equal(py, nat, "adversarial")
        back = np.asarray(decode_wirec(nat.slab, nat.bases, nat.n_events,
                                       nat.profile))
        assert (back == ev).all()

    def test_pinned_profile_streaming_chunks_fused(self):
        """The streaming shape: chunk 0 measures, later chunks emit under
        the PIN through the fused native call (blobs → lanes → wirec in
        one pass) into ONE reusable WirecBuffers slot — every chunk
        byte-identical to the numpy encoder under the same pin, with no
        stale bytes surviving slot reuse."""
        from cadence_tpu.core.codec import serialize_corpus
        from cadence_tpu.native.packing import pack_serialized
        from cadence_tpu.native.wirec import (
            WirecBuffers,
            pack_serialized_wirec,
        )
        from cadence_tpu.ops.encode import history_length
        from cadence_tpu.ops.wirec import pack_wirec

        hists = generate_corpus("basic", num_workflows=24, seed=41,
                                target_events=60)
        max_events = max(history_length(h) for h in hists)
        chunk_w = 8
        blobs = serialize_corpus(hists)
        buf = WirecBuffers(chunk_w, max_events)
        pinned = None
        for lo in range(0, len(blobs), chunk_w):
            chunk = blobs[lo:lo + chunk_w]
            corpus, total = pack_serialized_wirec(
                chunk, max_events, profile=pinned, out=buf)
            dense = pack_serialized(chunk, max_events)
            expect = pack_wirec(dense, profile=pinned)
            _assert_corpus_equal(expect, corpus, f"chunk@{lo}")
            assert total == int(expect.n_events.sum())
            if pinned is None:
                pinned = corpus.profile
            else:
                assert corpus.profile == pinned

    def test_profile_misfit_parity_and_refit(self):
        """A chunk outside the pinned widths must raise ProfileMisfit on
        BOTH encoders (the refit signal is path-independent), and the
        refit both sides then perform must land on identical bytes."""
        from cadence_tpu.native.wirec import pack_wirec_native
        from cadence_tpu.ops.encode import NUM_LANES
        from cadence_tpu.ops.wirec import ProfileMisfit, pack_wirec

        def corpus_with_ts_step(step):
            W, E = 6, 16
            ev = np.zeros((W, E, NUM_LANES), dtype=np.int64)
            for w in range(W):
                ev[w, :, 0] = np.arange(1, E + 1)
                ev[w, :, 1] = 5
                ev[w, :, 3] = 1_000_000 + np.arange(E) * step
            return ev

        narrow = corpus_with_ts_step(1)       # 1-byte deltas
        wide = corpus_with_ts_step(1 << 40)   # overflow the pinned width
        pin = pack_wirec(narrow).profile
        assert pack_wirec_native(narrow).profile == pin
        with pytest.raises(ProfileMisfit):
            pack_wirec(wide, profile=pin)
        with pytest.raises(ProfileMisfit):
            pack_wirec_native(wide, profile=pin)
        # the refit: fresh measurement on the misfitting chunk, both
        # sides, identical plan and bytes
        _assert_corpus_equal(pack_wirec(wide), pack_wirec_native(wide),
                             "refit")

    def test_scale_misfit_parity(self):
        """Scale (GCD) misfits — values that fit the width but break the
        pinned tick — must also raise on both sides."""
        from cadence_tpu.native.wirec import pack_wirec_native
        from cadence_tpu.ops.encode import NUM_LANES
        from cadence_tpu.ops.wirec import ProfileMisfit, pack_wirec

        def corpus(step):
            ev = np.zeros((4, 8, NUM_LANES), dtype=np.int64)
            for w in range(4):
                ev[w, :, 0] = np.arange(1, 9)
                ev[w, :, 1] = 5
                ev[w, :, 3] = 1_000 + np.arange(8) * step
            return ev

        pin = pack_wirec(corpus(1000)).profile   # tick of 1000
        off_tick = corpus(1001)                  # same widths, wrong tick
        raised_py = raised_nat = False
        try:
            pack_wirec(off_tick, profile=pin)
        except ProfileMisfit:
            raised_py = True
        try:
            pack_wirec_native(off_tick, profile=pin)
        except ProfileMisfit:
            raised_nat = True
        assert raised_py == raised_nat

    def test_suffix_repack_parity_via_packcache(self):
        """The append configuration: PackCache re-encodes only the
        appended suffix (resumed interner), and the wirec corpus built
        from those suffix-path lanes must be byte-identical native vs
        Python — the suffix-append feeder leg rides exactly this."""
        from cadence_tpu.engine.cache import PackCache
        from cadence_tpu.native.wirec import pack_wirec_native
        from cadence_tpu.ops.encode import assemble_corpus
        from cadence_tpu.ops.wirec import pack_wirec
        from cadence_tpu.utils import metrics as m

        hists = generate_corpus("concurrent_child", num_workflows=8,
                                seed=19, target_events=50)
        keys = [("d", f"w{i}", "r") for i in range(len(hists))]
        cache = PackCache(max_size=32)
        for k, h in zip(keys, hists):
            cache.encode(k, h[:-1])  # warm the prefix entries
        before = m.DEFAULT_REGISTRY.counter(m.SCOPE_PACK_CACHE,
                                            m.M_CACHE_SUFFIX_PACKS)
        suffixes = [cache.encode_suffix(k, h, len(h) - 1)
                    for k, h in zip(keys, hists)]
        assert m.DEFAULT_REGISTRY.counter(
            m.SCOPE_PACK_CACHE, m.M_CACHE_SUFFIX_PACKS) \
            >= before + len(hists)
        suf = assemble_corpus(suffixes,
                              max(r.shape[0] for r in suffixes))
        _assert_corpus_equal(pack_wirec(suf), pack_wirec_native(suf),
                             "suffix")
        # and the suffix-path lanes equal the tail of a cold full pack
        full = [cache.encode(k, h) for k, h in zip(keys, hists)]
        for i, (k, h) in enumerate(zip(keys, hists)):
            from cadence_tpu.ops.encode import (
                encode_batches_resumable,
                history_length,
            )
            cold, _ = encode_batches_resumable(h)
            assert (suffixes[i]
                    == cold[history_length(h[:-1]):]).all()

    def test_env_knob_pins_python_path(self, monkeypatch):
        """CADENCE_TPU_NATIVE_WIREC=0 must route pack_wirec_auto down the
        pure-Python encoder (counted under tpu.native/python-packs) and
        still produce the identical corpus."""
        from cadence_tpu.native.wirec import pack_wirec_auto
        from cadence_tpu.utils import metrics as m
        from cadence_tpu.utils.metrics import MetricsRegistry

        ev = encode_corpus(generate_corpus("basic", num_workflows=6,
                                           seed=3, target_events=40))
        reg_on, reg_off = MetricsRegistry(), MetricsRegistry()
        monkeypatch.delenv("CADENCE_TPU_NATIVE_WIREC", raising=False)
        on = pack_wirec_auto(ev, registry=reg_on)
        assert reg_on.counter(m.SCOPE_TPU_NATIVE, m.M_NATIVE_PACKS) == 1
        monkeypatch.setenv("CADENCE_TPU_NATIVE_WIREC", "0")
        off = pack_wirec_auto(ev, registry=reg_off)
        assert reg_off.counter(m.SCOPE_TPU_NATIVE, m.M_NATIVE_PY_PACKS) == 1
        _assert_corpus_equal(on, off, "env-knob")

    def test_device_crc_parity_native_corpus(self):
        """End to end: a natively packed corpus replays on device to the
        same CRCs as the Python-packed one, every suite."""
        import jax.numpy as jnp

        from cadence_tpu.core.checksum import DEFAULT_LAYOUT
        from cadence_tpu.native.wirec import pack_wirec_native
        from cadence_tpu.ops.replay import replay_wirec_to_crc
        from cadence_tpu.ops.wirec import pack_wirec

        for suite in SUITES:
            ev = encode_corpus(generate_corpus(suite, num_workflows=6,
                                               seed=29, target_events=40))
            py, nat = pack_wirec(ev), pack_wirec_native(ev)
            crc_p, err_p = replay_wirec_to_crc(
                jnp.asarray(py.slab), jnp.asarray(py.bases),
                jnp.asarray(py.n_events), py.profile, DEFAULT_LAYOUT)
            crc_n, err_n = replay_wirec_to_crc(
                jnp.asarray(nat.slab), jnp.asarray(nat.bases),
                jnp.asarray(nat.n_events), nat.profile, DEFAULT_LAYOUT)
            assert (np.asarray(crc_p) == np.asarray(crc_n)).all(), suite
            assert (np.asarray(err_p) == np.asarray(err_n)).all(), suite


# ---------------------------------------------------------------------------
# The streamed path (PR 31): blobs → wirec a row at a time, no lane tensor.
# Its reference is the dense pair pack_wirec(pack_serialized(...)).
# ---------------------------------------------------------------------------

_EMPTY_BLOB = b"\x00\x00\x00\x00"  # feeder._EMPTY_BLOB: the tail padding
STREAM_SUITES = SUITES + ("overflow",)
#: rows a streamed chunk holds: 10 real + 3 of padding = 13, a W that no
#: thread count below divides but 1 and 13
_STREAM_W = 13


def _stream_chunk(suite, seed=17):
    from cadence_tpu.ops.encode import history_length

    hists = generate_corpus(suite, num_workflows=_STREAM_W - 3, seed=seed,
                            target_events=50)
    blobs = serialize_corpus(hists) + [_EMPTY_BLOB] * 3
    return blobs, max(history_length(h) for h in hists)


def _streamed_vs_dense(blobs, max_events, threads, pinned, reuse, ctx):
    """pack_serialized_wirec against pack_wirec(pack_serialized(...)):
    profile, slab, bases, n_events and the event total. `pinned` packs
    under the dense side's measured profile; `reuse` packs twice into
    one WirecBuffers, over bytes that a stale slot would leave behind."""
    from cadence_tpu.native.wirec import WirecBuffers, pack_serialized_wirec
    from cadence_tpu.ops.wirec import pack_wirec

    expect = pack_wirec(pack_serialized(blobs, max_events))
    profile = expect.profile if pinned else None
    out = WirecBuffers(len(blobs), max_events) if reuse else None
    if reuse:
        stale, _ = pack_serialized_wirec(blobs[::-1], max_events, out=out,
                                         num_threads=threads)
        stale.slab[:] = 0xA5
        stale.bases[:] = -7
        stale.n_events[:] = 99
    got, total = pack_serialized_wirec(blobs, max_events, profile=profile,
                                       num_threads=threads, out=out)
    _assert_corpus_equal(expect, got, ctx)
    assert total == int(expect.n_events.sum()), ctx
    if reuse and got.slab.shape == stale.slab.shape:
        assert np.shares_memory(got.slab, out.slab), ctx


@native_wirec
@pytest.mark.parametrize("reuse", [True, False], ids=["out", "fresh"])
@pytest.mark.parametrize("pinned", [True, False], ids=["pinned", "measure"])
@pytest.mark.parametrize("threads", [1, 3, 4, 64])
@pytest.mark.parametrize("suite", STREAM_SUITES)
def test_streamed_pack_byte_parity(suite, threads, pinned, reuse):
    """The row-at-a-time pack equals the dense pair byte for byte, for
    every suite, with a tail of `_EMPTY_BLOB` padding, a W (13) that 3
    and 4 threads do not divide, more threads than rows, under a pin
    and measuring, into a reused slot and into fresh arrays."""
    blobs, max_events = _stream_chunk(suite)
    _streamed_vs_dense(blobs, max_events, threads, pinned, reuse,
                       f"{suite}/t{threads}/pinned={pinned}/out={reuse}")


def _event(eid, ts, etype, **attrs):
    from cadence_tpu.core.events import HistoryEvent

    return HistoryEvent(id=eid, event_type=etype, version=0, timestamp=ts,
                        task_id=1000 + eid, attrs=attrs)


def _history(events):
    from cadence_tpu.core.events import HistoryBatch

    return [HistoryBatch(domain_id="d", workflow_id="w", run_id="r",
                         events=list(events))]


def _plain_history(n=6, t0=1_700_000_000_000_000_000, version=0,
                   expiration=0, attempt=0):
    """Started + (n - 1) decision-task events, 1000 ns apart. `version`
    sets lane 2 of every event, `expiration` lane a4 of the Started
    event (absolute nanos, the TSREL_NZ shape), `attempt` lane a3."""
    from cadence_tpu.core.enums import EventType

    ev = [_event(1, t0, EventType.WorkflowExecutionStarted, task_list="tl",
                 workflow_type="wt",
                 execution_start_to_close_timeout_seconds=60,
                 task_start_to_close_timeout_seconds=10, attempt=attempt,
                 expiration_timestamp=expiration)]
    for i in range(2, n + 1):
        ev.append(_event(i, t0 + 1000 * i, EventType.DecisionTaskScheduled,
                         task_list="tl", start_to_close_timeout_seconds=10,
                         attempt=0))
    for e in ev:
        e.version = version
    return _history(ev)


def _merge_cases():
    """name → 12 blobs whose plan the MERGE of per-thread statistics has
    to get right (4 threads: blocks of 3 rows)."""
    t0 = 1_700_000_000_000_000_000
    plain = serialize_corpus([_plain_history()])[0]
    return {
        # the version lane reads 0 in every block but the third: CONST
        # inside each block, not CONST over the chunk
        "const-in-every-block-but-one": (
            [plain] * 6
            + serialize_corpus([_plain_history(version=9)]) * 3
            + [plain] * 3),
        # block 0 is all padding: `first`, and the CONST lanes' value,
        # come from a later block
        "first-value-in-a-later-block": (
            [_EMPTY_BLOB] * 3
            + serialize_corpus([_plain_history(attempt=2, version=5)]) * 9),
        # two blocks hold one value each, and they differ
        "two-constants": (
            serialize_corpus([_plain_history(version=3)]) * 6
            + serialize_corpus([_plain_history(version=4)]) * 6),
        # a sparse absolute-nanos lane: zeros and huge values, the huge
        # ones in the last block only
        "tsrel-lane": (
            [plain] * 9 + serialize_corpus(
                [_plain_history(expiration=t0 + 900_000_000_000 * k)
                 for k in (1, 2, 3)])),
        # GCDs that only the merge brings down: ticks of 4000 and 6000
        "gcd-across-blocks": (
            serialize_corpus([_history(
                _plain_history()[0].events[:1]
                + [_event(2, t0 + 4000, 4, task_list="tl", attempt=0,
                          start_to_close_timeout_seconds=10)])]) * 6
            + serialize_corpus([_history(
                _plain_history()[0].events[:1]
                + [_event(2, t0 + 6000, 4, task_list="tl", attempt=0,
                          start_to_close_timeout_seconds=10)])]) * 6),
    }


@native_wirec
@pytest.mark.parametrize("threads", [1, 4, 12])
@pytest.mark.parametrize("case", sorted(_merge_cases()))
def test_streamed_measure_merges_blocks_like_one_scan(case, threads):
    """The streamed measure keeps one set of lane statistics a thread
    and merges them; the plan must be the one a single scan decides."""
    from cadence_tpu.native.wirec import measure_profile_native
    from cadence_tpu.ops.wirec import (
        KIND_CONST,
        KIND_TSREL_NZ,
        pack_wirec,
    )

    blobs = _merge_cases()[case]
    dense = pack_serialized(blobs, 8)
    expect = pack_wirec(dense)
    # the case is what its name says, on the reference side
    if case == "tsrel-lane":
        assert any(e.kind == KIND_TSREL_NZ for e in expect.profile)
    if case in ("const-in-every-block-but-one", "two-constants"):
        assert expect.profile[2].kind != KIND_CONST
    if case == "first-value-in-a-later-block":
        assert expect.profile[2][:2] == (2, KIND_CONST)
        assert expect.profile[2].const == 5
    if case == "gcd-across-blocks":
        assert expect.profile[3].scale == 2000
    _streamed_vs_dense(blobs, 8, threads, False, False, case)
    # the dense entry point runs the same accumulate / merge / finish
    assert measure_profile_native(dense, num_threads=threads) \
        == expect.profile, case


@native_wirec
@pytest.mark.parametrize("threads", [1, 3])
@pytest.mark.parametrize("pinned", [True, False], ids=["pinned", "measure"])
@pytest.mark.parametrize("fault,code", [("truncated", 1),
                                        ("unknown-attr", 2),
                                        ("overlong", 3)])
def test_streamed_pack_decode_errors(fault, code, pinned, threads):
    """A blob that does not decode raises the ValueError it always did:
    the workflow's index in the chunk and the packer's code, from the
    pinned pass and from the measure pass alike."""
    from cadence_tpu.native.wirec import pack_serialized_wirec
    from cadence_tpu.ops.wirec import pack_wirec

    hists = generate_corpus("basic", 7, seed=1, target_events=40)
    blobs = serialize_corpus(hists)
    max_events = 64
    profile = (pack_wirec(pack_serialized(blobs, max_events)).profile
               if pinned else None)
    bad = 5
    if fault == "truncated":
        blobs[bad] = blobs[bad][:len(blobs[bad]) // 2]
    elif fault == "unknown-attr":
        # header: u32 batches, u16 events, then id(8) type(1) version(8)
        # ts(8) task(8) n_attrs(1); the first attr code follows
        b = bytearray(blobs[bad])
        b[4 + 2 + 8 + 1 + 8 + 8 + 8 + 1] = 0xEE
        blobs[bad] = bytes(b)
    else:
        blobs[bad] = serialize_corpus(
            generate_corpus("basic", 1, seed=2, target_events=200))[0]
    with pytest.raises(ValueError, match=f"workflow {bad} .code {code}:"):
        pack_serialized(blobs, max_events)
    with pytest.raises(ValueError, match=f"workflow {bad} .code {code}:"):
        pack_serialized_wirec(blobs, max_events, profile=profile,
                              num_threads=threads)


@native_wirec
@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("what", ["const", "scale", "width"])
def test_streamed_pack_misfit_names_the_lane_pack_wirec_names(what, threads):
    """A chunk outside the pinned plan raises ProfileMisfit from the
    streamed pass with the lane and the reason pack_wirec gives."""
    import re

    from cadence_tpu.native.wirec import pack_serialized_wirec
    from cadence_tpu.ops.wirec import ProfileMisfit, pack_wirec

    pin_blobs = serialize_corpus([_plain_history()] * 8)
    off = {"const": _plain_history(version=7),           # lane 2 is CONST 0
           "scale": _history(                            # lane 3 ticks 1000
               _plain_history()[0].events[:1]
               + [_event(2, 1_700_000_000_000_000_000 + 1500, 4,
                         task_list="tl", attempt=0,
                         start_to_close_timeout_seconds=10)]),
           "width": _history(                            # one byte a delta
               _plain_history()[0].events[:1]
               + [_event(2, 1_700_000_000_000_000_000 + 1000 * 4096, 4,
                         task_list="tl", attempt=0,
                         start_to_close_timeout_seconds=10)])}[what]
    blobs = pin_blobs[:5] + serialize_corpus([off]) + pin_blobs[:2]
    pin = pack_wirec(pack_serialized(pin_blobs, 8)).profile
    with pytest.raises(ProfileMisfit) as py:
        pack_wirec(pack_serialized(blobs, 8), profile=pin)
    with pytest.raises(ProfileMisfit) as nat:
        pack_serialized_wirec(blobs, 8, profile=pin, num_threads=threads)
    lane = re.match(r"lane (\d+):", str(py.value)).group(1)
    assert str(nat.value).startswith(f"lane {lane}:"), (py.value, nat.value)
    reason = {"const": "non-const", "scale": "misfit", "width": "overflow"}
    assert reason[what] in str(py.value) and reason[what] in str(nat.value)
    assert int(lane) == {"const": 2, "scale": 3, "width": 3}[what]
