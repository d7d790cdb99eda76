"""wirec compressed transfer format: exact round-trip, replay parity,
streaming profile pin/refit.

The host link is the product bottleneck (SURVEY §7 hard part 6); wirec
ships ~10-18 B/event instead of the dense int64 lanes' 144 by GCD-scaled
columnar delta/abs/const coding chosen per lane from the measured corpus,
decoded exactly on device (ops/wirec.py). These tests pin the exactness
contract: decode(pack(x)) == x bit-for-bit, and the replay CRCs match the
dense int64 reference (`replay_to_payload` + `crc32_of_rows`) on every
suite.
"""
import numpy as np
import pytest

from cadence_tpu.core.checksum import DEFAULT_LAYOUT, crc32_of_rows
from cadence_tpu.gen.corpus import SUITES, generate_corpus
from cadence_tpu.ops.encode import NUM_LANES, encode_corpus
from cadence_tpu.ops.wirec import (
    KIND_CONST,
    KIND_DELTA,
    ProfileMisfit,
    decode_wirec,
    pack_wirec,
)


def _corpus(suite, n=16, seed=9, target_events=80):
    return encode_corpus(generate_corpus(suite, num_workflows=n, seed=seed,
                                         target_events=target_events))


def _dense_reference(ev):
    """(crc32 [W], error [W]) of the dense int64 replay of `ev`."""
    import jax.numpy as jnp

    from cadence_tpu.ops.replay import replay_to_payload

    rows, errors = replay_to_payload(jnp.asarray(ev), DEFAULT_LAYOUT)
    return crc32_of_rows(np.asarray(rows)), np.asarray(errors)


class TestWirecRoundTrip:
    @pytest.mark.parametrize("suite", SUITES)
    def test_decode_is_exact(self, suite):
        ev = _corpus(suite)
        c = pack_wirec(ev)
        back = np.asarray(decode_wirec(c.slab, c.bases, c.n_events,
                                       c.profile))
        assert back.shape == ev.shape
        assert (back == ev).all()

    @pytest.mark.parametrize("suite", SUITES)
    def test_density_beats_dense_lanes(self, suite):
        """The whole point: ≤20 B/event (what the replay cells read as
        `h2d.bytes_per_event`) against the dense lanes' 144."""
        ev = _corpus(suite, n=64)
        c = pack_wirec(ev)
        assert ev.itemsize * NUM_LANES == 144
        assert c.bytes_per_event() <= 20.0
        assert c.wire_bytes < ev.nbytes / 5

    def test_adversarial_values_still_exact(self):
        """Pathological lanes (wide random values, negatives, 64-bit
        magnitudes) degrade toward raw width-8 columns, never corrupt."""
        rng = np.random.default_rng(3)
        W, E = 8, 32
        ev = np.zeros((W, E, NUM_LANES), dtype=np.int64)
        n = rng.integers(5, E, size=W)
        for w in range(W):
            ev[w, :n[w], 0] = np.arange(1, n[w] + 1)          # event ids
            ev[w, :n[w], 1] = rng.integers(0, 40, n[w])       # types
            ev[w, :n[w], 3] = rng.integers(-2**62, 2**62, n[w])  # wild ts
            ev[w, :n[w], 7] = rng.integers(-2**31, 2**31, n[w])
            ev[w, n[w]:, 1] = -1
        c = pack_wirec(ev)
        back = np.asarray(decode_wirec(c.slab, c.bases, c.n_events,
                                       c.profile))
        assert (back == ev).all()

    def test_empty_workflows_roundtrip(self):
        """All-padding rows (the feeder's tail-chunk filler blobs)."""
        ev = np.zeros((4, 16, NUM_LANES), dtype=np.int64)
        ev[:, :, 1] = -1  # event-type pad value
        ev[0, :3, 0] = [1, 2, 3]
        ev[0, :3, 1] = [0, 2, 3]
        c = pack_wirec(ev)
        assert (np.asarray(decode_wirec(c.slab, c.bases, c.n_events,
                                        c.profile)) == ev).all()


class TestWirecReplayParity:
    @pytest.mark.parametrize("suite", SUITES)
    def test_crc_matches_dense_reference(self, suite):
        import jax.numpy as jnp

        from cadence_tpu.ops.replay import replay_wirec_to_crc

        ev = _corpus(suite)
        crc_ref, err_ref = _dense_reference(ev)
        c = pack_wirec(ev)
        crcw, errw = replay_wirec_to_crc(jnp.asarray(c.slab),
                                         jnp.asarray(c.bases),
                                         jnp.asarray(c.n_events),
                                         c.profile, DEFAULT_LAYOUT)
        assert (np.asarray(crcw) == crc_ref).all()
        assert (np.asarray(errw) == err_ref).all()

    def test_sharded_crc_matches(self):
        """SPMD wirec replay over the 8-device CPU mesh: compressed in,
        identical CRCs out."""
        from cadence_tpu.parallel.mesh import (
            make_mesh,
            replay_wirec_sharded_crc,
        )

        ev = _corpus("ndc", n=32)
        crc_ref, err_ref = _dense_reference(ev)
        c = pack_wirec(ev)
        crcw, errw, stats = replay_wirec_sharded_crc(c, make_mesh(),
                                                     DEFAULT_LAYOUT)
        assert (np.asarray(crcw) == crc_ref).all()
        assert (np.asarray(errw) == err_ref).all()
        assert int(stats[0]) == int((err_ref != 0).sum())


class TestWirecStreaming:
    def test_pinned_profile_packs_identically(self):
        ev = _corpus("basic")
        c = pack_wirec(ev)
        c2 = pack_wirec(ev, profile=c.profile)
        assert (c2.slab == c.slab).all()
        assert (c2.bases == c.bases).all()

    def test_chunk_parallel_pack_byte_identical(self):
        """The chunk-parallel packer (per-lane planning + per-row-block
        emit fan-out) emits the serial packer's bytes."""
        ev = _corpus("timer_retry", n=640, seed=23, target_events=24)
        serial = pack_wirec(ev)
        threaded = pack_wirec(ev, num_threads=4)
        assert serial.profile == threaded.profile
        assert (serial.slab == threaded.slab).all()
        assert (serial.bases == threaded.bases).all()
        assert (serial.n_events == threaded.n_events).all()

    def test_profile_misfit_raises_not_corrupts(self):
        """A chunk outside the pinned widths/scales must REFUSE, so the
        feeder refits + recompiles instead of shipping wrong bytes."""
        ev = _corpus("basic")
        c = pack_wirec(ev)
        wild = ev.copy()
        wild[:, 1::2, 3] += 7  # ±7ns jitter breaks the delta GCD scale
        with pytest.raises(ProfileMisfit):
            pack_wirec(wild, profile=c.profile)

    @pytest.mark.parametrize("chunk_workflows", [16, 20, 48])
    def test_feeder_wirec_matches_dense_reference(self, chunk_workflows):
        """End-to-end ingest parity: serialized blobs → C++ packer →
        wirec → device decode+replay vs the dense one-shot replay, in
        whole chunks, with a padded tail chunk, and as one chunk."""
        from cadence_tpu.native import packing
        from cadence_tpu.native.feeder import feed_corpus_wirec
        from cadence_tpu.ops.replay import replay_corpus

        if not packing.native_available():
            pytest.skip("native packer not built")
        histories = generate_corpus("echo_signal", num_workflows=48, seed=5,
                                    target_events=60)
        crcw, errw, report = feed_corpus_wirec(
            histories, chunk_workflows=chunk_workflows)
        _rows, crc_ref, err_ref = replay_corpus(histories)
        assert (crcw == crc_ref).all()
        assert (errw == err_ref).all()
        assert report.chunks == -(-48 // chunk_workflows)
        assert report.profile_refits == 0
        assert report.bytes_per_event <= 25  # tiny chunks amortize worse

    def test_profile_kinds_are_sensible(self):
        """The plan the packer discovers on a real corpus: sequential ids
        delta/abs at width 1, constant lanes at width 0."""
        ev = _corpus("basic", n=64)
        c = pack_wirec(ev)
        by_lane = {e.lane: e for e in c.profile}
        assert by_lane[0].width <= 2            # event ids
        assert by_lane[3].kind == KIND_DELTA    # timestamps delta-coded
        assert by_lane[3].width <= 2
        assert any(e.kind == KIND_CONST for e in c.profile)
        total = sum(e.width for e in c.profile)
        assert total <= 20
