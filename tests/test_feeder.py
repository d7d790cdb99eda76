"""Pipelined feeder + resharding (VERDICT ask #9).

wire bytes → C++ packer → wirec → device replay chunks through the ring,
checked against the dense int64 reference (`replay_corpus`); and
shard-movement invariance: the same corpus on differently-shaped meshes
yields identical payloads.
"""
import numpy as np
import pytest

from cadence_tpu.core.checksum import crc32_of_rows
from cadence_tpu.gen.corpus import SUITES, generate_corpus
from cadence_tpu.native import packing
from cadence_tpu.native.feeder import feed_corpus_wirec
from cadence_tpu.ops.encode import encode_corpus, history_length
from cadence_tpu.ops.replay import replay_corpus

needs_native = pytest.mark.skipif(not packing.native_available(),
                                  reason="native packer unavailable")


def _serialized(histories):
    """(blobs, max_events): what `feed_corpus_wirec` hands the feed loop."""
    from cadence_tpu.core.codec import serialize_corpus

    return (serialize_corpus(histories),
            max(history_length(h) for h in histories))


@needs_native
class TestFeeder:
    def test_feeder_matches_direct_replay(self):
        """Chunked pipelined feed == one-shot dense replay, CRC for CRC."""
        histories = []
        for suite in SUITES:
            histories.extend(generate_corpus(suite, num_workflows=6, seed=5,
                                             target_events=40))
        _rows, crcs_direct, errors_direct = replay_corpus(histories)

        crcs, errors, report = feed_corpus_wirec(histories, chunk_workflows=8)
        assert (errors == errors_direct).all()
        assert (crcs == crcs_direct).all()
        assert report.workflows == len(histories)
        assert report.chunks == -(-len(histories) // 8)
        assert report.events_per_sec > 0
        assert report.pack_events_per_sec > 0
        assert 0 < report.bytes_per_event < 144  # the dense lanes' width

    @pytest.mark.parametrize("suite", SUITES)
    def test_feeder_matches_dense_reference_every_suite(self, suite):
        """Each suite alone measures its own wirec profile on chunk 0;
        the stream it pins replays to the dense reference's CRCs."""
        histories = generate_corpus(suite, num_workflows=20, seed=17,
                                    target_events=40)
        _rows, crcs_direct, errors_direct = replay_corpus(histories)
        crcs, errors, report = feed_corpus_wirec(histories, chunk_workflows=8)
        assert (errors == errors_direct).all()
        assert (crcs == crcs_direct).all()
        assert report.chunks == 3
        assert report.events == sum(history_length(h) for h in histories)
        assert report.wire_bytes > 0 and report.bytes_per_event <= 25

    @pytest.mark.parametrize("bad_chunk", [0, 1])
    def test_pack_failure_propagates_without_hang(self, bad_chunk):
        """A blob the packer refuses raises out of the call, whichever
        chunk holds it: when it is chunk 0, the packers waiting for its
        profile are released with the error, not left waiting."""
        from cadence_tpu.native.feeder import feed_serialized_wirec

        blobs, max_events = _serialized(generate_corpus(
            "basic", num_workflows=12, seed=3, target_events=30))
        bad = bad_chunk * 4 + 1
        blobs[bad] = blobs[bad][:len(blobs[bad]) // 2]
        with pytest.raises(ValueError, match="workflow 1 .code 1"):
            feed_serialized_wirec(blobs, max_events, chunk_workflows=4,
                                  depth=3)

    def test_feeder_pads_tail_chunk(self):
        histories = generate_corpus("basic", num_workflows=5, seed=3,
                                    target_events=30)
        crcs, errors, report = feed_corpus_wirec(histories, chunk_workflows=4)
        assert crcs.shape == (5,) and errors.shape == (5,)
        assert (errors == 0).all()
        assert (crcs == replay_corpus(histories)[1]).all()
        assert report.chunks == 2

    def test_feeder_event_count_is_real(self):
        histories = generate_corpus("basic", num_workflows=4, seed=9,
                                    target_events=30)
        total = sum(history_length(h) for h in histories)
        _, _, report = feed_corpus_wirec(histories, chunk_workflows=4)
        assert report.events == total


class TestResharding:
    def test_mesh_shapes_agree(self):
        """Replay on an 8-device mesh, then a 2-device mesh, then a single
        device: identical payload rows (shard movement never changes
        state — the P1 axis is pure data parallelism)."""
        import jax
        import jax.numpy as jnp

        from cadence_tpu.parallel.mesh import make_mesh, replay_sharded

        histories = []
        for suite in SUITES[:3]:
            histories.extend(generate_corpus(suite, num_workflows=8, seed=11,
                                             target_events=24))
        events = jnp.asarray(encode_corpus(histories))
        devices = jax.devices()
        assert len(devices) >= 8  # conftest forces the 8-device CPU mesh

        rows8, err8, _ = replay_sharded(events, make_mesh(devices[:8]))
        rows2, err2, _ = replay_sharded(events, make_mesh(devices[:2]))
        rows1, err1, _ = replay_sharded(events, make_mesh(devices[:1]))
        rows8, rows2, rows1 = map(np.asarray, (rows8, rows2, rows1))
        assert (np.asarray(err8) == 0).all()
        assert (rows8 == rows2).all()
        assert (rows8 == rows1).all()

    def test_resharded_array_replays_identically(self):
        """Move an ALREADY-SHARDED corpus to a different mesh (the
        shard-steal path: device_put with a new sharding) and replay —
        payloads unchanged."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from cadence_tpu.parallel.mesh import SHARD_AXIS, make_mesh, replay_sharded

        histories = generate_corpus("echo_signal", num_workflows=16, seed=2,
                                    target_events=24)
        events = jnp.asarray(encode_corpus(histories))
        devices = jax.devices()
        mesh_a = make_mesh(devices[:8])
        mesh_b = make_mesh(devices[4:8])  # different device set + shape

        rows_a, _, _ = replay_sharded(events, mesh_a)
        moved = jax.device_put(
            events, NamedSharding(mesh_b, P(SHARD_AXIS, None, None)))
        rows_b, _, _ = replay_sharded(moved, mesh_b)
        assert (np.asarray(rows_a) == np.asarray(rows_b)).all()


@needs_native
class TestFeederNativeWirec:
    """The ISSUE 9 ingest path: the wirec feeder routed through the
    native fused encoder must be CRC-identical to the pure-Python
    fallback (CADENCE_TPU_NATIVE_WIREC=0), with the report saying which
    encoder served and the profile pin surviving the whole stream."""

    def _hists(self, suite="basic"):
        return generate_corpus(suite, num_workflows=48, seed=21,
                               target_events=40)

    @pytest.mark.parametrize("suite", SUITES)
    def test_native_and_python_paths_crc_identical(self, monkeypatch, suite):
        from cadence_tpu.native import wirec as nwirec

        hists = self._hists(suite)
        monkeypatch.delenv(nwirec.NATIVE_WIREC_ENV, raising=False)
        crc_n, err_n, rep_n = feed_corpus_wirec(hists, chunk_workflows=16)
        monkeypatch.setenv(nwirec.NATIVE_WIREC_ENV, "0")
        crc_p, err_p, rep_p = feed_corpus_wirec(hists, chunk_workflows=16)
        if nwirec.native_wirec_available():
            assert rep_n.native_wirec
        assert not rep_p.native_wirec
        assert (crc_n == crc_p).all()
        assert (err_n == err_p).all()
        assert rep_n.events == rep_p.events
        assert rep_n.chunks == rep_p.chunks == 3
        assert rep_n.wire_bytes == rep_p.wire_bytes
        assert rep_n.profile_refits == rep_p.profile_refits

    @pytest.mark.parametrize("native", [True, False])
    def test_one_pack_counted_per_chunk_on_the_encoder_that_served(
            self, monkeypatch, native):
        """Under its own registry a feed counts one pack a chunk, on
        `native-packs` or `python-packs` — the scrape that says which
        encoder a host's bulk path runs."""
        from cadence_tpu.native import wirec as nwirec
        from cadence_tpu.native.feeder import feed_serialized_wirec
        from cadence_tpu.utils import metrics as m

        if native and not nwirec.native_wirec_available():
            pytest.skip("native wirec encoder unavailable")
        monkeypatch.setenv(nwirec.NATIVE_WIREC_ENV, "1" if native else "0")
        reg = m.MetricsRegistry()
        _crc, err, rep = feed_serialized_wirec(
            *_serialized(self._hists()), chunk_workflows=16, registry=reg)
        assert (err == 0).all() and rep.native_wirec == native
        served, other = ((m.M_NATIVE_PACKS, m.M_NATIVE_PY_PACKS) if native
                         else (m.M_NATIVE_PY_PACKS, m.M_NATIVE_PACKS))
        assert reg.counter(m.SCOPE_TPU_NATIVE, served) == rep.chunks == 3
        assert reg.counter(m.SCOPE_TPU_NATIVE, other) == 0
        # the native encoder keeps no lane tensor: chunk 0 is decoded
        # twice (measure, then emit), every pinned chunk once
        assert rep.profile_refits == 0
        assert rep.decode_passes == rep.chunks + (1 if native else 0)
        assert reg.counter(m.SCOPE_TPU_NATIVE,
                           m.M_NATIVE_DECODE_PASSES) == rep.decode_passes

    def test_native_feed_matches_direct_replay_crc(self):
        """Native-fed CRCs == a one-shot replay of the same corpus."""
        import jax.numpy as jnp

        from cadence_tpu.core.checksum import DEFAULT_LAYOUT
        from cadence_tpu.ops.replay import replay_to_payload

        hists = self._hists()
        max_events = max(history_length(h) for h in hists)
        crcs, errors, report = feed_corpus_wirec(hists, chunk_workflows=16,
                                                 max_events=max_events)
        assert (errors == 0).all()
        assert report.profile_refits == 0
        assert report.h2d_s >= 0.0
        rows, _ = replay_to_payload(
            jnp.asarray(encode_corpus(hists, max_events)), DEFAULT_LAYOUT)
        assert (crcs == crc32_of_rows(np.asarray(rows))).all()

    def test_streaming_zero_warm_recompiles(self):
        """Two passes of the same homogeneous stream: zero refits on
        both, identical CRCs, and the decode/replay jit cache must not
        grow on the second — the pinned profile is provably one
        executable, not one per chunk."""
        from cadence_tpu.ops.replay import replay_wirec_to_crc

        hists = generate_corpus("basic", num_workflows=96, seed=41,
                                target_events=30)
        crc1, err1, rep1 = feed_corpus_wirec(hists, chunk_workflows=32)
        assert rep1.profile_refits == 0, \
            "a homogeneous stream refit its pinned profile"
        assert (err1 == 0).all()
        size0 = replay_wirec_to_crc._cache_size()
        crc2, _err2, rep2 = feed_corpus_wirec(hists, chunk_workflows=32)
        assert rep2.profile_refits == 0
        assert replay_wirec_to_crc._cache_size() == size0, \
            "a warm streaming pass compiled a new wirec executable"
        assert (crc1 == crc2).all()

    def test_append_report_o_new_events_and_payload_parity(self):
        """The suffix-append path: PackCache.encode_suffix + resident
        from-state replay (`replay_append_report`, what rebuild,
        replication, migration and the serving tier call) — launched
        chunk shapes are sized by the SUFFIX event axis (O(new events)),
        payloads equal a full replay, and a second pass is all exact
        hits served from the resident payloads."""
        import jax.numpy as jnp

        from cadence_tpu.core.checksum import DEFAULT_LAYOUT
        from cadence_tpu.engine.cache import PackCache, content_address
        from cadence_tpu.engine.ladder import EscalationLadder
        from cadence_tpu.engine.resident import ResidentStateCache
        from cadence_tpu.ops.encode import assemble_corpus
        from cadence_tpu.ops.payload import payload_rows
        from cadence_tpu.ops.replay import replay_events

        layout = DEFAULT_LAYOUT
        hists = generate_corpus("basic", num_workflows=16, seed=33,
                                target_events=60)
        keys = [("d", f"wf-{i}", "r") for i in range(len(hists))]
        pack_cache = PackCache(max_size=64)
        cache = ResidentStateCache(layout, ladder=EscalationLadder(layout))
        prefix_rows = [pack_cache.encode(k, h[:-1])
                       for k, h in zip(keys, hists)]
        corpus = assemble_corpus(prefix_rows,
                                 max(r.shape[0] for r in prefix_rows))
        s = replay_events(jnp.asarray(corpus), layout)
        rows = np.asarray(payload_rows(s, layout))
        branch = np.asarray(s.current_branch)
        for i, k in enumerate(keys):
            assert cache.admit(k, content_address(hists[i][:-1]),
                               cache.extract_row(s, i), rows[i],
                               int(branch[i]))

        hits = [cache.lookup(k, h) for k, h in zip(keys, hists)]
        assert all(hit is not None and hit[0] == "suffix" for hit in hits)
        results, report = cache.replay_append_report(
            [(k, hit[1], h) for k, hit, h in zip(keys, hits, hists)],
            encode_suffix=pack_cache.encode_suffix)
        assert all(r.ok for r in results)
        assert report.events_appended == sum(
            len(h[-1].events) for h in hists)
        assert len(report.chunk_shapes) >= 1
        # O(new events): every launched suffix axis is far below the
        # (bucketed) history axis
        history_e = corpus.shape[1]
        for _w, e in report.chunk_shapes:
            assert e <= max(16, history_e // 2), (e, history_e)
        # payload parity vs full replay
        full_rows = [pack_cache.encode(k, h) for k, h in zip(keys, hists)]
        full = assemble_corpus(full_rows,
                               max(r.shape[0] for r in full_rows))
        s2 = replay_events(jnp.asarray(full), layout)
        expect = np.asarray(payload_rows(s2, layout))
        got = np.stack([np.asarray(r.payload) for r in results])
        assert (got == expect).all()
        # second pass: every key an exact hit on its resident payload,
        # nothing left to append
        hits2 = [cache.lookup(k, h) for k, h in zip(keys, hists)]
        assert all(hit is not None and hit[0] == "exact" for hit in hits2)
        got2 = np.stack([np.asarray(hit[1].payload) for hit in hits2])
        assert (got2 == expect).all()

    def test_heterogeneous_stream_refits_identically(self, monkeypatch):
        """A stream whose later chunks fall outside chunk 0's pinned
        profile must REFIT (counted, never silent) on both encoders and
        still land on identical CRCs — the refit contract is
        path-independent. The native encoder keeps no decoded lanes, so
        a refit packs the chunk again as chunk 0 was packed (measure,
        then emit): `decode_passes` = chunks + 1 + refits."""
        from cadence_tpu.native import wirec as nwirec

        hists = generate_corpus("basic", num_workflows=16, seed=3,
                                target_events=30)
        hists += generate_corpus("timer_retry", num_workflows=16, seed=3,
                                 target_events=30)
        monkeypatch.delenv(nwirec.NATIVE_WIREC_ENV, raising=False)
        crc_n, err_n, rep_n = feed_corpus_wirec(hists, chunk_workflows=16)
        monkeypatch.setenv(nwirec.NATIVE_WIREC_ENV, "0")
        crc_p, err_p, rep_p = feed_corpus_wirec(hists, chunk_workflows=16)
        assert rep_n.profile_refits == rep_p.profile_refits >= 1, \
            "the heterogeneous stream no longer exercises the refit path"
        assert (crc_n == crc_p).all()
        assert (err_n == err_p).all()
        assert rep_p.decode_passes == rep_p.chunks
        if rep_n.native_wirec:
            assert rep_n.decode_passes == (rep_n.chunks + 1
                                           + rep_n.profile_refits)
