"""Mesh-aware serving executor (ISSUE 7).

Covers, on the conftest-provided 8-device virtual CPU mesh:

- mesh-of-1 byte parity with the unsharded kernel (dense payload rows
  AND wirec CRCs) — the serving path at N=1 is the pre-mesh single-chip
  executor, bit for bit;
- mesh-of-2/4 checksum identity with mesh-of-1 on the basic /
  timer_retry / ndc suites — sharding the workflow axis never changes a
  row's result;
- the engine's verify path under a mesh: escalated (capacity-flagged)
  rows resolve identically at every mesh width, and resident suffix
  appends land on — and stay on — the owning device
  (parallel/mesh.workflow_shard);
- per-device observability series under tpu.executor/* and the sharded
  resident pool's per-device byte gauges;
- feeder and rebuilder parity through the same mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cadence_tpu.core.checksum import STICKY_ROW_INDEX, payload_row
from cadence_tpu.engine.executor import replay_corpus_mesh, stream_wirec_mesh
from cadence_tpu.engine.persistence import Stores
from cadence_tpu.engine.tpu_engine import TPUReplayEngine
from cadence_tpu.gen.corpus import generate_corpus
from cadence_tpu.ops.encode import encode_corpus
from cadence_tpu.oracle.state_builder import StateBuilder
from cadence_tpu.parallel.mesh import (
    make_mesh,
    mesh_devices_requested,
    serving_mesh,
    workflow_shard,
)
from cadence_tpu.utils import metrics as m

SEED = 20260730


def _events(suite="basic", n=24, seed=3, target=24):
    return encode_corpus(generate_corpus(suite, num_workflows=n, seed=seed,
                                         target_events=target))


def _stores_with(hists):
    stores = Stores()
    keys = []
    for h in hists:
        key = (h[0].domain_id, h[0].workflow_id, h[0].run_id)
        for b in h:
            stores.history.append_batch(*key, list(b.events))
        stores.execution.upsert_workflow(StateBuilder().replay_history(h))
        keys.append(key)
    return stores, keys


class TestServingPathParity:
    def test_mesh_of_1_dense_byte_identical_to_unsharded(self):
        """The pre-change invariant: the serving executor on a mesh of 1
        must produce the exact payload rows (and CRC XOR) of the
        unsharded single-chip kernel."""
        from cadence_tpu.core.checksum import crc32_of_rows
        from cadence_tpu.ops.replay import replay_to_payload

        ev = _events()
        rows_ref, err_ref = replay_to_payload(jnp.asarray(ev))
        rows_ref, err_ref = np.asarray(rows_ref), np.asarray(err_ref)
        rows, errors, _branch, report = replay_corpus_mesh(
            ev, make_mesh(jax.devices()[:1]), chunk_workflows=8)
        assert report.chunks == 3  # genuinely chunked, not one launch
        assert (rows == rows_ref).all()
        assert (errors == err_ref).all()
        assert (int(np.bitwise_xor.reduce(
            crc32_of_rows(rows).astype(np.uint32)))
            == int(np.bitwise_xor.reduce(
                crc32_of_rows(rows_ref).astype(np.uint32))))

    def test_mesh_of_1_wirec_crc_identical_to_oneshot(self):
        from cadence_tpu.ops.replay import replay_wirec_to_crc
        from cadence_tpu.ops.wirec import pack_wirec

        corpus = pack_wirec(_events(n=24))
        crc_ref, err_ref = replay_wirec_to_crc(
            jnp.asarray(corpus.slab), jnp.asarray(corpus.bases),
            jnp.asarray(corpus.n_events), corpus.profile)
        crc_ref = np.asarray(crc_ref).astype(np.uint32)
        crcs, errors, _rep = stream_wirec_mesh(
            corpus, make_mesh(jax.devices()[:1]), n_chunks=2)
        assert (crcs == crc_ref).all()
        assert (errors == np.asarray(err_ref)).all()

    def test_mesh_4_wirec_crc_program_keeps_its_one_collective(self):
        """The feeder's program on W-sharded inputs under plain `jit`:
        the CRC's matrix is replicated and its product contracts the
        unsharded axis, so the checksum adds no collective to the
        program's one all-reduce, and the sharded CRCs are the host's."""
        import re

        from cadence_tpu.core.checksum import crc32_of_rows
        from cadence_tpu.ops.replay import replay_to_payload, replay_wirec_to_crc
        from cadence_tpu.ops.wirec import pack_wirec
        from cadence_tpu.parallel.mesh import shard_wirec

        ev = _events(n=16)
        corpus = pack_wirec(ev)
        parts = shard_wirec(corpus, make_mesh(jax.devices()[:4]))
        compiled = replay_wirec_to_crc.lower(*parts, corpus.profile).compile()
        collectives = re.findall(
            r" (all-[a-z\-]+|collective-[a-z\-]+|reduce-scatter)\(",
            compiled.as_text())
        assert collectives == ["all-reduce"]
        crc, _err = compiled(*parts)
        rows, _ = replay_to_payload(jnp.asarray(ev))
        assert (np.asarray(crc) == crc32_of_rows(np.asarray(rows))).all()

    @pytest.mark.parametrize("n_chunks,depth", [(4, 3), (2, 2)])
    def test_pipelined_wirec_crc_equals_oneshot_full_mesh(self, n_chunks,
                                                          depth):
        """Chunked executor streaming over the whole mesh == a single
        sharded launch, CRC for CRC: overlapping the per-device H2D
        slices with the previous chunk's replay changes no result."""
        from cadence_tpu.ops.wirec import pack_wirec
        from cadence_tpu.parallel.mesh import replay_wirec_sharded_crc

        mesh = make_mesh()
        corpus = pack_wirec(_events(n=64, seed=29))
        assert (64 // n_chunks) % int(mesh.devices.size) == 0
        crcs_p, errs_p, report = stream_wirec_mesh(
            corpus, mesh, n_chunks=n_chunks, depth=depth)
        assert report.chunks == n_chunks
        crc_1, err_1, _ = replay_wirec_sharded_crc(corpus, mesh)
        assert (crcs_p == np.asarray(crc_1).astype(np.uint32)).all()
        assert (errs_p == np.asarray(err_1)).all()

    def test_warm_pass_zero_recompiles_across_seen_mesh_shapes(self):
        """Mesh shapes already seen recompile nothing on a warm pass:
        every chunk-kernel variant is a hit of the executor's cache."""
        ev = _events(n=48, seed=31)
        devices = jax.devices()
        meshes = [make_mesh(devices[:1]), make_mesh(devices[:2])]
        for mesh in meshes:  # first pass: compiles allowed
            replay_corpus_mesh(ev, mesh, chunk_workflows=16)
        reg = m.DEFAULT_REGISTRY
        misses0 = reg.counter(m.SCOPE_TPU_EXECUTOR, m.M_LADDER_CACHE_MISSES)
        hits0 = reg.counter(m.SCOPE_TPU_EXECUTOR, m.M_LADDER_CACHE_HITS)
        for mesh in meshes:  # warm pass: every variant must hit
            replay_corpus_mesh(ev, mesh, chunk_workflows=16)
        assert reg.counter(m.SCOPE_TPU_EXECUTOR,
                           m.M_LADDER_CACHE_MISSES) == misses0, \
            "a warm serving pass recompiled a mesh shape already seen"
        assert reg.counter(m.SCOPE_TPU_EXECUTOR,
                           m.M_LADDER_CACHE_HITS) >= hits0 + len(meshes)

    @pytest.mark.parametrize("suite", ["basic", "timer_retry", "ndc"])
    @pytest.mark.parametrize("n_dev", [2, 4])
    def test_mesh_n_checksum_identity(self, suite, n_dev):
        """Mesh-of-N payload rows equal mesh-of-1 on the same corpus —
        the PR-5 diagnostic invariant, now on the serving path."""
        devices = jax.devices()
        assert len(devices) >= n_dev
        ev = _events(suite=suite, n=16, seed=11)
        rows_1, err_1, _b1, _ = replay_corpus_mesh(
            ev, make_mesh(devices[:1]), chunk_workflows=8)
        rows_n, err_n, _bn, _ = replay_corpus_mesh(
            ev, make_mesh(devices[:n_dev]), chunk_workflows=8)
        assert (rows_n == rows_1).all()
        assert (err_n == err_1).all()


class TestEngineMeshVerify:
    def test_verify_all_mesh2_with_escalated_rows(self):
        """The engine's full verify path at mesh-of-2 vs mesh-of-1 on an
        overflow corpus: identical verified counts, the SAME keys
        resolved by the widened-K ladder (escalation rides the sharded
        kernels), zero divergence either way."""
        hists = generate_corpus("overflow", num_workflows=96, seed=SEED,
                                target_events=60)
        devices = jax.devices()
        stores1, keys1 = _stores_with(hists)
        r1 = TPUReplayEngine(stores1, chunk_workflows=32, pipeline_depth=2,
                             mesh=make_mesh(devices[:1])).verify_all(keys1)
        stores2, keys2 = _stores_with(hists)
        r2 = TPUReplayEngine(stores2, chunk_workflows=32, pipeline_depth=2,
                             mesh=make_mesh(devices[:2])).verify_all(keys2)
        assert r1.ok and r2.ok
        assert r1.verified_on_device == r2.verified_on_device == len(keys1)
        assert sorted(r1.escalated) == sorted(r2.escalated)
        assert len(r1.escalated) >= 1
        assert r1.fallback == r2.fallback == []

    def test_resident_suffix_append_lands_on_owning_device(self):
        """Verify seeds the sharded resident pool, an appended batch
        takes the suffix path, and the re-admitted state row lives on
        the device its key hashes to — before AND after the append."""
        hists = generate_corpus("basic", num_workflows=12, seed=7,
                                target_events=30)
        devices = jax.devices()
        mesh = make_mesh(devices[:2])
        stores = Stores()
        keys = []
        for h in hists:
            key = (h[0].domain_id, h[0].workflow_id, h[0].run_id)
            for b in h[:-1]:
                stores.history.append_batch(*key, list(b.events))
            stores.execution.upsert_workflow(
                StateBuilder().replay_history(h[:-1]))
            keys.append(key)
        engine = TPUReplayEngine(stores, chunk_workflows=8,
                                 pipeline_depth=2, mesh=mesh)
        assert engine.verify_all(keys).ok
        assert len(engine.resident) >= 1

        def owning_ok(key):
            shard = workflow_shard(key, 2)
            entry = engine.resident._slices[shard].get(key)
            if entry is None:
                return None
            leaf = jax.tree_util.tree_leaves(entry.state)[0]
            return leaf.devices() == {mesh.devices.flat[shard]}

        seeded = [k for k in keys if owning_ok(k)]
        assert seeded, "no resident entries on their owning device"
        assert all(owning_ok(k) for k in seeded)

        # append the held-back last batch: the suffix path must serve it
        # and the widened/re-admitted row must STAY on the owning device
        for h, key in zip(hists, keys):
            stores.history.append_batch(*key, list(h[-1].events))
            stores.execution.upsert_workflow(
                StateBuilder().replay_history(h), set_current=False)
        result = engine.verify_all(keys)
        assert result.ok
        reg = engine.metrics
        assert reg.counter(m.SCOPE_TPU_RESIDENT,
                           m.M_RESIDENT_SUFFIX_HITS) >= 1
        for k in keys:
            assert owning_ok(k) in (True, None)
        assert any(owning_ok(k) for k in keys)

    def test_per_device_series_on_metrics(self):
        """tpu.executor/* gains device-labelled series (chunks, rows,
        busy gauge) and the sharded resident pool exports per-device
        byte gauges — all reachable through prometheus exposition."""
        hists = generate_corpus("basic", num_workflows=16, seed=5,
                                target_events=24)
        stores, keys = _stores_with(hists)
        engine = TPUReplayEngine(stores, chunk_workflows=8,
                                 pipeline_depth=2,
                                 mesh=make_mesh(jax.devices()[:2]))
        assert engine.verify_all(keys).ok
        reg = engine.metrics
        assert reg.counter(m.SCOPE_TPU_EXECUTOR, m.M_EXEC_CHUNKS) >= 2
        for d in range(2):
            assert reg.counter(
                m.SCOPE_TPU_EXECUTOR,
                m.device_metric(m.M_EXEC_CHUNKS, d)) >= 2
            assert reg.counter(
                m.SCOPE_TPU_EXECUTOR,
                m.device_metric(m.M_EXEC_ROWS, d)) >= 1
        # busy gauge settled back to zero after the run
        assert reg.gauge_value(m.SCOPE_TPU_EXECUTOR,
                               m.M_EXEC_IN_FLIGHT) == 0.0
        prom = reg.to_prometheus()
        assert 'cadence_chunks_dispatched_dev0_total{scope="tpu.executor"}' \
            in prom
        assert 'cadence_launches_in_flight_dev1{scope="tpu.executor"}' in prom
        # sharded resident pool: per-device occupancy gauges
        assert reg.gauge_value(m.SCOPE_TPU_RESIDENT,
                               m.device_metric(m.M_RESIDENT_BYTES, 0)) \
            + reg.gauge_value(m.SCOPE_TPU_RESIDENT,
                              m.device_metric(m.M_RESIDENT_BYTES, 1)) > 0

    def test_resident_budget_splits_per_device(self):
        from cadence_tpu.engine.resident import ResidentStateCache

        cache = ResidentStateCache(budget_bytes=1 << 20,
                                   mesh=make_mesh(jax.devices()[:4]))
        assert cache.n_shards == 4
        assert cache.slice_budget == (1 << 20) // 4
        # rebinding to a different width drops entries (placement moved)
        cache.set_mesh(make_mesh(jax.devices()[:2]))
        assert cache.n_shards == 2 and len(cache) == 0


def _sharded_chunk(mesh, hists):
    """One verify chunk as the engine lays it out over `mesh`: P rows a
    device, each key's row in its owning device's slice, the rest
    padding. Returns (state, payload rows, branches, keys, row of key)."""
    from cadence_tpu.ops.encode import (
        LANE_EVENT_TYPE,
        NUM_LANES,
        assemble_corpus,
        encode_batches_resumable,
    )
    from cadence_tpu.ops.payload import payload_rows
    from cadence_tpu.ops.replay import replay_events
    from cadence_tpu.parallel.mesh import place_corpus

    n = int(mesh.devices.size)
    keys = [("d", f"w{i}", "r") for i in range(len(hists))]
    buckets = [[i for i, k in enumerate(keys) if workflow_shard(k, n) == s]
               for s in range(n)]
    per = max(len(b) for b in buckets) + 1      # a padding row a device
    row_of = {i: s * per + j for s, b in enumerate(buckets)
              for j, i in enumerate(b)}
    rows_list = [encode_batches_resumable(h)[0] for h in hists]
    E = max(r.shape[0] for r in rows_list)
    corpus = np.zeros((n * per, E, NUM_LANES), dtype=np.int64)
    corpus[:, :, LANE_EVENT_TYPE] = -1
    corpus[[row_of[i] for i in range(len(hists))]] = \
        assemble_corpus(rows_list, E)
    state = replay_events(place_corpus(corpus, mesh))
    assert (np.asarray(state.error) == 0).all()
    return (state, np.asarray(payload_rows(state)),
            np.asarray(state.current_branch), keys, row_of)


@pytest.mark.parametrize("how", ["views", "rows"])
def test_sharded_pool_seeded_by_a_chunk_of_views(how):
    """The sharded pool's case of tests/test_resident.py TestChunkViews:
    a view pins its OWN device's rows of the chunk, that part counts
    whole in the device's slice while a view of it is live there, and
    the row it materialises lies on the key's owning device."""
    import gc
    import weakref

    from cadence_tpu.core.checksum import DEFAULT_LAYOUT
    from cadence_tpu.engine.cache import content_address
    from cadence_tpu.engine.resident import ResidentStateCache

    mesh = make_mesh(jax.devices()[:2])
    hists = generate_corpus("basic", num_workflows=10, seed=11,
                            target_events=24)
    prefix = [h[:-1] for h in hists]
    state, rows, branch, keys, row_of = _sharded_chunk(mesh, prefix)
    cache = ResidentStateCache(mesh=mesh)
    items = [(keys[i], content_address(prefix[i]), row_of[i],
              rows[row_of[i]], int(branch[row_of[i]]))
             for i in range(len(keys))]
    if how == "views":
        assert cache.admit_chunk(state, items) == len(keys)
    else:
        for key, address, r, payload, br in items:
            assert cache.admit(key, address, cache.extract_row(state, r),
                               payload, br)
    owner = {k: workflow_shard(k, 2) for k in keys}
    count = [sum(1 for k in keys if owner[k] == s) for s in range(2)]
    assert min(count) >= 1
    row_nbytes = cache._row_nbytes(DEFAULT_LAYOUT)
    payload_nbytes = DEFAULT_LAYOUT.width * 8
    part_nbytes = sum(leaf.nbytes for leaf in
                      jax.tree_util.tree_leaves(state)) // 2

    def per_device():
        return cache.stats()["per_device_bytes"]

    if how == "views":
        assert per_device() == [part_nbytes + c * payload_nbytes
                                for c in count]
    else:
        assert per_device() == [c * row_nbytes for c in count]
    reg = cache.metrics
    for s in range(2):
        assert reg.gauge_value(
            m.SCOPE_TPU_RESIDENT,
            m.device_metric(m.M_RESIDENT_BYTES, s)) == per_device()[s]

    # the same lookups, and no state read by any of them
    for k, h in zip(keys, hists):
        assert cache.lookup(k, h[:-1])[0] == "exact"
        assert cache.lookup(k, h)[0] == "suffix"
    assert reg.counter(m.SCOPE_TPU_RESIDENT,
                       m.M_RESIDENT_VIEWS_MATERIALISED) == 0

    # device 0's part goes with device 0's last view, whatever device 1
    # still views
    part0 = weakref.ref(next(
        sh.data for sh in jax.tree_util.tree_leaves(state)[0]
        .addressable_shards if sh.device == mesh.devices.flat[0]))
    del state
    gc.collect()
    if how == "views":
        assert part0() is not None
    on0 = [k for k in keys if owner[k] == 0]
    for k in on0:
        leaf = jax.tree_util.tree_leaves(cache.entry_for(k).state)[0]
        assert leaf.devices() == {mesh.devices.flat[0]}
    gc.collect()
    assert part0() is None
    assert per_device()[0] == count[0] * row_nbytes
    if how == "views":
        assert per_device()[1] == part_nbytes + count[1] * payload_nbytes
        assert cache.stats()["view_entries"] == count[1]
        assert reg.counter(m.SCOPE_TPU_RESIDENT,
                           m.M_RESIDENT_VIEWS_MATERIALISED) == count[0]

    # a suffix append from the views left gives the row's append, on
    # the owning device, re-pinned as a view of the append's final state
    # there; the row it materialises lies there too
    on1 = [i for i, k in enumerate(keys) if owner[k] == 1]
    results = cache.replay_append(
        [(keys[i], cache.lookup(keys[i], hists[i])[1], hists[i])
         for i in on1])
    for i, res in zip(on1, results):
        assert res.ok
        oracle = payload_row(StateBuilder().replay_history(hists[i]))
        oracle[STICKY_ROW_INDEX] = 0
        assert (res.payload == oracle).all()
        kind, entry = cache.lookup(keys[i], hists[i])
        assert kind == "exact" and entry.is_view
        for leaf in jax.tree_util.tree_leaves(entry._chunk.state):
            assert leaf.devices() == {mesh.devices.flat[1]}
        leaf = jax.tree_util.tree_leaves(entry.state)[0]
        assert leaf.devices() == {mesh.devices.flat[1]}
    assert per_device() == [c * row_nbytes for c in count]
    assert cache.resident_bytes <= cache.budget_bytes


def test_a_view_must_lie_on_its_keys_owning_device():
    from cadence_tpu.engine.cache import content_address
    from cadence_tpu.engine.resident import ResidentStateCache

    mesh = make_mesh(jax.devices()[:2])
    hists = generate_corpus("basic", num_workflows=4, seed=13,
                            target_events=20)
    state, rows, branch, keys, row_of = _sharded_chunk(mesh, hists)
    cache = ResidentStateCache(mesh=mesh)
    per = rows.shape[0] // 2
    wrong = (row_of[0] + per) % (2 * per)    # the other device's slice
    with pytest.raises(ValueError, match="does not lie on shard"):
        cache.admit_chunk(state, [(keys[0], content_address(hists[0]),
                                   wrong, rows[wrong], 0)])
    assert len(cache) == 0 and cache.resident_bytes == 0


def _feeder_case():
    """(histories, dense CRCs, dense errors) for the feeder-over-a-mesh
    cases; skips where the native packer cannot be built."""
    from cadence_tpu.native import packing
    from cadence_tpu.ops.replay import replay_corpus

    if not packing.native_available():
        pytest.skip("native packer unavailable")
    hists = generate_corpus("basic", num_workflows=18, seed=7,
                            target_events=24)
    _, crcs_direct, errors_direct = replay_corpus(hists)
    return hists, crcs_direct, errors_direct


class TestMeshConsumers:
    def test_rebuilder_mesh_parity(self):
        from cadence_tpu.core.checksum import STICKY_ROW_INDEX, payload_row
        from cadence_tpu.engine.rebuild import DeviceRebuilder

        hists = generate_corpus("timer_retry", num_workflows=10, seed=9,
                                target_events=24)
        rb = DeviceRebuilder(chunk_jobs=4,
                             mesh=make_mesh(jax.devices()[:2]))
        states = rb.rebuild([(h, None) for h in hists])
        assert rb.stats.device == len(hists)
        assert rb.stats.oracle_fallback == 0
        for ms, h in zip(states, hists):
            got = payload_row(ms)
            got[STICKY_ROW_INDEX] = 0
            expected = payload_row(StateBuilder().replay_history(h))
            expected[STICKY_ROW_INDEX] = 0
            assert (got == expected).all()

    @pytest.mark.parametrize("n_dev,chunk_workflows,chunks", [
        (2, 6, 3), (4, 6, 3), (8, 8, 3)])
    def test_feeder_mesh_parity(self, n_dev, chunk_workflows, chunks):
        """The feeder over a mesh against the dense one-shot replay; a
        chunk width that is no multiple of the mesh is rounded up to a
        whole slice per device (6 → 8 on four devices)."""
        from cadence_tpu.native.feeder import feed_corpus_wirec

        hists, crcs_direct, errors_direct = _feeder_case()
        crcs, errors, report = feed_corpus_wirec(
            hists, chunk_workflows=chunk_workflows, depth=3,
            mesh=make_mesh(jax.devices()[:n_dev]))
        assert report.chunks == chunks
        assert (errors == errors_direct).all()
        assert (crcs == crcs_direct).all()

    def test_feeder_resolves_mesh_from_env_knob(self, monkeypatch):
        """With no mesh handed in, CADENCE_TPU_MESH_DEVICES decides:
        the feeder shards every chunk over that many devices."""
        from cadence_tpu.native.feeder import feed_corpus_wirec

        hists, crcs_direct, errors_direct = _feeder_case()
        monkeypatch.setenv("CADENCE_TPU_MESH_DEVICES", "2")
        reg = m.DEFAULT_REGISTRY
        name = m.device_metric(m.M_EXEC_CHUNKS, 1)
        before = reg.counter(m.SCOPE_TPU_EXECUTOR, name)
        crcs, errors, report = feed_corpus_wirec(hists, chunk_workflows=5)
        assert report.chunks == 3  # 5 rounds up to 6: a whole slice each
        assert (errors == errors_direct).all()
        assert (crcs == crcs_direct).all()
        assert reg.counter(m.SCOPE_TPU_EXECUTOR, name) == before + 3

    def test_serving_mesh_env_knob(self, monkeypatch):
        monkeypatch.delenv("CADENCE_TPU_MESH_DEVICES", raising=False)
        assert mesh_devices_requested() == 1
        assert int(serving_mesh().devices.size) == 1
        monkeypatch.setenv("CADENCE_TPU_MESH_DEVICES", "4")
        assert mesh_devices_requested() == 4
        assert int(serving_mesh().devices.size) == 4
        monkeypatch.setenv("CADENCE_TPU_MESH_DEVICES", "all")
        assert mesh_devices_requested() == 0
        assert int(serving_mesh().devices.size) == len(jax.devices())

    def test_workflow_shard_stable(self):
        key = ("d", "wf", "run")
        assert workflow_shard(key, 1) == 0
        for n in (2, 4, 8):
            s = workflow_shard(key, n)
            assert 0 <= s < n
            assert workflow_shard(key, n) == s  # deterministic
