"""Pipelined bulk-replay executor + pack cache (ISSUE 4).

Covers: the depth-N ring discipline and error paths of
engine/executor.BulkReplayExecutor; pack-cache correctness (cold vs
warm vs suffix-extended packs byte-identical, CRC parity on both wire
formats); the chunked replay engine's bounded-footprint contract (a
long-tail history inflates only its own chunk); device-side verify_all
still detecting divergence through the mismatch bitmap; and the feeder
ring at depth > 2.
"""
import threading
import time

import numpy as np
import pytest

from cadence_tpu.engine.cache import PackCache
from cadence_tpu.engine.executor import BulkReplayExecutor, pipeline_depth
from cadence_tpu.engine.persistence import Stores
from cadence_tpu.engine.tpu_engine import TPUReplayEngine
from cadence_tpu.gen.corpus import generate_corpus
from cadence_tpu.ops.encode import assemble_corpus, encode_corpus
from cadence_tpu.utils import metrics as m

# ---------------------------------------------------------------------------
# executor mechanics (no device work: numpy stands in for device outputs)
# ---------------------------------------------------------------------------


class TestExecutorMechanics:
    def _run(self, depth, n_chunks, fail_at=None):
        log = []
        lock = threading.Lock()
        executor = BulkReplayExecutor(depth=depth)

        def pack(ci):
            with lock:
                log.append(("pack", ci))
            if fail_at is not None and ci == fail_at:
                raise ValueError(f"pack {ci} failed")
            return np.full((4,), ci)

        def launch(ci, packed):
            with lock:
                log.append(("launch", ci))
            return packed * 2

        def consume(ci, outs):
            return int(outs.sum())

        outs, report = executor.run(n_chunks, pack, launch, consume)
        return outs, report, log

    def test_results_ordered_and_consumed(self):
        outs, report, _ = self._run(depth=3, n_chunks=8)
        assert outs == [ci * 2 * 4 for ci in range(8)]
        assert report.chunks == 8 and report.depth == 3
        assert report.pack_s >= 0 and report.wall_s > 0

    def test_ring_discipline_depth_n(self):
        """pack(ci) must never start before chunk ci - depth was LAUNCHED
        (its outputs are what frees the ring slot) — at every depth."""
        for depth in (2, 3, 4):
            _, _, log = self._run(depth=depth, n_chunks=2 * depth + 3)
            for ci in range(depth, 2 * depth + 3):
                pack_at = log.index(("pack", ci))
                launch_at = log.index(("launch", ci - depth))
                assert launch_at < pack_at, (
                    f"depth={depth}: pack({ci}) ran before "
                    f"launch({ci - depth}) freed its ring slot")

    def test_pack_queue_wait_leg_recorded(self):
        m.DEFAULT_REGISTRY.reset()
        self._run(depth=2, n_chunks=5)
        hist = m.DEFAULT_REGISTRY.histogram(m.SCOPE_TPU_REPLAY,
                                            m.M_PROFILE_PACK_WAIT)
        assert hist.count == 5

    def test_pack_failure_propagates_without_hang(self):
        t0 = time.monotonic()
        with pytest.raises(ValueError, match="pack 2 failed"):
            self._run(depth=2, n_chunks=6, fail_at=2)
        assert time.monotonic() - t0 < 30  # pool must not wedge

    def test_pipeline_depth_floor(self):
        assert pipeline_depth(1) == 2
        assert pipeline_depth(5) == 5


# ---------------------------------------------------------------------------
# pack cache: cold == warm == suffix-extended, on every wire format
# ---------------------------------------------------------------------------


class TestPackCacheParity:
    def _corpus(self):
        return generate_corpus("basic", num_workflows=10, seed=17,
                               target_events=40)

    def test_suffix_pack_byte_identical_both_wire_formats(self):
        """A cache hit after appending a batch must produce byte-identical
        packed lanes and identical crc_xor to a cold pack — int64 lanes
        AND wirec."""
        import jax.numpy as jnp

        from cadence_tpu.core.checksum import crc32_of_rows
        from cadence_tpu.ops.replay import replay_to_payload, replay_wirec_to_crc
        from cadence_tpu.ops.wirec import pack_wirec

        hists = self._corpus()
        cache = PackCache()
        keys = [("d", "w", f"r{i}") for i in range(len(hists))]
        # warm the cache on a PREFIX (all but the last batch), then encode
        # the full history: the suffix path must extend the cached rows
        for key, h in zip(keys, hists):
            cache.encode(key, h[:-1])
        warm_rows = [cache.encode(k, h) for k, h in zip(keys, hists)]
        reg = m.DEFAULT_REGISTRY
        assert reg.counter(m.SCOPE_PACK_CACHE, m.M_CACHE_SUFFIX_PACKS) \
            == len(hists)

        cold = encode_corpus(hists)
        warm = assemble_corpus(warm_rows, cold.shape[1])
        assert warm.shape == cold.shape and (warm == cold).all()

        # dense reference: the warm lanes replay to the cold lanes' CRCs
        rows_cold, err_cold = replay_to_payload(jnp.asarray(cold))
        rows_warm, _err_warm = replay_to_payload(jnp.asarray(warm))
        crc_cold = crc32_of_rows(np.asarray(rows_cold))
        crc_warm = crc32_of_rows(np.asarray(rows_warm))
        assert (np.asarray(err_cold) == 0).all()
        assert (crc_cold == crc_warm).all()
        assert (int(np.bitwise_xor.reduce(crc_cold))
                == int(np.bitwise_xor.reduce(crc_warm)))

        # wirec: identical slab/bases/counts, identical device CRCs
        wc_cold = pack_wirec(cold)
        wc_warm = pack_wirec(warm, profile=wc_cold.profile)
        assert (wc_cold.slab == wc_warm.slab).all()
        assert (wc_cold.bases == wc_warm.bases).all()
        assert (wc_cold.n_events == wc_warm.n_events).all()
        crc_c, _ = replay_wirec_to_crc(
            jnp.asarray(wc_cold.slab), jnp.asarray(wc_cold.bases),
            jnp.asarray(wc_cold.n_events), wc_cold.profile)
        crc_w, _ = replay_wirec_to_crc(
            jnp.asarray(wc_warm.slab), jnp.asarray(wc_warm.bases),
            jnp.asarray(wc_warm.n_events), wc_warm.profile)
        assert (np.asarray(crc_c) == np.asarray(crc_w)).all()
        assert (np.asarray(crc_c) == crc_cold).all()

    def test_exact_hit_returns_cached_rows(self):
        hists = self._corpus()
        cache = PackCache()
        a = cache.encode(("d", "w", "r0"), hists[0])
        b = cache.encode(("d", "w", "r0"), hists[0])
        assert a is b  # the cached array itself, no repack
        assert m.DEFAULT_REGISTRY.counter(
            m.SCOPE_PACK_CACHE, m.M_CACHE_HITS) == 1

    def test_tail_overwrite_invalidates(self):
        """A rewritten last batch (transaction-retry overwrite semantics)
        must MISS — the checksum changes."""
        hists = self._corpus()
        h = hists[0]
        cache = PackCache()
        cache.encode(("d", "w", "r0"), h)
        mutated = list(h[:-1]) + [h[-2]]  # different tail bytes
        cache.encode(("d", "w", "r0"), mutated)
        assert m.DEFAULT_REGISTRY.counter(
            m.SCOPE_PACK_CACHE, m.M_CACHE_MISSES) == 2

    def test_eviction_counter_on_metrics(self):
        cache = PackCache(max_size=2)
        hists = self._corpus()
        for i in range(4):
            cache.encode(("d", "w", f"r{i}"), hists[i])
        assert m.DEFAULT_REGISTRY.counter(
            m.SCOPE_PACK_CACHE, m.M_CACHE_EVICTIONS) == 2
        assert 'cadence_evictions_total{scope="tpu.pack-cache"}' in \
            m.DEFAULT_REGISTRY.to_prometheus()


# ---------------------------------------------------------------------------
# chunked replay engine: bounded footprint + unchanged results
# ---------------------------------------------------------------------------


def _stores_with_corpus(hists):
    stores = Stores()
    keys = []
    for i, h in enumerate(hists):
        key = ("dom", f"wf-{i}", f"run-{i}")
        for batch in h:
            stores.history.append_batch(*key, list(batch.events))
        keys.append(key)
    return stores, keys


class TestChunkedReplay:
    def test_long_tail_inflates_only_its_chunk(self):
        """Regression for the unbounded [W, E_max, L] corpus: with one
        long-tail history among many short ones, chunking sizes every
        other chunk's event axis to ITS OWN longest history."""
        short = generate_corpus("basic", num_workflows=11, seed=3,
                                target_events=12)
        long_h = generate_corpus("basic", num_workflows=1, seed=9,
                                 target_events=160)
        hists = short[:5] + long_h + short[5:]
        stores, keys = _stores_with_corpus(hists)

        chunked = TPUReplayEngine(stores, chunk_workflows=4)
        rows_c, err_c, br_c = chunked.replay_tree_payloads(keys)
        shapes = chunked.last_run_chunk_shapes
        assert len(shapes) == 3
        long_e = max(e for _, e in shapes)
        assert sum(1 for _, e in shapes if e == long_e) == 1
        # chunks without the long-tail history stay small: the peak
        # host/HBM footprint is bounded by chunk x its OWN max, not
        # W x corpus max
        assert all(e <= 32 for _, e in shapes if e != long_e)
        assert long_e >= 128

        single = TPUReplayEngine(stores, chunk_workflows=4096)
        rows_s, err_s, br_s = single.replay_tree_payloads(keys)
        assert len(single.last_run_chunk_shapes) == 1
        assert (rows_c == rows_s).all()
        assert (err_c == err_s).all() and (br_c == br_s).all()
        assert (err_c == 0).all()

    def test_chunked_matches_oracle_payloads(self):
        from cadence_tpu.core.checksum import STICKY_ROW_INDEX, payload_row
        from cadence_tpu.oracle.state_builder import StateBuilder

        hists = generate_corpus("timer_retry", num_workflows=9, seed=5,
                                target_events=24)
        stores, keys = _stores_with_corpus(hists)
        engine = TPUReplayEngine(stores, chunk_workflows=4)
        rows, errors, _ = engine.replay_tree_payloads(keys)
        assert (errors == 0).all()
        for i, h in enumerate(hists):
            ms = StateBuilder().replay_history(h)
            expected = payload_row(ms)
            expected[STICKY_ROW_INDEX] = 0
            assert (rows[i] == expected).all()


# ---------------------------------------------------------------------------
# engine-level verify_all: cache warm path + device-side divergence bitmap
# ---------------------------------------------------------------------------


DOMAIN = "exec-domain"
TL = "exec-tl"


@pytest.fixture()
def box():
    from cadence_tpu.engine.onebox import Onebox
    b = Onebox(num_hosts=2, num_shards=8)
    b.frontend.register_domain(DOMAIN)
    return b


class TestVerifyAllExecutor:
    def test_warm_verify_hits_pack_cache_and_suffix_packs(self, box):
        """Acceptance: a warm re-verify of an unchanged corpus is served
        by the resident-state cache (exact hits, zero repacking); an
        appended batch takes the suffix path end to end — a resident
        suffix hit whose lanes come from the pack cache's suffix repack
        (engine/cache.encode_suffix), so BOTH caches' counters move."""
        box.frontend.start_workflow_execution(DOMAIN, "wf-cache", "t", TL)
        result = box.tpu.verify_all()
        assert result.ok
        reg = box.tpu.pack_cache.metrics
        assert reg.counter(m.SCOPE_PACK_CACHE, m.M_CACHE_MISSES) >= 1
        assert reg.counter(m.SCOPE_PACK_CACHE, m.M_CACHE_HITS) == 0
        assert not result.resident  # cold: nothing was pinned yet

        # unchanged corpus: pure resident exact hits, no repacking
        result = box.tpu.verify_all()
        assert result.ok and result.resident
        assert reg.counter(m.SCOPE_TPU_RESIDENT, m.M_CACHE_HITS) >= 1
        assert 'cadence_hits_total{scope="tpu.resident"}' in \
            reg.to_prometheus()

        # append one batch (a signal) — only the suffix repacks, and it
        # replays against the resident state instead of from event 0
        box.frontend.signal_workflow_execution(DOMAIN, "wf-cache", "go")
        assert box.tpu.verify_all().ok
        assert reg.counter(m.SCOPE_PACK_CACHE, m.M_CACHE_SUFFIX_PACKS) >= 1
        assert reg.counter(m.SCOPE_TPU_RESIDENT,
                           m.M_RESIDENT_SUFFIX_HITS) >= 1

    def test_divergence_detected_via_device_bitmap(self, box):
        """verify_all compares on device now; a tampered live state must
        still surface as divergent."""
        from cadence_tpu.models.deciders import CompleteDecider
        from tests.taskpoller import TaskPoller

        box.frontend.start_workflow_execution(DOMAIN, "wf-div", "t", TL)
        TaskPoller(box, DOMAIN, TL, {"wf-div": CompleteDecider()}).drain()
        assert box.tpu.verify_all().ok
        domain_id = box.stores.domain.by_name(DOMAIN).domain_id
        run_id = box.stores.execution.get_current_run_id(domain_id, "wf-div")
        key = (domain_id, "wf-div", run_id)
        ms = box.stores.execution.get_workflow(*key)
        ms.execution_info.signal_count += 1  # foreign corruption
        result = box.tpu.verify_all()
        assert key in result.divergent

    def test_branch_arbitration_mismatch_still_divergent(self, box):
        from cadence_tpu.models.deciders import CompleteDecider
        from tests.taskpoller import TaskPoller

        box.frontend.start_workflow_execution(DOMAIN, "wf-br", "t", TL)
        TaskPoller(box, DOMAIN, TL, {"wf-br": CompleteDecider()}).drain()
        import copy

        domain_id = box.stores.domain.by_name(DOMAIN).domain_id
        run_id = box.stores.execution.get_current_run_id(domain_id, "wf-br")
        key = (domain_id, "wf-br", run_id)
        ms = box.stores.execution.get_workflow(*key)
        # a phantom duplicate branch with the current pointer moved onto
        # it: the device arbitrates branch 0, the store claims 1 — the
        # on-device branch compare must flag it
        vhs = ms.version_histories
        vhs.histories.append(copy.deepcopy(vhs.histories[0]))
        vhs.current_index = 1
        result = box.tpu.verify_all()
        assert key in result.divergent


# ---------------------------------------------------------------------------
# feeder ring at depth > 2
# ---------------------------------------------------------------------------


class TestFeederDepth:
    @pytest.mark.parametrize("depth,suite,workflows,seed", [
        (3, "basic", 26, 7), (4, "basic", 26, 7),
        (4, "echo_signal", 18, 11)])
    def test_deep_ring_matches_direct_replay(self, depth, suite, workflows,
                                             seed):
        from cadence_tpu.native import packing
        from cadence_tpu.native.feeder import feed_corpus_wirec
        from cadence_tpu.ops.replay import replay_corpus

        if not packing.native_available():
            pytest.skip("native packer unavailable")
        hists = generate_corpus(suite, num_workflows=workflows, seed=seed,
                                target_events=30)
        _, crcs_direct, errors_direct = replay_corpus(hists)
        # chunks of 4: several full wraps of the ring
        crcs, errors, report = feed_corpus_wirec(hists, chunk_workflows=4,
                                                 depth=depth)
        assert report.depth == depth
        assert report.chunks == -(-workflows // 4)
        assert (errors == errors_direct).all()
        assert (crcs == crcs_direct).all()
        assert report.pack_queue_wait_s >= 0
