"""Device-resident incremental replay (ISSUE 6).

Covers: the from-state kernel family (ops/replay.replay_from_state*,
dense + wirec) replaying suffixes byte-identically to full-history
replay; ResidentStateCache content-address semantics (exact / suffix /
stale), LRU eviction under the HBM budget, and invalidation on tail
overwrite / reset / NDC branch switch through verify_all; the
capacity-escalation ladder widening a resident state on an overflowing
append and re-narrowing it once the load drains; the pipelined executor
packing only suffix batches at depth >= 2; the rebuilder's resident
consult; an append's base-rung rows re-pinned as views of its final
state (a widened rung's row by row); an append's spans
(`resident.launch`, `.device-wait`, `.readmit`, once a chunk under the
caller's span) and the `row-slices` count; and the tpu.resident/*
metrics surface.
"""
import random
from collections import Counter

import numpy as np
import pytest

from cadence_tpu.core.checksum import (
    DEFAULT_LAYOUT,
    STICKY_ROW_INDEX,
    crc32_of_rows,
    payload_row,
)
from cadence_tpu.core.enums import EventType
from cadence_tpu.engine.cache import (
    ContentAddress,
    address_relation,
    content_address,
)
from cadence_tpu.engine import resident as resident_mod
from cadence_tpu.engine.ladder import EscalationLadder
from cadence_tpu.engine.resident import ResidentStateCache
from cadence_tpu.gen.corpus import generate_corpus
from cadence_tpu.ops.encode import assemble_corpus, encode_batches_resumable
from cadence_tpu.oracle.state_builder import StateBuilder
from cadence_tpu.utils import metrics as m
from cadence_tpu.utils import tracing
from tests.test_recover_warm import _kinds, _recover
from tests.test_recover_warm import wal, written  # noqa: F401 (fixtures)
from tests.test_serving import _Harness

DOMAIN = "res-domain"
TL = "res-tl"


def _oracle_row(batches, layout=DEFAULT_LAYOUT):
    ms = StateBuilder().replay_history(batches)
    row = payload_row(ms, layout)
    row[STICKY_ROW_INDEX] = 0
    return row


def _replay_full(hists):
    """Full-history device replay -> (state, payload rows np)."""
    import jax.numpy as jnp

    from cadence_tpu.ops.payload import payload_rows
    from cadence_tpu.ops.replay import replay_events

    rows_list = [encode_batches_resumable(h)[0] for h in hists]
    corpus = assemble_corpus(rows_list,
                             max(r.shape[0] for r in rows_list))
    s = replay_events(jnp.asarray(corpus))
    return s, np.asarray(payload_rows(s))


def _seed_cache(cache, keys, prefix_hists):
    """Pin every workflow's prefix state (the cold-path admission the
    engine does from verify_all, done directly)."""
    s, rows = _replay_full(prefix_hists)
    branch = np.asarray(s.current_branch)
    assert (np.asarray(s.error) == 0).all()
    for i, key in enumerate(keys):
        assert cache.admit(key, content_address(prefix_hists[i]),
                           cache.extract_row(s, i), rows[i],
                           int(branch[i]))


# ---------------------------------------------------------------------------
# from-state kernels: suffix replay == full replay, dense and wirec
# ---------------------------------------------------------------------------


class TestFromStateKernels:
    @pytest.mark.parametrize("suite", ["basic", "timer_retry",
                                       "concurrent_child", "ndc"])
    def test_dense_suffix_parity_every_suite(self, suite):
        """replay_from_state over the appended batches must land on the
        exact payload bytes of a full-history replay — the correctness
        gate of the whole subsystem, per workload suite."""
        import jax.numpy as jnp

        from cadence_tpu.ops.replay import (
            replay_events,
            replay_from_state_to_payload,
        )

        hists = generate_corpus(suite, num_workflows=8, seed=11,
                                target_events=40)
        _, rows_full = _replay_full(hists)

        prefixes = [encode_batches_resumable(h[:-1]) for h in hists]
        pref = assemble_corpus([r for r, _ in prefixes],
                               max(r.shape[0] for r, _ in prefixes))
        s_pref = replay_events(jnp.asarray(pref))
        suffix_rows = [encode_batches_resumable(h[-1:], mp)[0]
                       for h, (_, mp) in zip(hists, prefixes)]
        suf = assemble_corpus(suffix_rows,
                              max(r.shape[0] for r in suffix_rows))
        _s, rows, err, ovf = replay_from_state_to_payload(
            jnp.asarray(suf), s_pref, DEFAULT_LAYOUT)
        assert (np.asarray(err) == 0).all()
        assert not np.asarray(ovf).any()
        assert (np.asarray(rows) == rows_full).all()
        for i, h in enumerate(hists):
            assert (np.asarray(rows)[i] == _oracle_row(h)).all()

    def test_wirec_suffix_crc_parity(self):
        """The compressed-wire variant: suffix packs as its own wirec
        corpus and the from-state CRC matches full replay bit for bit."""
        import jax.numpy as jnp

        from cadence_tpu.ops.replay import (
            replay_events,
            replay_wirec_from_state_to_crc,
        )
        from cadence_tpu.ops.wirec import pack_wirec

        hists = generate_corpus("echo_signal", num_workflows=6, seed=5,
                                target_events=32)
        _, rows_full = _replay_full(hists)
        crc_full = crc32_of_rows(rows_full)

        prefixes = [encode_batches_resumable(h[:-1]) for h in hists]
        pref = assemble_corpus([r for r, _ in prefixes],
                               max(r.shape[0] for r, _ in prefixes))
        s_pref = replay_events(jnp.asarray(pref))
        suffix_rows = [encode_batches_resumable(h[-1:], mp)[0]
                       for h, (_, mp) in zip(hists, prefixes)]
        suf = assemble_corpus(suffix_rows,
                              max(r.shape[0] for r in suffix_rows))
        wc = pack_wirec(suf)
        crc, err, ovf = replay_wirec_from_state_to_crc(
            jnp.asarray(wc.slab), jnp.asarray(wc.bases),
            jnp.asarray(wc.n_events), wc.profile, s_pref, DEFAULT_LAYOUT)
        assert (np.asarray(err) == 0).all()
        assert not np.asarray(ovf).any()
        assert (np.asarray(crc).astype(np.uint32) == crc_full).all()

    def test_wirec_suffix_payload_parity(self):
        """The payload twin of the compressed suffix path
        (replay_wirec_from_state_to_payload — the serving shape): wirec
        suffix from-state replay lands on the exact payload rows of the
        dense from-state replay and of a full-history replay."""
        import jax.numpy as jnp

        from cadence_tpu.ops.replay import (
            replay_events,
            replay_wirec_from_state_to_payload,
        )
        from cadence_tpu.ops.wirec import pack_wirec

        hists = generate_corpus("basic", num_workflows=6, seed=17,
                                target_events=32)
        _, rows_full = _replay_full(hists)
        prefixes = [encode_batches_resumable(h[:-1]) for h in hists]
        pref = assemble_corpus([r for r, _ in prefixes],
                               max(r.shape[0] for r, _ in prefixes))
        s_pref = replay_events(jnp.asarray(pref))
        suffix_rows = [encode_batches_resumable(h[-1:], mp)[0]
                       for h, (_, mp) in zip(hists, prefixes)]
        suf = assemble_corpus(suffix_rows,
                              max(r.shape[0] for r in suffix_rows))
        wc = pack_wirec(suf)
        _s, rows, err, ovf = replay_wirec_from_state_to_payload(
            jnp.asarray(wc.slab), jnp.asarray(wc.bases),
            jnp.asarray(wc.n_events), wc.profile, s_pref, DEFAULT_LAYOUT)
        assert (np.asarray(err) == 0).all()
        assert not np.asarray(ovf).any()
        assert (np.asarray(rows) == rows_full).all()

    def test_widen_then_suffix_replay_then_narrow(self):
        """A base state widened to 2K replays the suffix to the same
        base-width payload, and narrow_state round-trips it back."""
        import jax.numpy as jnp

        from cadence_tpu.ops.payload import payload_rows
        from cadence_tpu.ops.replay import (
            replay_events,
            replay_from_state_to_payload,
        )
        from cadence_tpu.ops.state import (
            layout_of,
            narrow_ok,
            narrow_state,
            widen_layout,
            widen_state,
        )

        hists = generate_corpus("timer_retry", num_workflows=5, seed=7,
                                target_events=36)
        _, rows_full = _replay_full(hists)
        prefixes = [encode_batches_resumable(h[:-1]) for h in hists]
        pref = assemble_corpus([r for r, _ in prefixes],
                               max(r.shape[0] for r, _ in prefixes))
        s_pref = replay_events(jnp.asarray(pref))
        wide = widen_layout(DEFAULT_LAYOUT, 2)
        s_wide = widen_state(s_pref, wide)
        assert layout_of(s_wide) == wide
        suffix_rows = [encode_batches_resumable(h[-1:], mp)[0]
                       for h, (_, mp) in zip(hists, prefixes)]
        suf = assemble_corpus(suffix_rows,
                              max(r.shape[0] for r in suffix_rows))
        s_fin, rows, err, _ovf = replay_from_state_to_payload(
            jnp.asarray(suf), s_wide, DEFAULT_LAYOUT)
        assert (np.asarray(err) == 0).all()
        assert (np.asarray(rows) == rows_full).all()
        assert np.asarray(narrow_ok(s_fin, DEFAULT_LAYOUT)).all()
        s_narrow = narrow_state(s_fin, DEFAULT_LAYOUT)
        assert layout_of(s_narrow) == DEFAULT_LAYOUT
        assert (np.asarray(payload_rows(s_narrow)) == rows_full).all()


# ---------------------------------------------------------------------------
# content-address + cache unit semantics
# ---------------------------------------------------------------------------


class TestContentAddress:
    def test_relations(self):
        hists = generate_corpus("basic", num_workflows=1, seed=3,
                                target_events=24)
        h = hists[0]
        addr = content_address(h[:-1])
        assert addr == ContentAddress(len(h) - 1,
                                      content_address(h[:-1]).last_batch_crc)
        assert address_relation(addr, h[:-1]) == "exact"
        assert address_relation(addr, h) == "prefix"
        # fewer batches than cached: stale
        assert address_relation(content_address(h), h[:-1]) == "stale"
        # overwritten tail at the cached position: stale
        mutated = list(h[:-2]) + [h[-1]]
        assert address_relation(addr, mutated) == "stale"

    def test_packcache_and_resident_share_the_helper(self):
        """The drift guard: both caches must address through the SAME
        functions (no private copies of the tuple logic)."""
        import inspect

        from cadence_tpu.engine import cache as cache_mod
        from cadence_tpu.engine import resident as resident_mod

        src_pack = inspect.getsource(cache_mod.PackCache)
        src_res = inspect.getsource(resident_mod.ResidentStateCache)
        assert "address_relation" in src_pack
        assert "address_relation" in src_res or \
            "address_relation" in inspect.getsource(
                resident_mod.ResidentStateCache.lookup)
        assert "_batch_crc" not in src_pack  # the old private copy is gone


class TestResidentCacheUnit:
    def _cache(self, **kw):
        kw.setdefault("ladder", EscalationLadder(DEFAULT_LAYOUT))
        return ResidentStateCache(DEFAULT_LAYOUT, **kw)

    def test_lookup_exact_suffix_stale(self):
        cache = self._cache()
        hists = generate_corpus("basic", num_workflows=2, seed=13,
                                target_events=24)
        keys = [("d", f"w{i}", "r") for i in range(2)]
        _seed_cache(cache, keys, [h[:-1] for h in hists])
        reg = cache.metrics

        kind, entry = cache.lookup(keys[0], hists[0][:-1])
        assert kind == "exact"
        assert (entry.payload == _oracle_row(hists[0][:-1])).all()
        kind, _ = cache.lookup(keys[0], hists[0])
        assert kind == "suffix"
        assert reg.counter(m.SCOPE_TPU_RESIDENT, m.M_CACHE_HITS) == 1
        assert reg.counter(m.SCOPE_TPU_RESIDENT,
                           m.M_RESIDENT_SUFFIX_HITS) == 1

        # tail overwrite: stale -> entry invalidated, then a clean miss
        mutated = list(hists[1][:-2]) + [hists[1][-1]]
        assert cache.lookup(keys[1], mutated) is None
        assert reg.counter(m.SCOPE_TPU_RESIDENT,
                           m.M_CACHE_INVALIDATIONS) == 1
        assert cache.lookup(keys[1], hists[1][:-1]) is None  # dropped
        assert reg.counter(m.SCOPE_TPU_RESIDENT, m.M_CACHE_MISSES) == 2

        # non-authoritative prefix lookups (rebuild at a reset point)
        # must NOT invalidate the entry
        assert cache.lookup(keys[0], hists[0][:1],
                            authoritative=False) is None
        assert cache.lookup(keys[0], hists[0][:-1])[0] == "exact"

    def test_lru_eviction_at_budget(self):
        probe = self._cache()
        row_bytes = probe._row_nbytes(DEFAULT_LAYOUT)
        cache = self._cache(budget_bytes=3 * row_bytes + 1)
        hists = generate_corpus("basic", num_workflows=5, seed=17,
                                target_events=20)
        keys = [("d", f"w{i}", "r") for i in range(5)]
        _seed_cache(cache, keys, [h[:-1] for h in hists])
        assert len(cache) == 3
        assert cache.resident_bytes <= cache.budget_bytes
        reg = cache.metrics
        assert reg.counter(m.SCOPE_TPU_RESIDENT, m.M_CACHE_EVICTIONS) == 2
        # LRU order: the first two admitted were evicted
        assert cache.lookup(keys[0], hists[0][:-1]) is None
        assert cache.lookup(keys[4], hists[4][:-1])[0] == "exact"
        assert reg.gauge_value(m.SCOPE_TPU_RESIDENT,
                               m.M_RESIDENT_BYTES) == cache.resident_bytes
        assert reg.gauge_value(m.SCOPE_TPU_RESIDENT,
                               m.M_RESIDENT_ENTRIES) == 3

    def test_oversized_budget_rejects_admission(self):
        cache = self._cache(budget_bytes=16)  # smaller than any row
        hists = generate_corpus("basic", num_workflows=1, seed=19,
                                target_events=20)
        s, rows = _replay_full([hists[0][:-1]])
        assert not cache.admit(("d", "w", "r"),
                               content_address(hists[0][:-1]),
                               cache.extract_row(s, 0), rows[0], 0)
        assert len(cache) == 0

    def test_replay_append_parity_and_readdress(self):
        cache = self._cache()
        hists = generate_corpus("concurrent_child", num_workflows=4,
                                seed=23, target_events=40)
        keys = [("d", f"w{i}", "r") for i in range(4)]
        _seed_cache(cache, keys, [h[:-1] for h in hists])
        items = [(k, cache.lookup(k, h)[1], h)
                 for k, h in zip(keys, hists)]
        results, report = cache.replay_append_report(items)
        for h, res in zip(hists, results):
            assert res.ok and not res.escalated
            assert (res.payload == _oracle_row(h)).all()
        # entries re-addressed at the full history: exact hits now
        for k, h in zip(keys, hists):
            assert cache.lookup(k, h)[0] == "exact"
        assert report.events_appended == sum(
            len(h[-1].events) for h in hists)


# ---------------------------------------------------------------------------
# a bulk chunk's verified rows pinned as VIEWS of the chunk's state
# (admit_chunk) against the same rows pinned one by one (admit)
# ---------------------------------------------------------------------------


def _seed_from_chunk(cache, keys, prefix_hists, how, rows_of=None):
    """Pin rows `rows_of` (default: all) of ONE replayed chunk: as views
    of its state or, the reference, sliced and admitted row by row.
    Returns the chunk's state."""
    s, rows = _replay_full(prefix_hists)
    branch = np.asarray(s.current_branch)
    assert (np.asarray(s.error) == 0).all()
    picked = list(range(len(keys))) if rows_of is None else list(rows_of)
    if how == "views":
        assert cache.admit_chunk(s, [
            (keys[i], content_address(prefix_hists[i]), i, rows[i],
             int(branch[i])) for i in picked]) == len(picked)
    else:
        for i in picked:
            assert cache.admit(keys[i], content_address(prefix_hists[i]),
                               cache.extract_row(s, i), rows[i],
                               int(branch[i]))
    return s


def _view_counters(cache):
    reg = cache.metrics
    return (reg.counter(m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_VIEW_ROWS),
            reg.counter(m.SCOPE_TPU_RESIDENT,
                        m.M_RESIDENT_VIEWS_MATERIALISED))


def _state_nbytes(state):
    import jax

    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(state))


def _counted(cache):
    """The pool's byte count made again from its entries and the chunks
    their live views pin: the stated budget rule."""
    total = 0
    for sl in cache._slices:
        pins = {id(e._charge): e._charge for e in sl.values()
                if e._charge is not None}
        total += sum(e.nbytes for e in sl.values())
        total += sum(pin.nbytes for pin in pins.values())
    return total


@pytest.mark.parametrize("how", ["views", "rows"])
class TestChunkViews:
    N = 6

    def _case(self, suite="basic", seed=29, **kw):
        kw.setdefault("ladder", EscalationLadder(DEFAULT_LAYOUT))
        cache = ResidentStateCache(DEFAULT_LAYOUT, **kw)
        hists = generate_corpus(suite, num_workflows=self.N, seed=seed,
                                target_events=24)
        keys = [("d", f"w{i}", "r") for i in range(self.N)]
        return cache, keys, hists

    def test_lookups_serve_what_rows_serve(self, how):
        cache, keys, hists = self._case()
        _seed_from_chunk(cache, keys, [h[:-1] for h in hists], how)
        assert len(cache) == self.N
        for k, h in zip(keys[:3], hists):
            kind, entry = cache.lookup(k, h[:-1])
            assert kind == "exact"
            assert (entry.payload == _oracle_row(h[:-1])).all()
            assert entry.address == content_address(h[:-1])
            assert entry.rung == 0
        assert cache.lookup(keys[3], hists[3])[0] == "suffix"
        mutated = list(hists[4][:-2]) + [hists[4][-1]]
        assert cache.lookup(keys[4], mutated) is None      # stale: dropped
        assert cache.lookup(keys[4], hists[4][:-1]) is None
        assert cache.lookup(keys[5], hists[5][:1],
                            authoritative=False) is None   # prefix: kept
        assert cache.lookup(keys[5], hists[5][:-1])[0] == "exact"
        reg = cache.metrics
        assert reg.counter(m.SCOPE_TPU_RESIDENT, m.M_CACHE_HITS) == 4
        assert reg.counter(m.SCOPE_TPU_RESIDENT,
                           m.M_CACHE_INVALIDATIONS) == 1
        # an exact hit, a suffix LOOKUP and an invalidation read no state
        assert _view_counters(cache) == (
            (self.N, 0) if how == "views" else (0, 0))
        stats = cache.stats()
        assert stats["view_rows"] == (self.N if how == "views" else 0)
        assert stats["view_entries"] == (self.N - 1 if how == "views" else 0)
        assert stats["views_materialised"] == 0

    def test_suffix_append_from_a_view_is_a_rows_append(self, how):
        cache, keys, hists = self._case("concurrent_child", seed=31)
        _seed_from_chunk(cache, keys, [h[:-1] for h in hists], how)
        appended = [0, 2, 3]
        items = [(keys[i], cache.lookup(keys[i], hists[i])[1], hists[i])
                 for i in appended]
        results = cache.replay_append(items)
        payload_nbytes = DEFAULT_LAYOUT.width * 8
        for i, res in zip(appended, results):
            assert res.ok and not res.escalated and res.rung == 0
            assert (res.payload == _oracle_row(hists[i])).all()
            oracle = StateBuilder().replay_history(hists[i])
            assert res.branch == oracle.version_histories.current_index
            kind, entry = cache.lookup(keys[i], hists[i])
            assert kind == "exact"          # re-admitted at the new address
            # as a view of the append's own final state
            assert entry.is_view and entry.nbytes == payload_nbytes
            assert (entry.payload == res.payload).all()
        # only the rows appended to were materialised (for the launch),
        # then re-pinned as views; the rest still view the seeding chunk
        seeded = self.N if how == "views" else 0
        assert _view_counters(cache) == (
            seeded + len(appended), len(appended) if seeded else 0)
        assert cache.stats()["view_entries"] == (
            self.N if how == "views" else len(appended))
        assert cache.resident_bytes == _counted(cache)

    def test_bytes_follow_the_stated_rule(self, how):
        """A chunk counts whole, once, while a view of it is live (the
        rows nobody admitted included); a view adds its payload row; a
        materialised row counts as a row."""
        cache, keys, hists = self._case()
        admitted = [0, 1, 2, 4]   # rows 3 and 5 are pinned but not viewed
        state = _seed_from_chunk(cache, keys, [h[:-1] for h in hists], how,
                                 rows_of=admitted)
        row_nbytes = cache._row_nbytes(DEFAULT_LAYOUT)
        payload_nbytes = DEFAULT_LAYOUT.width * 8
        chunk_nbytes = _state_nbytes(state)
        assert chunk_nbytes == self.N * (row_nbytes - payload_nbytes)
        k = len(admitted)

        def holds(expected):
            assert cache.resident_bytes == expected == _counted(cache)
            assert cache.stats()["resident_bytes"] == expected
            assert cache.metrics.gauge_value(
                m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_BYTES) == expected
            assert expected <= cache.budget_bytes

        if how == "rows":
            holds(k * row_nbytes)
            return
        holds(chunk_nbytes + k * payload_nbytes)
        # one row read: it counts as a row, the chunk still whole
        first = cache.entry_for(keys[0])
        assert first.is_view
        assert _state_nbytes(first.state) == row_nbytes - payload_nbytes
        assert not first.is_view and first.nbytes == row_nbytes
        holds(chunk_nbytes + (k - 1) * payload_nbytes + row_nbytes)
        # one view invalidated: its payload row leaves, the chunk stays
        assert cache.invalidate(keys[1])
        holds(chunk_nbytes + (k - 2) * payload_nbytes + row_nbytes)
        # the last views read: the chunk leaves the count
        for i in (2, 4):
            cache.entry_for(keys[i]).state
        holds((k - 1) * row_nbytes)
        assert _view_counters(cache) == (k, 3)

    def test_a_chunk_over_its_budget_is_admitted_row_by_row(self, how):
        probe = ResidentStateCache(DEFAULT_LAYOUT)
        row_nbytes = probe._row_nbytes(DEFAULT_LAYOUT)
        cache, keys, hists = self._case(budget_bytes=3 * row_nbytes + 1)
        _seed_from_chunk(cache, keys, [h[:-1] for h in hists], how)
        # six rows of state do not fit three rows of budget: no view
        assert len(cache) == 3
        assert cache.resident_bytes == 3 * row_nbytes <= cache.budget_bytes
        assert _view_counters(cache) == (0, 0)
        assert cache.metrics.counter(m.SCOPE_TPU_RESIDENT,
                                     m.M_CACHE_EVICTIONS) == 3
        assert cache.lookup(keys[0], hists[0][:-1]) is None
        assert cache.lookup(keys[5], hists[5][:-1])[0] == "exact"

    def test_materialising_evicts_rather_than_pass_the_budget(self, how):
        probe = ResidentStateCache(DEFAULT_LAYOUT)
        row_nbytes = probe._row_nbytes(DEFAULT_LAYOUT)
        payload_nbytes = DEFAULT_LAYOUT.width * 8
        # the chunk and its six payload rows fit with one row to spare
        budget = self.N * row_nbytes + (row_nbytes - payload_nbytes)
        cache, keys, hists = self._case(budget_bytes=budget)
        _seed_from_chunk(cache, keys, [h[:-1] for h in hists], how)
        assert len(cache) == self.N
        for i in (5, 4):
            entry = cache.entry_for(keys[i])
            assert (np.asarray(entry.state.error) == 0).all()
            assert cache.resident_bytes <= budget
            assert cache.resident_bytes == _counted(cache)
        if how == "views":
            # the first row read filled the budget; the second pushed the
            # four cold views out in a run, and the chunk went with the
            # last of them
            assert sorted(cache.keys()) == sorted([keys[4], keys[5]])
            assert cache.resident_bytes == 2 * row_nbytes
            assert cache.metrics.counter(m.SCOPE_TPU_RESIDENT,
                                         m.M_CACHE_EVICTIONS) == 4
        else:
            assert len(cache) == self.N


@pytest.mark.parametrize("how", ["views", "rows"])
def test_a_sweep_over_views_writes_the_blobs_rows_write(how):
    """The snapshot writer reads `entry.state`: over a pool of views it
    persists, key for key, the packed W=1 slice it persisted before."""
    from cadence_tpu.engine.persistence import Stores
    from cadence_tpu.engine.snapshot import pack_state_row
    from cadence_tpu.engine.tpu_engine import TPUReplayEngine

    hists = generate_corpus("timer_retry", num_workflows=4, seed=47,
                            target_events=24)
    stores = Stores()
    keys = []
    for h in hists:
        key = (h[0].domain_id, h[0].workflow_id, h[0].run_id)
        for b in h:
            stores.history.append_batch(*key, list(b.events))
        stores.execution.upsert_workflow(StateBuilder().replay_history(h))
        keys.append(key)
    tpu = TPUReplayEngine(stores)
    if how == "views":
        assert tpu.verify_all().ok      # seeds the pool a chunk at a time
        assert tpu.resident.stats()["view_entries"] == len(keys)
    else:
        _seed_from_chunk(tpu.resident, keys, hists, "rows")
    full, _rows = _replay_full(hists)
    assert tpu.snapshot_sweep(force=True).written == len(keys)
    for i, key in enumerate(keys):
        assert stores.snapshot.get(key).state_blob == pack_state_row(
            tpu.resident.extract_row(full, i)), key
    assert _view_counters(tpu.resident) == (
        (len(keys), len(keys)) if how == "views" else (0, 0))
    assert tpu.resident.stats()["view_entries"] == 0


@pytest.mark.parametrize("gone", ["evicted", "invalidated", "materialised",
                                  "cleared", "replaced"])
def test_a_chunk_is_collectable_once_its_last_view_is_gone(gone):
    import gc
    import weakref

    import jax

    cache = ResidentStateCache(DEFAULT_LAYOUT,
                               ladder=EscalationLadder(DEFAULT_LAYOUT))
    hists = generate_corpus("basic", num_workflows=4, seed=37,
                            target_events=20)
    keys = [("d", f"w{i}", "r") for i in range(4)]
    prefix = [h[:-1] for h in hists]
    state = _seed_from_chunk(cache, keys, prefix, "views")
    chunk_nbytes = _state_nbytes(state)
    leaf = weakref.ref(jax.tree_util.tree_leaves(state)[0])
    del state
    gc.collect()
    assert leaf() is not None            # the views hold the chunk
    if gone == "evicted":
        # a second chunk that fits only alone: the first one's views leave
        # the LRU in a run
        cache.budget_bytes = chunk_nbytes + 4 * DEFAULT_LAYOUT.width * 8 + 8
        other = [("d", f"x{i}", "r") for i in range(4)]
        _seed_from_chunk(cache, other, prefix, "views")
        assert sorted(cache.keys()) == sorted(other)
        assert cache.metrics.counter(m.SCOPE_TPU_RESIDENT,
                                     m.M_CACHE_EVICTIONS) == 4
        assert cache.resident_bytes <= cache.budget_bytes
    for n, key in enumerate(keys):
        if gone != "evicted":
            assert leaf() is not None, n  # one live view is enough
        if gone == "invalidated":
            assert cache.invalidate(key)
        elif gone == "materialised":
            cache.entry_for(key).state
        elif gone == "replaced":
            s1, rows1 = _replay_full([prefix[n]])
            cache.admit(key, content_address(prefix[n]),
                        cache.extract_row(s1, 0), rows1[0], 0)
    if gone == "cleared":
        cache.clear()
    gc.collect()
    assert leaf() is None
    assert cache.resident_bytes == _counted(cache)
    assert cache.stats()["view_entries"] == (4 if gone == "evicted" else 0)


def test_an_evicted_view_still_held_by_a_caller_reads_its_state():
    """A suffix item looked up before a concurrent admission evicted it
    keeps its chunk: the state is there, and the pool counts nothing."""
    cache = ResidentStateCache(DEFAULT_LAYOUT)
    hists = generate_corpus("basic", num_workflows=2, seed=41,
                            target_events=20)
    keys = [("d", f"w{i}", "r") for i in range(2)]
    _seed_from_chunk(cache, keys, hists, "views")
    held = cache.entry_for(keys[0])
    cache.clear()
    assert held.is_view and cache.resident_bytes == 0
    assert (np.asarray(held.state.error) == 0).all()
    assert cache.resident_bytes == 0 and len(cache) == 0
    assert _view_counters(cache) == (2, 1)


def _views_made_by(made_by, cache, keys, hists):
    """Pin every key's whole-history row as a view: of the chunk that
    replayed the histories (`admit_chunk`), or of the final state of an
    append of each history's last batch to its prefix row. Returns a
    state whose row i is key i's."""
    if made_by == "admit_chunk":
        return _seed_from_chunk(cache, keys, hists, "views")
    _seed_cache(cache, keys, [h[:-1] for h in hists])
    results = cache.replay_append(
        [(k, cache.lookup(k, h)[1], h) for k, h in zip(keys, hists)])
    assert all(r.ok for r in results)
    return _replay_full(hists)[0]


@pytest.mark.parametrize("made_by", ["admit_chunk", "append"])
def test_views_under_readers_and_invalidations_at_once(made_by):
    """Eight threads read every view's state (a verified chunk's, or an
    append's re-pinned rows) while one invalidates and re-admits: each
    view is sliced once, every reader sees the row the eager slice gives,
    and the count is the rule's at the end."""
    import sys
    import threading

    n = 12
    cache = ResidentStateCache(DEFAULT_LAYOUT)
    hists = generate_corpus("basic", num_workflows=n, seed=43,
                            target_events=20)
    keys = [("d", f"w{i}", "r") for i in range(n)]
    state = _views_made_by(made_by, cache, keys, hists)
    assert cache.stats()["view_entries"] == n
    expected = [np.asarray(cache.extract_row(state, i).next_event_id)
                for i in range(n)]
    entries = [cache.entry_for(k) for k in keys]
    failures = []

    def read(order):
        try:
            for i in order:
                got = np.asarray(entries[i].state.next_event_id)
                if not (got == expected[i]).all():
                    failures.append(("row", i))
        except Exception as exc:  # a reader must never raise
            failures.append(("raised", repr(exc)))

    def churn():
        try:
            for i in range(0, n, 3):
                cache.invalidate(keys[i])
                s1, rows1 = _replay_full([hists[i]])
                cache.admit(keys[i], content_address(hists[i]),
                            cache.extract_row(s1, 0), rows1[0], 0)
        except Exception as exc:
            failures.append(("churn", repr(exc)))

    rng = random.Random(5)
    threads = [threading.Thread(target=read,
                                args=(rng.sample(range(n), n),))
               for _ in range(8)] + [threading.Thread(target=churn)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert failures == []
    assert _view_counters(cache) == (n, n)      # once each, never twice
    assert len(cache) == n and cache.stats()["view_entries"] == 0
    assert cache.resident_bytes == _counted(cache) \
        == n * cache._row_nbytes(DEFAULT_LAYOUT)


# ---------------------------------------------------------------------------
# an append's base-rung rows re-pinned as views of the append's final state
# ---------------------------------------------------------------------------


def _append_last_batches(cache, n=5, suite="basic", seed=59):
    """Seed n W=1 prefix rows, then append each history's last batch:
    (keys, hists, the append's results)."""
    hists = generate_corpus(suite, num_workflows=n, seed=seed,
                            target_events=32)
    keys = [("d", f"w{i}", "r") for i in range(n)]
    _seed_cache(cache, keys, [h[:-1] for h in hists])
    results = cache.replay_append(
        [(k, cache.lookup(k, h)[1], h) for k, h in zip(keys, hists)])
    return keys, hists, results


def _fields(results):
    return [(r.ok, r.payload.tobytes(), r.branch, r.error, r.rung,
             r.escalated) for r in results]


def test_an_appends_rows_come_back_as_views_of_one_pin(monkeypatch):
    launched = []
    real = resident_mod._slice_row
    monkeypatch.setattr(resident_mod, "_slice_row",
                        lambda s, i: launched.append(i) or real(s, i))
    cache = ResidentStateCache(DEFAULT_LAYOUT,
                               ladder=EscalationLadder(DEFAULT_LAYOUT))
    n = 5
    keys, hists, results = _append_last_batches(cache, n)
    # the seeding sliced its n rows; the append slices none
    assert _row_slices(cache) == len(launched) == n
    assert _view_counters(cache) == (n, 0)
    for h, res in zip(hists, results):
        assert res.ok and not res.escalated and res.rung == 0
        assert (res.payload == _oracle_row(h)).all()
    entries = [cache.lookup(k, h)[1] for k, h in zip(keys, hists)]
    assert all(e.is_view and e.rung == 0 for e in entries)
    (pin,) = {id(e._chunk): e._chunk for e in entries}.values()
    assert [e._row for e in entries] == list(range(n))
    assert cache.resident_bytes == _counted(cache) \
        == pin.nbytes + n * DEFAULT_LAYOUT.width * 8
    assert cache.stats()["view_entries"] == n
    # the pin is the append's final state: row i is history i's whole row
    import jax

    full, _rows = _replay_full(hists)
    for leaf, want in zip(jax.tree_util.tree_leaves(pin.state),
                          jax.tree_util.tree_leaves(full)):
        assert (np.asarray(leaf)[:n] == np.asarray(want)).all()


@pytest.mark.parametrize("suite", ["basic", "echo_signal", "timer_retry",
                                   "concurrent_child", "ndc"])
def test_an_append_from_re_pinned_views_is_byte_identical(suite):
    """Two appends in a row: the second replays from the views the first
    re-pinned, and lands on a full replay's payloads, CRCs and branches."""
    cache = ResidentStateCache(DEFAULT_LAYOUT,
                               ladder=EscalationLadder(DEFAULT_LAYOUT))
    n = 4
    hists = generate_corpus(suite, num_workflows=n, seed=61,
                            target_events=32)
    keys = [("d", f"w{i}", "r") for i in range(n)]
    _seed_cache(cache, keys, [h[:-2] for h in hists])
    for cut in (-1, None):
        stage = [h[:cut] for h in hists]
        results = cache.replay_append(
            [(k, cache.lookup(k, h)[1], h) for k, h in zip(keys, stage)])
        assert all(r.ok and r.rung == 0 for r in results)
        assert all(cache.lookup(k, h)[1].is_view
                   for k, h in zip(keys, stage))
    full, rows = _replay_full(hists)
    got = np.stack([r.payload for r in results])
    assert got.tobytes() == rows.tobytes()
    assert (crc32_of_rows(got) == crc32_of_rows(rows)).all()
    assert [r.branch for r in results] == \
        np.asarray(full.current_branch).tolist()
    # the seeding's n slices, and the second launch's read of the views
    # the first re-pinned
    assert _row_slices(cache) == 2 * n
    assert _view_counters(cache) == (2 * n, n)


def test_a_re_pinned_chunk_too_large_for_its_slice_is_admitted_row_by_row():
    """Room for five rows and not for the append's eight-row final state
    beside their payloads: the rows are sliced and admitted one by one,
    and the append's results read as they do when the views fit."""
    probe = ResidentStateCache(DEFAULT_LAYOUT)
    row_nbytes = probe._row_nbytes(DEFAULT_LAYOUT)
    n = 5
    _keys, _hists, viewed = _append_last_batches(
        ResidentStateCache(DEFAULT_LAYOUT,
                           ladder=EscalationLadder(DEFAULT_LAYOUT),
                           registry=m.MetricsRegistry()), n)
    cache = ResidentStateCache(DEFAULT_LAYOUT,
                               ladder=EscalationLadder(DEFAULT_LAYOUT),
                               budget_bytes=n * row_nbytes + 1)
    keys, hists, results = _append_last_batches(cache, n)
    assert _fields(results) == _fields(viewed)
    assert _view_counters(cache) == (0, 0)
    assert _row_slices(cache) == 2 * n
    assert len(cache) == n and cache.stats()["view_entries"] == 0
    assert cache.resident_bytes == _counted(cache) == n * row_nbytes
    for k, h in zip(keys, hists):
        kind, entry = cache.lookup(k, h)
        assert kind == "exact" and not entry.is_view
        assert entry.nbytes == row_nbytes


# ---------------------------------------------------------------------------
# capacity escalation: widen on overflowing append, stay resident,
# re-narrow once the load drains
# ---------------------------------------------------------------------------


def _overflow_chain():
    """A 3-stage history: prefix pins 12 pending activities (fits the
    base K=16); append-1 schedules 10 more (transient 22 -> TABLE_OVERFLOW
    at base, fits 2K) and completes the 8 OLDEST (final 14 <= 16 but
    high table slots stay occupied -> not narrowable); append-2 completes
    the 6 activities sitting in the widened slots (narrowable again).
    Returns (prefix, after_append1, after_append2) batch lists."""
    from cadence_tpu.gen.corpus import (
        HistoryWriter,
        _begin_decision_completed_batch,
        _run_decision,
        _schedule_decision,
        _start,
    )

    w = HistoryWriter(workflow_id="ovf")
    _start(w, random.Random(0))
    cyc = _run_decision(w, 2)
    completed = _begin_decision_completed_batch(w, cyc)
    prefix_acts = [w.add(
        EventType.ActivityTaskScheduled, activity_id=f"p{i}",
        task_list=TL, schedule_to_start_timeout_seconds=60,
        schedule_to_close_timeout_seconds=120,
        start_to_close_timeout_seconds=60, heartbeat_timeout_seconds=0,
    ) for i in range(12)]
    sched = _schedule_decision(w, in_batch=True)
    w.end_batch()
    prefix = list(w.batches)

    def complete(act_ev):
        started = w.single(EventType.ActivityTaskStarted,
                           scheduled_event_id=act_ev.id,
                           request_id=f"poll-{act_ev.id}")
        w.begin_batch()
        w.add(EventType.ActivityTaskCompleted, scheduled_event_id=act_ev.id,
              started_event_id=started.id)
        w.end_batch()

    cyc = _run_decision(w, sched)
    _begin_decision_completed_batch(w, cyc)
    flood_acts = [w.add(
        EventType.ActivityTaskScheduled, activity_id=f"f{i}",
        task_list=TL, schedule_to_start_timeout_seconds=60,
        schedule_to_close_timeout_seconds=120,
        start_to_close_timeout_seconds=60, heartbeat_timeout_seconds=0,
    ) for i in range(10)]
    _schedule_decision(w, in_batch=True)
    w.end_batch()
    for ev in prefix_acts[:8]:  # oldest slots free; widened slots stay
        complete(ev)
    after_append1 = list(w.batches)

    # the 6 flood activities in widened slots (base indices >= 16 were
    # taken by flood acts 4..9) drain -> the state can re-narrow
    for ev in flood_acts[4:]:
        complete(ev)
    after_append2 = list(w.batches)
    return prefix, after_append1, after_append2


class TestResidentLadder:
    def test_overflowing_append_widens_and_renarrows(self):
        cache = ResidentStateCache(DEFAULT_LAYOUT,
                                   ladder=EscalationLadder(DEFAULT_LAYOUT))
        prefix, append1, append2 = _overflow_chain()
        key = ("d", "ovf", "r")
        _seed_cache(cache, [key], [prefix])
        reg = cache.metrics

        # append-1 overflows the base tables: the ladder widens the
        # RESIDENT state, replays only the suffix, stays resident widened
        items = [(key, cache.lookup(key, append1)[1], append1)]
        res = cache.replay_append(items)[0]
        assert res.ok and res.escalated and res.rung == 1
        assert (res.payload == _oracle_row(append1)).all()
        kind, entry = cache.lookup(key, append1)
        assert kind == "exact" and entry.rung == 1
        assert reg.counter(m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_WIDENED) == 1
        assert reg.counter(m.SCOPE_TPU_FALLBACK, m.M_LADDER_RESOLVED) >= 1
        assert cache.stats()["widened_entries"] == 1

        # append-2 replays against the WIDENED resident state, drains the
        # widened slots, and the state re-narrows to the base footprint
        items = [(key, entry, append2)]
        res = cache.replay_append(items)[0]
        assert res.ok and res.rung == 0
        assert (res.payload == _oracle_row(append2)).all()
        kind, entry = cache.lookup(key, append2)
        assert kind == "exact" and entry.rung == 0
        assert reg.counter(m.SCOPE_TPU_RESIDENT,
                           m.M_RESIDENT_NARROWED) == 1
        assert cache.stats()["widened_entries"] == 0

    def test_a_widened_row_renarrows_through_the_row_path(self):
        """Rows of a widened rung are re-pinned one by one, the only path
        that narrows: no view is made of a widened state."""
        cache = ResidentStateCache(DEFAULT_LAYOUT,
                                   ladder=EscalationLadder(DEFAULT_LAYOUT))
        prefix, append1, append2 = _overflow_chain()
        key = ("d", "ovf", "r")
        _seed_cache(cache, [key], [prefix])
        res = cache.replay_append(
            [(key, cache.lookup(key, append1)[1], append1)])[0]
        assert res.ok and res.escalated and res.rung == 1
        entry = cache.lookup(key, append1)[1]
        assert not entry.is_view and entry.rung == 1
        res = cache.replay_append([(key, entry, append2)])[0]
        assert res.ok and res.rung == 0 and not res.escalated
        assert (res.payload == _oracle_row(append2)).all()
        kind, entry = cache.lookup(key, append2)
        assert kind == "exact" and entry.rung == 0 and not entry.is_view
        assert entry.nbytes == cache._row_nbytes(DEFAULT_LAYOUT)
        # the seeding's slice, the escalated row's, the narrowed row's
        assert _row_slices(cache) == 3
        assert _view_counters(cache) == (0, 0)
        assert cache.metrics.counter(m.SCOPE_TPU_RESIDENT,
                                     m.M_RESIDENT_NARROWED) == 1

    def test_no_ladder_falls_back_cleanly(self):
        cache = ResidentStateCache(DEFAULT_LAYOUT, ladder=None)
        prefix, append1, _ = _overflow_chain()
        key = ("d", "ovf", "r")
        _seed_cache(cache, [key], [prefix])
        items = [(key, cache.lookup(key, append1)[1], append1)]
        res = cache.replay_append(items)[0]
        assert not res.ok
        assert len(cache) == 0  # invalidated for oracle arbitration


# ---------------------------------------------------------------------------
# pipelined executor integration: suffix-only packing at depth >= 2
# ---------------------------------------------------------------------------


class TestExecutorIntegration:
    def test_suffix_chunks_through_pipeline_depth3(self):
        cache = ResidentStateCache(
            DEFAULT_LAYOUT, ladder=EscalationLadder(DEFAULT_LAYOUT),
            chunk_workflows=4, pipeline_depth=3)
        hists = generate_corpus("basic", num_workflows=12, seed=29,
                                target_events=48)
        keys = [("d", f"w{i}", "r") for i in range(12)]
        _seed_cache(cache, keys, [h[:-1] for h in hists])
        items = [(k, cache.lookup(k, h)[1], h)
                 for k, h in zip(keys, hists)]
        results, report = cache.replay_append_report(items)
        for h, res in zip(hists, results):
            assert res.ok
            assert (res.payload == _oracle_row(h)).all()
        # 12 items / chunk 4 = 3 chunks, each packed to the SUFFIX event
        # axis (pow2 floor 16), not the 48-event history
        shapes = report.chunk_shapes
        assert len(shapes) == 3
        assert all(e <= 16 for _, e in shapes)

    def test_append_shapes_independent_of_history_length(self):
        """The O(new events) contract, structurally: appending equal-size
        suffixes to SHORT and LONG histories launches identical suffix
        corpus shapes — history length never enters the append cost."""
        shapes = {}
        for label, target in (("short", 24), ("long", 160)):
            cache = ResidentStateCache(
                DEFAULT_LAYOUT, ladder=EscalationLadder(DEFAULT_LAYOUT))
            hists = generate_corpus("basic", num_workflows=6, seed=31,
                                    target_events=target)
            keys = [("d", f"w{i}-{label}", "r") for i in range(6)]
            _seed_cache(cache, keys, [h[:-1] for h in hists])
            items = [(k, cache.lookup(k, h)[1], h)
                     for k, h in zip(keys, hists)]
            results, report = cache.replay_append_report(items)
            for h, res in zip(hists, results):
                assert res.ok
                assert (res.payload == _oracle_row(h)).all()
            shapes[label] = report.chunk_shapes
        assert shapes["short"] == shapes["long"]


# ---------------------------------------------------------------------------
# an append's legs as spans, once a chunk under the caller's span; the
# pool's W=1 row slices as a count
# ---------------------------------------------------------------------------

LEGS = ("resident.launch", "resident.device-wait", "resident.readmit")


def _flush_of_two_chunks(_request, _monkeypatch):
    """A serving flush of six suffix appends, three a chunk."""
    h = _Harness(workflows=6)
    for k in h.keys:
        h.counts[k] = len(h.by_key[k]) - 1
        h.submit(k)
    h.flush()   # cold admits
    h.sched.resident.chunk_workflows = 3
    tickets = []
    for k in h.keys:
        h.counts[k] += 1
        tickets.append(h.submit(k))
    tracing.DEFAULT_TRACER.reset()
    with h.sched._prof.leg(m.M_PROFILE_SERVING, span="serving.flush"):
        h.flush()
    assert all(t.result(timeout=5).path == "suffix" for t in tickets)
    return ("serving.flush",)


def _recovery_of_two_chunks(request, monkeypatch):
    """A small warm recovery whose suffix rows make two chunks a pass."""
    path, histories, cuts, _sweep = request.getfixturevalue("wal")
    _exact, suffix, _none = _kinds(histories, cuts)
    assert suffix >= 6
    monkeypatch.setenv(resident_mod.CHUNK_ENV, str((suffix + 1) // 2))
    tracing.DEFAULT_TRACER.reset()
    _stores, report = _recover(path)
    assert report.ok
    assert report.suffix_rows == {"rebuild": suffix, "verify": suffix}
    return ("rebuild.suffix-replay", "verify.suffix-replay")


@pytest.mark.parametrize("run", [_flush_of_two_chunks,
                                 _recovery_of_two_chunks],
                         ids=["serving.flush", "rebuild.suffix-replay"])
def test_an_append_lays_its_legs_once_a_chunk_under_the_callers_span(
        run, request, monkeypatch):
    callers = run(request, monkeypatch)
    spans = tracing.DEFAULT_TRACER.finished_spans()
    ours = [s for s in spans if s.operation.startswith("resident.")]
    # six a call whatever its rows, all of them directly under the caller
    assert len(ours) == 6 * len(callers)
    for name in callers:
        (outer,) = [s for s in spans if s.operation == name]
        legs = [s for s in ours if s.parent_id == outer.span_id]
        assert Counter(s.operation for s in legs) == {leg: 2 for leg in LEGS}
        by_leg = [sorted((s for s in legs if s.operation == leg),
                         key=lambda s: s.start_ns) for leg in LEGS]
        for launch, wait, readmit in zip(*by_leg):
            assert launch.start_ns + launch.duration_ns <= wait.start_ns
            assert wait.start_ns + wait.duration_ns <= readmit.start_ns


def _row_slices(cache):
    return cache.stats()["row_slices"]


@pytest.mark.parametrize("how", ["views", "rows"])
def test_row_slices_count_the_pools_slice_row_launches(how, monkeypatch):
    """`tpu.resident/row-slices` = the `slice_row` launches: one a row
    sliced and admitted, one a view's FIRST read; an append's rows are
    re-pinned as views of its final state, and slice nothing."""
    launched = []
    real = resident_mod._slice_row
    monkeypatch.setattr(resident_mod, "_slice_row",
                        lambda s, i: launched.append(i) or real(s, i))
    cache = ResidentStateCache(DEFAULT_LAYOUT,
                               ladder=EscalationLadder(DEFAULT_LAYOUT),
                               chunk_workflows=2)
    n = 5
    hists = generate_corpus("basic", num_workflows=n, seed=29,
                            target_events=32)
    keys = [("d", f"w{i}", "r") for i in range(n)]
    _seed_from_chunk(cache, keys, [h[:-1] for h in hists], how)
    assert _row_slices(cache) == len(launched) == (0 if how == "views"
                                                   else n)
    seeded = len(launched)
    if how == "views":
        # a view's first read slices its row; a second read does not
        entry = cache.entry_for(keys[0])
        assert entry.state is entry.state
        assert _row_slices(cache) == len(launched) == 1
    before = len(launched)
    items = [(k, cache.lookup(k, h)[1], h) for k, h in zip(keys, hists)]
    results, _report = cache.replay_append_report(items)
    assert all(r.ok for r in results)
    # each view still unread is sliced once for the launch state; the
    # appended rows are sliced by nobody
    unread = n - 1 if how == "views" else 0
    assert len(launched) - before == unread
    assert _row_slices(cache) == len(launched) == seeded + (
        1 if how == "views" else 0) + unread


def test_serving_stats_carry_the_pools_row_slices():
    h = _Harness(workflows=3)
    seen = [h.sched.stats()["row_slices"]]
    assert seen == [0]
    # the cold admits, then two suffix appends each: the first re-pins
    # its rows as views and slices nothing, the second's launch reads
    # them and slices each once
    for counts in (-2, -1, 0):
        for k in h.keys:
            h.counts[k] = len(h.by_key[k]) + counts
            h.submit(k)
        h.flush()
        seen.append(h.sched.stats()["row_slices"])
    assert seen == [0, 3, 3, 6]
    assert h.sched.resident.stats()["row_slices"] == 6


# ---------------------------------------------------------------------------
# verify_all integration: invalidation on tail overwrite / reset / NDC
# ---------------------------------------------------------------------------


@pytest.fixture()
def box():
    from cadence_tpu.engine.onebox import Onebox
    b = Onebox(num_hosts=1, num_shards=4)
    b.frontend.register_domain(DOMAIN)
    return b


def _current_key(box, wf):
    domain_id = box.stores.domain.by_name(DOMAIN).domain_id
    run_id = box.stores.execution.get_current_run_id(domain_id, wf)
    return (domain_id, wf, run_id)


class TestVerifyAllResident:
    def test_tail_overwrite_invalidates_then_reverifies(self, box):
        """A retried-transaction tail overwrite (same event ids, new
        bytes) changes the last batch's CRC: the pinned entry must drop
        (counted) and the key re-verify through the full path — never
        served from the stale resident state."""
        import copy

        box.frontend.start_workflow_execution(DOMAIN, "wf-ow", "t", TL)
        box.frontend.signal_workflow_execution(DOMAIN, "wf-ow", "first")
        box.pump_once()
        key = _current_key(box, "wf-ow")
        assert box.tpu.verify_all().ok
        assert box.tpu.verify_all().resident  # pinned and serving

        # overwrite the tail batch in place: same ids and event types
        # (the live state's payload is unchanged — only the BYTES moved,
        # exactly what a retried transaction produces)
        batches = box.stores.history.read_batches(*key)
        tail = [copy.deepcopy(e) for e in batches[-1]]
        for e in tail:
            if e.event_type == EventType.WorkflowExecutionSignaled:
                e.attrs = dict(e.attrs, signal_name="rewritten")
        box.stores.history.append_batch(*key, tail)

        reg = box.metrics
        inval0 = reg.counter(m.SCOPE_TPU_RESIDENT, m.M_CACHE_INVALIDATIONS)
        result = box.tpu.verify_all()
        assert result.ok  # payload identical; bytes differ
        assert key not in result.resident
        assert reg.counter(m.SCOPE_TPU_RESIDENT,
                           m.M_CACHE_INVALIDATIONS) == inval0 + 1
        # re-seeded from the full replay: warm again
        assert key in box.tpu.verify_all().resident

    def test_reset_stays_byte_identical(self, box):
        """Reset rewrites the world (new run forked at the decision
        boundary, base run terminated): every key must still verify
        byte-identically — the resident cache may serve only what the
        content address proves unchanged."""
        from cadence_tpu.models.deciders import SignalDecider
        from tests.taskpoller import TaskPoller

        box.frontend.start_workflow_execution(DOMAIN, "wf-rst", "signal", TL)
        poller = TaskPoller(box, DOMAIN, TL,
                            {"wf-rst": SignalDecider(expected_signals=3)})
        poller.drain()
        key = _current_key(box, "wf-rst")
        box.frontend.signal_workflow_execution(DOMAIN, "wf-rst", "s-1")
        poller.drain()
        assert box.tpu.verify_all().ok  # pin pre-reset states

        new_run = box.frontend.reset_workflow_execution(
            DOMAIN, "wf-rst", decision_finish_event_id=4, run_id=key[2],
            reason="resident-test")
        result = box.tpu.verify_all()
        assert result.ok
        # the forked new run is a fresh key: it cannot have been served
        # from the cache on its first verify
        new_key = (key[0], "wf-rst", new_run)
        assert new_key not in result.resident
        # base run's termination append and the new run both verified;
        # a second pass serves everything resident
        result2 = box.tpu.verify_all()
        assert result2.ok
        assert len(result2.resident) == result2.total

    def test_ndc_branch_switch_invalidates(self, box):
        """An NDC branch switch (current-branch pointer moves) makes the
        pinned single-lineage state wrong: the entry must invalidate and
        the key route through the full tree path."""
        box.frontend.start_workflow_execution(DOMAIN, "wf-ndc", "t", TL)
        box.pump_once()
        key = _current_key(box, "wf-ndc")
        assert box.tpu.verify_all().ok
        assert key in box.tpu.verify_all().resident

        hs = box.stores.history
        last_id = hs.read_events(*key)[-1].id
        hs.fork_branch(*key, source_branch=0, fork_event_id=last_id)
        hs.set_current_branch(*key, 1)

        reg = box.metrics
        inval0 = reg.counter(m.SCOPE_TPU_RESIDENT, m.M_CACHE_INVALIDATIONS)
        result = box.tpu.verify_all()
        assert key not in result.resident
        assert reg.counter(m.SCOPE_TPU_RESIDENT,
                           m.M_CACHE_INVALIDATIONS) == inval0 + 1
        # the live state still points at branch 0: the device's branch
        # arbitration must surface the disagreement, not the stale cache
        assert key in result.divergent

    def test_disable_env_forces_full_path(self, box, monkeypatch):
        from cadence_tpu.engine import resident as resident_mod

        box.frontend.start_workflow_execution(DOMAIN, "wf-off", "t", TL)
        assert box.tpu.verify_all().ok
        monkeypatch.setenv(resident_mod.ENABLE_ENV, "0")
        result = box.tpu.verify_all()
        assert result.ok and not result.resident


# ---------------------------------------------------------------------------
# rebuilder consult
# ---------------------------------------------------------------------------


class TestRebuilderResident:
    def test_rebuild_exact_then_suffix(self, box):
        box.frontend.start_workflow_execution(DOMAIN, "wf-rb", "t", TL)
        box.pump_once()
        key = _current_key(box, "wf-rb")
        assert box.tpu.verify_all().ok  # pins the state

        batches = box.stores.history.as_history_batches(*key)
        before = box.rebuilder.stats.resident
        ms = box.rebuilder.rebuild_one(batches)
        assert box.rebuilder.stats.resident == before + 1
        expected = payload_row(
            StateBuilder().replay_history(batches), DEFAULT_LAYOUT)
        got = payload_row(ms, DEFAULT_LAYOUT)
        got[STICKY_ROW_INDEX] = expected[STICKY_ROW_INDEX]
        assert (got == expected).all()

        # appended batch: the rebuild replays only the suffix
        box.frontend.signal_workflow_execution(DOMAIN, "wf-rb", "go")
        box.pump_once()
        batches = box.stores.history.as_history_batches(*key)
        ms2 = box.rebuilder.rebuild_one(batches)
        assert box.rebuilder.stats.resident == before + 2
        assert ms2.execution_info.signal_count == 1
        reg = box.metrics
        assert reg.counter(m.SCOPE_TPU_RESIDENT,
                           m.M_RESIDENT_SUFFIX_HITS) >= 1

    def test_rebuild_prefix_does_not_invalidate(self, box):
        """Rebuild at a reset point passes a PREFIX of the stored
        history: the lookup is non-authoritative — the pinned entry must
        survive for the next full verify."""
        box.frontend.start_workflow_execution(DOMAIN, "wf-pre", "t", TL)
        box.frontend.signal_workflow_execution(DOMAIN, "wf-pre", "x")
        box.pump_once()
        key = _current_key(box, "wf-pre")
        assert box.tpu.verify_all().ok
        batches = box.stores.history.as_history_batches(*key)
        box.rebuilder.rebuild_one(batches[:1])  # prefix rebuild
        assert key in box.tpu.verify_all().resident  # still pinned


# ---------------------------------------------------------------------------
# metrics surface
# ---------------------------------------------------------------------------


class TestMetricsSurface:
    def test_prometheus_series(self, box):
        box.frontend.start_workflow_execution(DOMAIN, "wf-m", "t", TL)
        assert box.tpu.verify_all().ok   # cold: miss + seed
        box.frontend.signal_workflow_execution(DOMAIN, "wf-m", "go")
        assert box.tpu.verify_all().ok   # suffix hit
        assert box.tpu.verify_all().ok   # exact hit
        text = box.metrics.to_prometheus()
        for series in (
            'cadence_hits_total{scope="tpu.resident"}',
            'cadence_misses_total{scope="tpu.resident"}',
            'cadence_suffix_hits_total{scope="tpu.resident"}',
            'cadence_events_appended_total{scope="tpu.resident"}',
            'cadence_resident_bytes{scope="tpu.resident"}',
            'cadence_resident_entries{scope="tpu.resident"}',
            'cadence_budget_bytes{scope="tpu.resident"}',
        ):
            assert series in text, series

    def test_servicehost_preregisters_resident_series(self):
        """A fresh host's /metrics must already expose the tpu.resident
        names (scraped as zero before the first verify)."""
        import urllib.request

        from cadence_tpu.rpc.cluster import launch

        cluster = launch(num_hosts=1, num_shards=2)
        try:
            (_name, port), = cluster.http_ports.items()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
                text = r.read().decode()
        finally:
            cluster.stop()
        assert 'cadence_invalidations_total{scope="tpu.resident"} 0' in text
        assert 'cadence_suffix_hits_total{scope="tpu.resident"} 0' in text
        assert 'cadence_view_rows_total{scope="tpu.resident"} 0' in text
        assert 'cadence_views_materialised_total{scope="tpu.resident"} 0' \
            in text
        assert 'cadence_host_stacked_rows_total{scope="tpu.resident"} 0' \
            in text
        assert 'cadence_row_slices_total{scope="tpu.resident"} 0' in text
        assert 'cadence_resident_bytes{scope="tpu.resident"} 0' in text
        assert 'cadence_budget_bytes{scope="tpu.resident"} 0' in text


# ---------------------------------------------------------------------------
# admin surface
# ---------------------------------------------------------------------------


class TestAdminResident:
    def test_admin_resident_rollup(self, box):
        from cadence_tpu.engine.admin import AdminHandler

        box.frontend.start_workflow_execution(DOMAIN, "wf-adm", "t", TL)
        admin = AdminHandler(box)
        assert admin.verify().ok
        assert admin.verify().ok
        info = admin.resident()
        assert info["enabled"] is True
        assert info["entries"] == 1
        assert info["hits"] >= 1
        assert 0.0 < info["hit_rate"] <= 1.0
        assert info["resident_bytes"] > 0
        assert info["budget_bytes"] > 0
