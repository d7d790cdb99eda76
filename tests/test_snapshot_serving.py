"""The snapshot writer on the served path: over a service host's remote
stores, and inside a serving flush.

Covers: a `Snapshotter` whose stores are `RemoteStores` against a loopback
store server writes its record, raises nothing, pays no gauge round trip
on the write path, and every render of its registry (GET /metrics, the
structured dump) reads the gauges from the store server's `stats()`, or
leaves them as they were once that server is gone; a write that raises
for one key of a flush fails no ticket, leaves every other key noted and
written, the flush's cold items served and every resident entry in
place, and is counted under `write-errors`; the two new `tpu.snapshot/*`
counters are pre-registered on a fresh service host.
"""
import threading

from cadence_tpu.core.checksum import STICKY_ROW_INDEX, payload_row
from cadence_tpu.engine.cache import batch_crc
from cadence_tpu.engine.persistence import Stores
from cadence_tpu.engine.snapshot import Snapshotter
from cadence_tpu.engine.tpu_engine import TPUReplayEngine
from cadence_tpu.gen.corpus import generate_corpus
from cadence_tpu.oracle.state_builder import StateBuilder
from cadence_tpu.utils import flightrecorder
from cadence_tpu.utils import metrics as m


def _append_and_commit(stores, key, batches):
    """Append `batches` to the run's history and upsert the oracle's
    mutable state over the whole stored history (what a commit leaves)."""
    for b in batches:
        stores.history.append_batch(*key, list(b.events))
    ms = StateBuilder().replay_history(
        stores.history.as_history_batches(*key))
    info = ms.execution_info
    info.domain_id, info.workflow_id, info.run_id = key
    stores.execution.upsert_workflow(ms)
    return ms


def _seed(stores, n, held_back=0, seed=17):
    """n generated runs, each stored but for its last `held_back` batches;
    returns [(key, history)]."""
    out = []
    for h in generate_corpus("basic", num_workflows=n, seed=seed,
                             target_events=24):
        key = (h[0].domain_id, h[0].workflow_id, h[0].run_id)
        _append_and_commit(stores, key, h[:len(h) - held_back])
        out.append((key, h))
    return out


def _counter(registry, name):
    return registry.counter(m.SCOPE_TPU_SNAPSHOT, name)


def _scrape(registry) -> str:
    """GET /metrics of a scrape server over `registry`."""
    import urllib.request

    from cadence_tpu.utils.scrape import ObservabilityHTTPServer

    server = ObservabilityHTTPServer(registry).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics", timeout=30) as r:
            assert r.status == 200
            return r.read().decode()
    finally:
        server.stop()


def _gauge_line(gauge: str, value) -> str:
    name = gauge.replace("-", "_")
    return f'cadence_{name}{{scope="{m.SCOPE_TPU_SNAPSHOT}"}} {int(value)}'


class TestWriterOverRemoteStores:
    def test_writes_a_record_and_reads_gauges_from_the_store_server(self):
        from cadence_tpu.rpc.client import RemoteStores
        from cadence_tpu.rpc.storeserver import StoreServer

        stores = Stores()
        ((key, _h),) = _seed(stores, n=1)
        server = StoreServer(("127.0.0.1", 0), stores)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        try:
            tpu = TPUReplayEngine(stores)
            assert tpu.verify_all().ok  # the resident row to write
            registry = m.MetricsRegistry()
            remote = RemoteStores(server.server_address, metrics=registry)
            snapper = Snapshotter(remote, tpu.resident, tpu.pack_cache,
                                  tpu.layout, registry=registry,
                                  min_events=1, every_events=1)
            stats_calls = []
            real_stats = stores.snapshot.stats

            def stats():
                stats_calls.append(1)
                return real_stats()

            stores.snapshot.stats = stats
            assert snapper.maybe_snapshot(key)  # raises nothing
            assert stats_calls == []  # no gauge round trip on a write
            assert _counter(registry, m.M_SNAP_WRITES) == 1
            assert _counter(registry, m.M_SNAP_GATE_CHAINS) == 1
            rec = stores.snapshot.get(key)
            assert rec is not None
            assert rec.batch_count == stores.history.batch_count(*key)
            want = real_stats()
            assert want["entries"] == 1 and want["bytes"] > 0
            # every render reads the gauges fresh: the HTTP scrape and the
            # structured dump alike, with no caller asking
            text = _scrape(registry)
            assert stats_calls == [1]
            for gauge in (m.M_SNAP_ENTRIES, m.M_SNAP_BYTES):
                value = want["entries" if gauge == m.M_SNAP_ENTRIES
                             else "bytes"]
                assert _gauge_line(gauge, value) in text, gauge
                assert registry.snapshot()[m.SCOPE_TPU_SNAPSHOT][gauge] \
                    == value
            assert stats_calls == [1, 1, 1]
        finally:
            server.shutdown()
            server.server_close()
        # the store server gone: the scrape still answers, the gauges as
        # they were, and the flight recorder says why
        text = _scrape(registry)
        assert _gauge_line(m.M_SNAP_ENTRIES, 1) in text
        assert any(e["kind"] == "snapshot-gauges-unread"
                   for e in flightrecorder.DEFAULT_RECORDER.snapshot())


class TestWriteFailureInAFlush:
    def test_a_raising_write_fails_no_ticket_and_is_counted(self):
        stores = Stores()
        runs = _seed(stores, n=4, held_back=1)
        tpu = TPUReplayEngine(stores)
        tpu.metrics = m.MetricsRegistry()  # counts of this test alone
        assert tpu.verify_all().ok
        cold_key = runs[3][0]
        tpu.resident.invalidate(cold_key)  # its flush item admits cold
        snapper = tpu.snapshotter()
        snapper.min_events = snapper.every_events = 1
        bad_key = runs[1][0]
        noted, tried = [], []
        real_note, real_write = snapper.note_append, snapper.snapshot_key

        def note_append(key, events):
            noted.append(key)
            real_note(key, events)

        def snapshot_key(key, force=False):
            tried.append(key)
            if key == bad_key:
                raise RuntimeError("store went away")
            return real_write(key, force)

        snapper.note_append, snapper.snapshot_key = note_append, snapshot_key
        sched = tpu.serving_scheduler()
        sched._ensure_thread = lambda: None  # flush by hand
        try:
            tickets = {}
            for key, h in runs:
                ms = _append_and_commit(stores, key, h[-1:])
                row = payload_row(ms, tpu.layout)
                row[STICKY_ROW_INDEX] = 0
                tickets[key] = sched.submit(
                    key, row, int(ms.version_histories.current_index),
                    batch_crc(h[-1]))
            with sched._cv:
                batch = list(sched._pending.values())
                sched._pending.clear()
            sched._flush(batch)
        finally:
            sched.stop()
        results = {k: t.result(timeout=10) for k, t in tickets.items()}
        assert all(r.ok and r.parity_ok for r in results.values()), results
        assert results[cold_key].path == "cold"
        assert {r.path for k, r in results.items() if k != cold_key} \
            == {"suffix"}
        keys = [k for k, _h in runs]
        assert sorted(noted) == sorted(keys)
        assert sorted(tried) == sorted(keys)
        assert all(tpu.resident.entry_for(k) is not None for k in keys)
        reg = tpu.metrics
        assert _counter(reg, m.M_SNAP_WRITE_ERRORS) == 1
        assert _counter(reg, m.M_SNAP_GATE_CHAINS) == len(keys)
        assert _counter(reg, m.M_SNAP_WRITES) == len(keys) - 1
        assert stores.snapshot.get(bad_key) is None
        assert all(stores.snapshot.get(k) is not None
                   for k in keys if k != bad_key)


class TestServiceHostSeries:
    def test_new_counters_preregistered_on_a_fresh_host(self):
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
        for series in ("gate_chains", "write_errors"):
            assert f'cadence_{series}_total{{scope="tpu.snapshot"}} 0' \
                in text, series
