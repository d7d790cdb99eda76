"""Device-serving transaction tier (engine/serving.py).

Two layers:

1. Scheduler-seam tests: a ServingScheduler with injected read seams and
   the drain thread disabled, so every flush is driven synchronously —
   coalescing, parity gating, divergence invalidation, tail-moved
   re-reads, multi-branch bypass, bounded-queue backpressure, shutdown.

2. Cluster integration: an Onebox with the tier wired into its history
   engines — committed start/signal/decision transactions flow through
   `_Txn.commit`'s handoff, the resident pool stays parity-clean, and
   the full oracle<->device verify stays green over tier-maintained
   state.
"""
import threading

import numpy as np
import pytest

from cadence_tpu.core.checksum import (
    DEFAULT_LAYOUT,
    STICKY_ROW_INDEX,
    crc32_of_row,
    payload_row,
)
from cadence_tpu.engine.cache import batch_crc
from cadence_tpu.engine.persistence import Stores
from cadence_tpu.engine.serving import ServingScheduler, ServingTicket
from cadence_tpu.engine.tpu_engine import TPUReplayEngine
from cadence_tpu.gen.corpus import generate_corpus
from cadence_tpu.oracle.state_builder import StateBuilder
from cadence_tpu.utils import metrics as m
from cadence_tpu.utils.quotas import ServiceBusyError

LAYOUT = DEFAULT_LAYOUT


class _Harness:
    """Scheduler over injected histories; flushes driven by hand."""

    def __init__(self, workflows=3, target_events=24, **kw):
        self.hists = generate_corpus("basic", num_workflows=workflows,
                                     seed=11, target_events=target_events)
        self.keys = [("t", f"wf-{i}", "r") for i in range(workflows)]
        self.counts = {k: len(h) for k, h in zip(self.keys, self.hists)}
        self.by_key = dict(zip(self.keys, self.hists))
        self.tpu = TPUReplayEngine(Stores(), LAYOUT)
        self.sched = ServingScheduler(
            self.tpu, read_batches=self.read_batches,
            read_live_row=self.read_live_row, **kw)
        # drain by hand: deterministic single-threaded flushes
        self.sched._ensure_thread = lambda: None

    def read_batches(self, key):
        return self.by_key[key][:self.counts[key]]

    def read_live_row(self, key):
        ms = StateBuilder().replay_history(self.read_batches(key))
        row = payload_row(ms, LAYOUT)
        row[STICKY_ROW_INDEX] = 0
        return row, int(ms.version_histories.current_index), \
            int(ms.execution_info.next_event_id)

    def oracle(self, key):
        row, br, _ = self.read_live_row(key)
        return row, br

    def submit(self, key, row=None, branch=None, tail_crc=None):
        if row is None:
            row, branch = self.oracle(key)
        if tail_crc is None:
            tail_crc = batch_crc(self.read_batches(key)[-1])
        return self.sched.submit(key, row, branch, tail_crc)

    def flush(self):
        with self.sched._cv:
            batch = list(self.sched._pending.values())
            self.sched._pending.clear()
        if batch:
            self.sched._flush(batch)

    def counter(self, name):
        return self.sched.metrics.counter(m.SCOPE_TPU_SERVING, name)


class TestSchedulerSeam:
    def test_cold_admit_then_suffix_serve_checksums_match_oracle(self):
        h = _Harness(workflows=2)
        k = h.keys[0]
        h.counts[k] = len(h.by_key[k]) - 1
        t_cold = h.submit(k)
        h.flush()
        res = t_cold.result(timeout=1)
        assert res.ok and res.parity_ok and res.path == "cold"
        assert res.checksum == int(crc32_of_row(h.oracle(k)[0]))
        assert h.counter(m.M_SERVING_COLD) == 1
        # append one batch: the next transaction replays ONLY the suffix
        # against the resident state
        h.counts[k] += 1
        t_sfx = h.submit(k)
        h.flush()
        res = t_sfx.result(timeout=1)
        assert res.ok and res.parity_ok and res.path == "suffix"
        assert res.checksum == int(crc32_of_row(h.oracle(k)[0]))
        assert h.counter(m.M_SERVING_SUFFIX) == 1
        assert h.counter(m.M_SERVING_DIVERGENCE) == 0

    def test_warmed_host_stacks_any_row_count_without_a_new_program(self):
        """A suffix flush stacks its k resident rows to the flush WIDTH
        (resident._stack_padded), so after warm() the stack program
        exists whatever k the drain window holds — one program per
        width, not per row count (a per-count program is a mid-window
        compile on the CPU and minutes of boot warm-up on a TPU)."""
        from cadence_tpu.engine import resident

        h = _Harness(workflows=5)
        for k in h.keys:
            h.counts[k] = len(h.by_key[k]) - 2
            h.submit(k)
        h.flush()  # seed five residents
        def stack_programs():
            fn = resident._STACK_FN  # built on first use
            return fn._cache_size() if fn is not None else 0

        before = stack_programs()
        h.sched.warm(e_shapes=(16,), width=8)
        programs = stack_programs()
        assert programs - before <= 1  # one width warmed
        for rows in (1, 3, 5):
            tickets = []
            for k in h.keys[:rows]:
                h.counts[k] = min(h.counts[k] + 1, len(h.by_key[k]))
                tickets.append(h.submit(k))
            h.flush()
            assert all(t.result(timeout=1).ok for t in tickets)
        assert h.counter(m.M_SERVING_SUFFIX) >= 5
        assert stack_programs() == programs

    def test_same_key_transactions_coalesce_into_one_pass(self):
        h = _Harness(workflows=1)
        k = h.keys[0]
        h.counts[k] = len(h.by_key[k]) - 2
        h.submit(k)
        h.flush()  # seed resident
        tickets = []
        for _ in range(2):
            h.counts[k] += 1
            tickets.append(h.submit(k))
        assert h.counter(m.M_SERVING_COALESCED) == 1
        assert len(h.sched._pending) == 1  # one queue slot per workflow
        h.flush()
        results = [t.result(timeout=1) for t in tickets]
        assert all(r.ok for r in results)
        # both tickets settle from the SAME device pass at the newest
        # committed state
        assert results[0].checksum == results[1].checksum
        assert results[1].coalesced

    def test_exact_serve_zero_device_work(self):
        h = _Harness(workflows=1)
        k = h.keys[0]
        h.submit(k)
        h.flush()
        launches = h.counter(m.M_SERVING_LAUNCHES)
        # same committed state again (e.g. a fold already covered it)
        t = h.submit(k)
        h.flush()
        res = t.result(timeout=1)
        assert res.ok and res.path == "exact"
        assert h.counter(m.M_SERVING_LAUNCHES) == launches
        assert h.counter(m.M_SERVING_EXACT) == 1

    def test_parity_divergence_invalidates_never_serves(self):
        h = _Harness(workflows=1)
        k = h.keys[0]
        h.submit(k)
        h.flush()
        assert h.tpu.resident.lookup(k, h.read_batches(k)) is not None
        wrong = h.oracle(k)[0].copy()
        wrong[0] += 1
        t = h.submit(k, row=wrong, branch=h.oracle(k)[1])
        h.flush()
        res = t.result(timeout=1)
        assert not res.ok and not res.parity_ok
        assert h.counter(m.M_SERVING_DIVERGENCE) == 1
        # the entry was dropped — wrong state is never retained
        assert h.tpu.resident.lookup(k, h.read_batches(k)) is None
        assert h.tpu.resident.metrics.counter(
            m.SCOPE_TPU_RESIDENT, m.M_CACHE_INVALIDATIONS) >= 1

    def test_tail_moved_re_reads_live_state(self):
        h = _Harness(workflows=1)
        k = h.keys[0]
        h.counts[k] = len(h.by_key[k]) - 1
        h.submit(k)
        h.flush()
        # a "newer commit" lands after submit: the enqueued tail_crc no
        # longer matches the store tail — the drain must re-read the
        # live row instead of comparing a stale expectation
        stale_tail = batch_crc(h.read_batches(k)[-1])
        row, br = h.oracle(k)
        h.counts[k] += 1  # store moves first
        t = h.sched.submit(k, row, br, stale_tail)
        h.flush()
        res = t.result(timeout=1)
        assert res.ok and res.parity_ok
        assert res.checksum == int(crc32_of_row(h.oracle(k)[0]))

    def test_multi_branch_bypasses_and_invalidates(self):
        h = _Harness(workflows=1)
        k = h.keys[0]
        h.submit(k)
        h.flush()
        # simulate an NDC branch switch: the read seam reports
        # "not single-lineage" (None), same as the stores-backed seam
        h.by_key[k] = None
        h.counts[k] = 0

        def read_none(key):
            return None
        h.sched._read_batches = read_none
        t = h.sched.submit(k, np.zeros(LAYOUT.width, np.int64), 0, 1)
        h.flush()
        res = t.result(timeout=1)
        assert not res.ok and res.path == "bypass"
        assert h.counter(m.M_SERVING_BYPASSED) == 1
        assert h.tpu.resident.metrics.counter(
            m.SCOPE_TPU_RESIDENT, m.M_CACHE_INVALIDATIONS) >= 1

    def test_bounded_queue_sheds_typed_service_busy(self):
        h = _Harness(workflows=3, max_queue=2)
        h.submit(h.keys[0])
        h.submit(h.keys[1])
        with pytest.raises(ServiceBusyError) as exc:
            h.submit(h.keys[2])
        assert exc.value.retry_after_s > 0
        assert h.counter(m.M_SERVING_REJECTED) == 1
        # a SAME-key submit still folds — backpressure never blocks
        # coalescing into an existing slot
        t = h.submit(h.keys[0])
        assert isinstance(t, ServingTicket)
        assert h.counter(m.M_SERVING_COALESCED) == 1

    def test_chained_append_reads_nothing_from_the_store(self):
        """The zero-read chain: when the engine hands the committed
        batches and the resident tail matches the submit ledger, the
        flush must touch neither the history store nor the serializer —
        pinned by a read seam that RAISES if consulted."""
        h = _Harness(workflows=1)
        k = h.keys[0]
        h.counts[k] = len(h.by_key[k]) - 2
        h.submit(k)
        h.flush()  # cold admit (store reads allowed here)

        boom = {"armed": False}
        real_read = h.read_batches

        def guarded_read(key):
            if boom["armed"]:
                raise AssertionError("chain path read the store")
            return real_read(key)
        h.sched._read_batches = guarded_read

        for _ in range(2):  # two chained appends, zero store reads
            h.counts[k] += 1
            row, br = h.oracle(k)
            batch = h.by_key[k][h.counts[k] - 1]
            t = h.sched.submit(k, row, br, batch_crc(batch), batch=batch)
            boom["armed"] = True
            h.flush()
            boom["armed"] = False
            res = t.result(timeout=1)
            assert res.ok and res.parity_ok and res.path == "suffix"
            assert res.checksum == int(crc32_of_row(h.oracle(k)[0]))
        assert h.counter(m.M_SERVING_DIVERGENCE) == 0

    def test_stop_resolves_pending_not_ok(self):
        h = _Harness(workflows=1)
        t = h.submit(h.keys[0])
        h.sched.stop()
        res = t.result(timeout=1)
        assert not res.ok and res.error == "stopped"

    def test_drain_thread_end_to_end(self):
        """The real drain loop (no manual flushes): lazy thread start,
        adaptive window, drain() settling."""
        h = _Harness(workflows=2, max_wait_us=1000)
        del h.sched._ensure_thread  # restore the real lazy-start
        tickets = [h.submit(k) for k in h.keys]
        assert h.sched.drain(timeout=120.0)
        for t in tickets:
            res = t.result(timeout=1)
            assert res.ok and res.parity_ok
        h.sched.stop()


class TestOneboxServingTier:
    def _box(self):
        from cadence_tpu.engine.onebox import Onebox
        box = Onebox(num_hosts=1, num_shards=2)
        sched = box.enable_serving()
        return box, sched

    def test_committed_transactions_flow_through_tier(self):
        box, sched = self._box()
        fe = box.frontend
        fe.register_domain("svd")
        fe.start_workflow_execution("svd", "wf-a", "t", "tl")
        assert sched.drain(timeout=300.0)
        for i in range(3):
            fe.signal_workflow_execution("svd", "wf-a", f"s{i}",
                                         request_id=f"r{i}")
        assert sched.drain(timeout=300.0)
        stats = sched.stats()
        assert stats["transactions"] >= 4
        assert stats["parity_divergence"] == 0
        assert stats["cold_admits"] >= 1
        # every engine handoff carried a resolvable ticket
        eng = box.route("wf-a")
        res = eng.last_serving_ticket.result(timeout=60)
        assert res.ok and res.parity_ok
        # the tier-maintained resident state verifies against the oracle
        r = box.tpu.verify_all()
        assert r.ok
        assert len(r.resident) >= 1
        sched.stop()

    def test_admin_serving_rollup(self):
        from cadence_tpu.engine.admin import AdminHandler
        box, sched = self._box()
        fe = box.frontend
        fe.register_domain("svd")
        fe.start_workflow_execution("svd", "wf-b", "t", "tl")
        assert sched.drain(timeout=300.0)
        doc = AdminHandler(box).serving()
        assert doc["tier_wired"]
        assert doc["transactions"] >= 1
        assert doc["parity_divergence"] == 0
        assert "coalescing_factor" in doc and "queue_depth" in doc
        assert doc["resident_entries"] >= 1
        sched.stop()

    def test_handoff_is_fire_and_forget_on_backpressure(self):
        """A full serving queue must never fail the transaction: the
        oracle commit already happened; the handoff sheds and the engine
        carries on."""
        box, sched = self._box()
        sched.max_queue = 0  # every distinct-key submit sheds
        fe = box.frontend
        fe.register_domain("svd")
        run_id = fe.start_workflow_execution("svd", "wf-c", "t", "tl")
        assert run_id  # the transaction itself succeeded
        assert box.metrics.counter(m.SCOPE_TPU_SERVING,
                                   m.M_SERVING_REJECTED) >= 1
        eng = box.route("wf-c")
        assert eng.last_serving_ticket is None
        sched.stop()
