"""Metrics + dynamic config + quotas (VERDICT ask #6).

Reference analogs: common/metrics (defs.go scopes), common/dynamicconfig
(~350 knobs consumed as closures), common/quotas/ratelimiter.go:43.
"""
import pytest

from cadence_tpu.engine.onebox import Onebox
from cadence_tpu.models.deciders import CompleteDecider, SignalDecider
from cadence_tpu.utils import metrics as m
from cadence_tpu.utils.clock import ManualTimeSource
from cadence_tpu.utils.dynamicconfig import (
    KEY_FRONTEND_DOMAIN_RPS,
    KEY_FRONTEND_RPS,
    KEY_MAX_ACTIVITIES,
    KEY_MAX_BRANCHES,
    DynamicConfig,
)
from cadence_tpu.utils.quotas import (
    NANOS,
    Collection,
    MultiStageRateLimiter,
    ServiceBusyError,
    TokenBucket,
    parse_quota_spec,
)
from tests.taskpoller import TaskPoller

DOMAIN = "metrics-domain"
TL = "metrics-tl"


@pytest.fixture()
def box():
    b = Onebox(num_hosts=1, num_shards=4)
    b.frontend.register_domain(DOMAIN)
    return b


class TestMetrics:
    def test_engine_transaction_counters_emit(self, box):
        box.frontend.start_workflow_execution(DOMAIN, "m-1", "t", TL)
        poller = TaskPoller(box, DOMAIN, TL, {"m-1": CompleteDecider()})
        poller.drain()
        assert box.metrics.counter(m.SCOPE_FRONTEND_START, m.M_REQUESTS) == 1
        assert box.metrics.counter(m.SCOPE_HISTORY_START_WORKFLOW,
                                   m.M_REQUESTS) == 1
        assert box.metrics.counter(m.SCOPE_HISTORY_DECISION_COMPLETED,
                                   m.M_REQUESTS) >= 1
        assert box.metrics.counter(m.SCOPE_QUEUE_TRANSFER,
                                   m.M_TASKS_PROCESSED) >= 1

    def test_buffered_flush_counter(self, box):
        box.frontend.start_workflow_execution(DOMAIN, "m-2", "signal", TL)
        box.pump_once()
        resp = box.frontend.poll_for_decision_task(DOMAIN, TL)
        box.frontend.signal_workflow_execution(DOMAIN, "m-2", "s")
        box.frontend.respond_decision_task_completed(resp.token, [])
        assert box.metrics.counter(m.SCOPE_HISTORY_DECISION_COMPLETED,
                                   m.M_BUFFERED_FLUSHED) == 1

    def test_replay_throughput_and_kernel_metrics_emit(self, box):
        """verify_all records kernel launches, events replayed, and a
        replay-throughput gauge (the VERDICT 'Done' criterion)."""
        box.frontend.start_workflow_execution(DOMAIN, "m-3", "t", TL)
        poller = TaskPoller(box, DOMAIN, TL, {"m-3": CompleteDecider()})
        poller.drain()
        assert box.tpu.verify_all().ok
        assert box.metrics.counter(m.SCOPE_TPU_REPLAY, m.M_KERNEL_LAUNCHES) >= 1
        assert box.metrics.counter(m.SCOPE_TPU_REPLAY, m.M_EVENTS_REPLAYED) > 0
        assert box.metrics.gauge_value(m.SCOPE_TPU_REPLAY,
                                       m.M_REPLAY_THROUGHPUT) > 0

    def test_fallback_rate_gauge_emits(self, box):
        """A reset runs the device rebuilder, which publishes the
        fallback-rate gauge (0.0 when everything stayed on device)."""
        box.frontend.start_workflow_execution(DOMAIN, "m-4", "signal", TL)
        poller = TaskPoller(box, DOMAIN, TL,
                            {"m-4": SignalDecider(expected_signals=2)})
        poller.drain()
        domain_id = box.stores.domain.by_name(DOMAIN).domain_id
        run_id = box.stores.execution.get_current_run_id(domain_id, "m-4")
        box.frontend.reset_workflow_execution(
            DOMAIN, "m-4", decision_finish_event_id=4, run_id=run_id)
        assert box.metrics.counter(m.SCOPE_REBUILD, m.M_DEVICE_REBUILDS) >= 1
        assert box.metrics.gauge_value(m.SCOPE_REBUILD, m.M_FALLBACK_RATE,
                                       default=-1.0) == 0.0
        snap = box.metrics.snapshot()
        assert m.SCOPE_REBUILD in snap and m.SCOPE_TPU_REPLAY not in ("",)

    @pytest.mark.parametrize("render", ["snapshot", "to_prometheus"])
    def test_collectors_run_before_every_render_and_go_with_their_object(
            self, render):
        """A collector sets its series before each render, whichever
        renders; the registry holds it weakly, so it goes with its
        object."""
        import gc

        registry = m.MetricsRegistry()

        class Store:
            entries = 0

            def collect(self):
                self.entries += 1
                registry.gauge("store", "entries", float(self.entries))

        store = Store()
        registry.add_collector(store.collect)
        getattr(registry, render)()
        getattr(registry, render)()
        assert store.entries == 2
        assert registry.gauge_value("store", "entries") == 2.0
        del store
        gc.collect()
        getattr(registry, render)()
        assert registry.gauge_value("store", "entries") == 2.0


class TestDynamicConfig:
    def test_payload_layout_tunable_without_code_edits(self):
        cfg = DynamicConfig({KEY_MAX_ACTIVITIES: 32, KEY_MAX_BRANCHES: 4})
        box = Onebox(num_hosts=1, num_shards=2, config=cfg)
        assert box.tpu.layout.max_activities == 32
        assert box.tpu.layout.max_branches == 4
        assert box.rebuilder.layout.max_activities == 32

    def test_live_update_via_closure(self):
        cfg = DynamicConfig()
        prop = cfg.int_property(KEY_FRONTEND_RPS)
        assert prop() == 0
        cfg.set(KEY_FRONTEND_RPS, 7)
        assert prop() == 7  # consumers see updates without rebuilds

    def test_domain_filter_precedence(self):
        cfg = DynamicConfig({KEY_FRONTEND_DOMAIN_RPS: 10})
        cfg.set(KEY_FRONTEND_DOMAIN_RPS, 3, domain="hot-domain")
        assert cfg.get(KEY_FRONTEND_DOMAIN_RPS, domain="hot-domain") == 3
        assert cfg.get(KEY_FRONTEND_DOMAIN_RPS, domain="other") == 10


class TestQuotas:
    def test_token_bucket_refills_with_clock(self):
        clock = ManualTimeSource()
        tb = TokenBucket(clock, rps=2, burst=2)
        assert tb.allow() and tb.allow()
        assert not tb.allow()  # burst exhausted
        clock.advance(500_000_000)  # 0.5s → one token back
        assert tb.allow()
        assert not tb.allow()

    def test_over_limit_start_rejected_cleanly(self):
        cfg = DynamicConfig({KEY_FRONTEND_RPS: 2})
        box = Onebox(num_hosts=1, num_shards=2, config=cfg)
        box.frontend.register_domain(DOMAIN)
        box.frontend.start_workflow_execution(DOMAIN, "q-1", "t", TL)
        box.frontend.start_workflow_execution(DOMAIN, "q-2", "t", TL)
        with pytest.raises(ServiceBusyError):
            box.frontend.start_workflow_execution(DOMAIN, "q-3", "t", TL)
        assert box.metrics.counter(m.SCOPE_FRONTEND_START,
                                   m.M_RATE_LIMITED) == 1
        # nothing was persisted for the rejected start
        domain_id = box.stores.domain.by_name(DOMAIN).domain_id
        assert len([k for k in box.stores.execution.list_executions()
                    if k[1] == "q-3"]) == 0
        # tokens refill with time → admitted again
        box.clock.advance(1_000_000_000)
        box.frontend.start_workflow_execution(DOMAIN, "q-3", "t", TL)

    def test_per_domain_limit(self):
        cfg = DynamicConfig()
        cfg.set(KEY_FRONTEND_DOMAIN_RPS, 1, domain="limited")
        box = Onebox(num_hosts=1, num_shards=2, config=cfg)
        box.frontend.register_domain("limited")
        box.frontend.register_domain("free")
        box.frontend.start_workflow_execution("limited", "a", "t", TL)
        with pytest.raises(ServiceBusyError):
            box.frontend.start_workflow_execution("limited", "b", "t", TL)
        # other domains unaffected
        for i in range(5):
            box.frontend.start_workflow_execution("free", f"f-{i}", "t", TL)

    def test_per_domain_series_capped_against_junk_domains(self):
        """_admit charges BEFORE domain validation, so the domain name in
        the per-domain metric series is request-supplied: a spray of junk
        names must stop growing the registry at the cap (totals keep
        counting) — the metrics side of quotas.Collection's no-leak
        guard."""
        box = Onebox(num_hosts=1, num_shards=2)
        fe = box.frontend
        fe.MAX_DOMAIN_SERIES = 3
        for i in range(10):
            with pytest.raises(Exception):  # EntityNotExist, post-admit
                fe.start_workflow_execution(f"junk-{i}", "w", "t", TL)
        per_domain = [name for name in box.metrics.snapshot()["quotas"]
                      if name.startswith("admitted-domain-")]
        assert len(per_domain) == 3
        assert box.metrics.counter(m.SCOPE_QUOTAS, "admitted") == 10


class TestTokenBucket:
    """Satellite: burst semantics + the non-consuming reserve/wait path
    (common/tokenbucket/tb.go), deterministic under ManualTimeSource."""

    def test_burst_zero_aliases_to_rps(self):
        clock = ManualTimeSource()
        tb = TokenBucket(clock, rps=5, burst=0)
        assert tb.burst == 5.0  # documented alias: one second's tokens
        assert TokenBucket(clock, rps=5, burst=2).burst == 2.0
        for _ in range(5):
            assert tb.try_consume()
        assert not tb.try_consume()

    def test_try_consume_n(self):
        clock = ManualTimeSource()
        tb = TokenBucket(clock, rps=4, burst=4)
        assert tb.try_consume(3)
        assert not tb.try_consume(2)  # only 1 left
        assert tb.try_consume(1)
        clock.advance(NANOS)  # 1s -> 4 tokens back
        assert tb.try_consume(4)

    def test_time_to_is_non_consuming(self):
        clock = ManualTimeSource()
        tb = TokenBucket(clock, rps=2, burst=2)
        assert tb.time_to() == 0.0
        assert tb.time_to() == 0.0  # asking twice consumed nothing
        assert tb.try_consume(2)
        assert tb.time_to(1) == pytest.approx(0.5)
        assert tb.time_to(2) == pytest.approx(1.0)
        # n beyond burst capacity can never be granted in one piece
        assert tb.time_to(3) == float("inf")

    def test_wait_deterministic_on_manual_clock(self):
        clock = ManualTimeSource()
        sleeps = []

        def manual_sleep(s):
            sleeps.append(s)
            clock.advance(int(s * NANOS))

        tb = TokenBucket(clock, rps=2, burst=2, sleep=manual_sleep)
        assert tb.try_consume(2)
        assert tb.wait(1)  # slept exactly the 0.5s deficit, then got it
        assert sleeps == pytest.approx([0.5])
        assert not tb.try_consume()  # wait() consumed the refilled token

    def test_wait_respects_deadline(self):
        clock = ManualTimeSource()
        tb = TokenBucket(clock, rps=1, burst=1,
                         sleep=lambda s: clock.advance(int(s * NANOS)))
        assert tb.try_consume()
        # 1 token needs 1s; deadline only 0.2s out -> refuse WITHOUT
        # sleeping (the clock must not advance)
        before = clock.now()
        assert not tb.wait(1, deadline=before + int(0.2 * NANOS))
        assert clock.now() == before
        # n > burst is unsatisfiable regardless of deadline
        assert not tb.wait(5, deadline=before + 100 * NANOS)

    def test_non_monotonic_clock_grants_nothing(self):
        clock = ManualTimeSource()
        tb = TokenBucket(clock, rps=10, burst=10)
        assert all(tb.try_consume() for _ in range(10))
        clock.advance(-5 * NANOS)  # NTP step-back
        assert not tb.try_consume()  # backwards time granted no tokens
        clock.advance(5 * NANOS)  # catch back up to the old reading
        # re-elapsed time must not be credited: still empty
        assert not tb.try_consume()
        clock.advance(NANOS // 10)  # genuinely new time -> 1 token
        assert tb.try_consume()
        assert not tb.try_consume()

    def test_unlimited_when_rps_zero(self):
        tb = TokenBucket(ManualTimeSource(), rps=0)
        assert all(tb.try_consume(100) for _ in range(50))
        assert tb.time_to(1000) == 0.0


class TestQuotaCollection:
    """Satellite: the per-domain collection under ManualTimeSource —
    deterministic refill, two-domain isolation, live-limit rebuild."""

    def test_deterministic_refill_per_domain(self):
        clock = ManualTimeSource()
        limits = {"hot": 2.0, "cold": 4.0}
        coll = Collection(clock, rps_for=lambda d: limits[d])
        assert [coll.allow("hot") for _ in range(3)] == [True, True, False]
        clock.advance(NANOS // 2)  # 0.5s: hot +1, cold untouched at 4
        assert coll.allow("hot")
        assert not coll.allow("hot")
        assert [coll.allow("cold") for _ in range(5)] == [
            True, True, True, True, False]

    def test_two_domain_isolation(self):
        clock = ManualTimeSource()
        coll = Collection(clock, rps_for=lambda d: 1.0)
        assert coll.allow("a")
        assert not coll.allow("a")  # a exhausted...
        assert coll.allow("b")      # ...b's bucket untouched

    def test_live_limit_change_rebuilds_bucket(self):
        clock = ManualTimeSource()
        limits = {"d": 1.0}
        coll = Collection(clock, rps_for=lambda d: limits[d])
        assert coll.allow("d")
        assert not coll.allow("d")
        limits["d"] = 3.0  # operator raises the limit
        # next request sees a fresh 3-rps bucket, no restart
        assert [coll.allow("d") for _ in range(4)] == [
            True, True, True, False]

    def test_multistage_admit_carries_retry_after(self):
        clock = ManualTimeSource()
        lim = MultiStageRateLimiter(clock, global_rps=lambda: 100,
                                    domain_rps=lambda d: 2,
                                    burst=lambda: 0)
        lim.admit("d")
        lim.admit("d")
        with pytest.raises(ServiceBusyError) as ei:
            lim.admit("d")
        assert ei.value.domain == "d"
        assert ei.value.retry_after_s == pytest.approx(0.5)
        assert "retry after" in str(ei.value)
        clock.advance(NANOS // 2)
        lim.admit("d")  # the hint was accurate

    def test_domain_stage_rejection_spares_global_bucket(self):
        """multistageratelimiter.go ordering: a hot domain's rejections
        must not drain the global stage for everyone else."""
        clock = ManualTimeSource()
        lim = MultiStageRateLimiter(clock, global_rps=lambda: 3,
                                    domain_rps=lambda d:
                                    2 if d == "hot" else 0,
                                    burst=lambda: 0)
        assert lim.allow("hot") and lim.allow("hot")
        for _ in range(10):
            assert not lim.allow("hot")  # hot-stage rejections
        # global stage still has its third token for the cold domain
        assert lim.allow("cold")

    def test_dynamicconfig_hot_update_takes_effect_without_restart(self):
        """Satellite acceptance: an operator config.set on a domain's
        RPS reaches the frontend's limiter mid-flight — the live closure
        rebuilds that domain's bucket on its next request."""
        cfg = DynamicConfig()
        cfg.set(KEY_FRONTEND_DOMAIN_RPS, 1, domain="tuned")
        box = Onebox(num_hosts=1, num_shards=2, config=cfg)
        box.frontend.register_domain("tuned")
        box.frontend.start_workflow_execution("tuned", "h-0", "t", TL)
        with pytest.raises(ServiceBusyError):
            box.frontend.start_workflow_execution("tuned", "h-1", "t", TL)
        cfg.set(KEY_FRONTEND_DOMAIN_RPS, 5, domain="tuned")  # hot update
        # the rebuilt bucket carries a fresh 5-token burst (burst=0
        # aliases to rps): five admits, then the sixth sheds
        for i in range(1, 6):
            box.frontend.start_workflow_execution("tuned", f"h-{i}",
                                                  "t", TL)
        with pytest.raises(ServiceBusyError):
            box.frontend.start_workflow_execution("tuned", "h-9", "t", TL)
        # and back down: the rebuilt bucket applies the new, lower limit
        cfg.set(KEY_FRONTEND_DOMAIN_RPS, 1, domain="tuned")
        box.clock.advance(1_000_000_000)
        box.frontend.start_workflow_execution("tuned", "h-10", "t", TL)
        with pytest.raises(ServiceBusyError):
            box.frontend.start_workflow_execution("tuned", "h-11", "t", TL)


class TestQuotaSpec:
    """Satellite: the CADENCE_TPU_QUOTAS per-host knob format."""

    def test_round_trip(self):
        g, b, d = parse_quota_spec(
            "rps=200, burst=50, domain.hot=20, domain.cold=80")
        assert (g, b) == (200.0, 50.0)
        assert d == {"hot": 20.0, "cold": 80.0}

    def test_empty_and_partial(self):
        assert parse_quota_spec("") == (0.0, 0.0, {})
        assert parse_quota_spec("domain.x=3") == (0.0, 0.0, {"x": 3.0})

    def test_malformed_rejected_loudly(self):
        with pytest.raises(ValueError):
            parse_quota_spec("rps")  # no '='
        with pytest.raises(ValueError):
            parse_quota_spec("domain.=5")  # empty domain
        with pytest.raises(ValueError):
            parse_quota_spec("rsp=5")  # typo'd key must not silently
            #                            admit everything
