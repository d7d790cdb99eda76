"""Cluster telemetry plane (ISSUE 16): time-series ring-buffer window
math (rates, retention, counter-reset tolerance, leg/saturation
derivation), host-runtime attribution on named threads, flight-recorder
ring bounds + dump-on-signal + dump-on-crash via subprocess kill, SLO
burn rates over seeded synthetic series, the scrape surface's new
routes, and the `admin top` fleet rollup over a live wire cluster.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from cadence_tpu.engine.admin import (
    AdminHandler,
    _cluster_rollup,
    fleet_top,
    scrape_timeseries,
    summarize_windows,
)
from cadence_tpu.engine.onebox import Onebox
from cadence_tpu.loadgen.slo import BurnRateEvaluator, BurnTarget
from cadence_tpu.models.deciders import CompleteDecider
from cadence_tpu.utils import flightrecorder
from cadence_tpu.utils import metrics as m
from cadence_tpu.utils.flightrecorder import MAX_STR, FlightRecorder
from cadence_tpu.utils.hostprof import HostProfiler, subsystem_for
from cadence_tpu.utils.metrics import MetricsRegistry
from cadence_tpu.utils.timeseries import TimeSeriesSampler
from tests.taskpoller import TaskPoller

DOMAIN = "telemetry-domain"
TL = "telemetry-tl"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def box():
    b = Onebox(num_hosts=2, num_shards=8)
    b.frontend.register_domain(DOMAIN)
    return b


def _run_one_workflow(b: Onebox, workflow_id: str = "tel-wf") -> None:
    b.frontend.start_workflow_execution(DOMAIN, workflow_id, "t", TL)
    TaskPoller(b, DOMAIN, TL, {workflow_id: CompleteDecider()}).drain()


# ---------------------------------------------------------------------------
# time-series ring buffers
# ---------------------------------------------------------------------------

class TestTimeSeriesSampler:
    def test_first_sample_anchors_no_window(self):
        sampler = TimeSeriesSampler(MetricsRegistry(), period_s=1.0)
        assert sampler.sample_once(now=0.0) is None
        assert sampler.samples_total == 1
        assert sampler.windows() == []

    def test_counter_deltas_rates_and_gauges(self):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0)
        sampler.sample_once(now=0.0)
        reg.inc("a", "commits", 10)
        reg.gauge("a", "depth", 7.0)
        window = sampler.sample_once(now=2.0)
        assert window.dur_s == pytest.approx(2.0)
        assert window.deltas[("a", "commits")] == 10
        assert window.rates[("a", "commits")] == pytest.approx(5.0)
        assert window.gauges[("a", "depth")] == 7.0
        # second window sees only the NEW increments
        reg.inc("a", "commits", 4)
        window = sampler.sample_once(now=3.0)
        assert window.deltas[("a", "commits")] == 4
        assert window.rates[("a", "commits")] == pytest.approx(4.0)

    def test_counter_reset_reads_as_fresh_epoch(self):
        """An in-place registry reset() moves cumulatives BACKWARD; the
        window must report the new cumulative as the delta, never a
        negative rate."""
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0)
        reg.inc("a", "commits", 10)
        sampler.sample_once(now=0.0)
        reg.reset()
        reg.inc("a", "commits", 3)
        window = sampler.sample_once(now=1.0)
        assert window.deltas[("a", "commits")] == 3
        assert all(r >= 0 for r in window.rates.values())

    def test_histogram_count_total_deltas(self):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0)
        sampler.sample_once(now=0.0)
        reg.record("s", "lat", 0.2)
        reg.record("s", "lat", 0.3)
        window = sampler.sample_once(now=1.0)
        count, total = window.hist_deltas[("s", "lat")]
        assert count == 2
        assert total == pytest.approx(0.5)
        assert window.rates[("s", "lat")] == pytest.approx(2.0)

    def test_retention_evicts_oldest(self):
        sampler = TimeSeriesSampler(MetricsRegistry(), period_s=1.0,
                                    retention=3)
        for t in range(6):
            sampler.sample_once(now=float(t))
        windows = sampler.windows()
        assert len(windows) == 3
        assert [w.t for w in windows] == [3.0, 4.0, 5.0]
        # horizon read clips to the trailing span
        assert [w.t for w in sampler.windows(horizon_s=2.0, now=5.0)] == \
            [4.0, 5.0]

    def test_leg_decomposition_binding_and_utilization(self):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0)
        sampler.sample_once(now=0.0)
        reg.record(m.SCOPE_TPU_REPLAY, m.M_PROFILE_KERNEL, 0.6)
        reg.record(m.SCOPE_REBUILD, m.M_PROFILE_KERNEL, 0.2)
        reg.record(m.SCOPE_TPU_REPLAY, m.M_PROFILE_PACK, 0.1)
        window = sampler.sample_once(now=1.0)
        assert window.legs[m.M_PROFILE_KERNEL] == pytest.approx(0.8)
        assert window.legs[m.M_PROFILE_PACK] == pytest.approx(0.1)
        assert window.binding_resource == m.M_PROFILE_KERNEL
        assert window.utilization == pytest.approx(0.9)
        # idle window: nothing ran
        window = sampler.sample_once(now=2.0)
        assert window.binding_resource == "idle"
        assert window.utilization == 0.0

    def test_saturation_queue_fill_and_launches_in_flight(self):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0)
        sampler.set_capacity(m.SCOPE_TPU_SERVING, m.M_SERVING_QUEUE_DEPTH,
                             lambda: 8)
        sampler.sample_once(now=0.0)
        reg.gauge(m.SCOPE_TPU_SERVING, m.M_SERVING_QUEUE_DEPTH, 6.0)
        reg.gauge(m.SCOPE_TPU_EXECUTOR, m.M_EXEC_IN_FLIGHT, 0.5)
        reg.record(m.SCOPE_TPU_REPLAY, m.M_PROFILE_PACK_WAIT, 0.3)
        reg.record(m.SCOPE_TPU_REPLAY, m.M_PROFILE_KERNEL, 0.1)
        window = sampler.sample_once(now=1.0)
        sat = window.saturation
        assert sat["queue_depth"] == 6.0
        assert sat["queue_capacity"] == 8.0
        assert sat["queue_fill"] == pytest.approx(0.75)
        assert sat["launches_in_flight"] == 0.5
        assert sat["queue_wait_share"] == pytest.approx(0.75)

    def test_fraction_over_bucket_boundary_semantics(self):
        """Bucket-granular over-counting: a bucket bounded exactly AT
        the threshold counts under (le semantics make those observations
        provably <= the ceiling); between bounds the violation rounds UP
        to the enclosing bucket (conservative)."""
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0)
        sampler.track_histogram("s", "lat")
        sampler.sample_once(now=0.0)
        reg.observe("s", "lat", 0.3)   # le=0.5 bucket
        reg.observe("s", "lat", 0.7)   # le=1.0 bucket
        reg.observe("s", "lat", 2.0)   # le=2.5 bucket
        sampler.sample_once(now=1.0)
        # 0.5 is a DEFAULT_BUCKETS bound: the le=0.5 bucket is under
        assert sampler.fraction_over("s", "lat", 0.5, 10.0, now=1.0) == (2, 3)
        # 0.6 is between bounds: the 0.7 (le=1.0 bucket) still counts over
        assert sampler.fraction_over("s", "lat", 0.6, 10.0, now=1.0) == (2, 3)
        # horizon excludes the window entirely
        assert sampler.fraction_over("s", "lat", 0.5, 10.0, now=99.0) == (0, 0)

    def test_untracked_histograms_keep_no_buckets(self):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0)
        sampler.sample_once(now=0.0)
        reg.observe("s", "lat", 0.3)
        window = sampler.sample_once(now=1.0)
        assert ("s", "lat") in window.hist_deltas
        assert window.bucket_deltas == {}

    def test_publishes_own_health_gauges(self):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0)
        sampler.sample_once(now=0.0)
        sampler.sample_once(now=1.0)
        assert reg.gauge_value(m.SCOPE_TIMESERIES, "windows") == 1.0
        assert reg.gauge_value(m.SCOPE_TIMESERIES, "samples") == 2.0

    def test_on_sample_hook_sees_window_and_cannot_break_sampler(self):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0)
        seen = []
        sampler.on_sample = lambda w: seen.append(w.t)
        sampler.sample_once(now=0.0)
        sampler.sample_once(now=1.0)
        assert seen == [1.0]
        sampler.on_sample = lambda w: 1 / 0
        assert sampler.sample_once(now=2.0) is not None  # hook swallowed

    def test_doc_shape(self):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0, retention=10)
        sampler.sample_once(now=0.0)
        reg.inc("a", "b")
        sampler.sample_once(now=1.0)
        doc = sampler.doc(last_n=5)
        assert doc["retention"] == 10
        assert doc["samples"] == 2
        (window,) = doc["windows"]
        assert window["t"] == 1.0
        assert window["rates"]["a/b"] == pytest.approx(1.0)
        assert window["binding_resource"] == "idle"

    def test_thread_lifecycle(self):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=0.02)
        sampler.start()
        try:
            deadline = time.monotonic() + 5
            while sampler.samples_total < 3 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert sampler.samples_total >= 3
            assert any(t.name == "cadence-timeseries"
                       for t in threading.enumerate())
        finally:
            sampler.stop()
        assert not any(t.name == "cadence-timeseries"
                       for t in threading.enumerate())


# ---------------------------------------------------------------------------
# SLO burn rate
# ---------------------------------------------------------------------------

class TestBurnRate:
    def _rig(self, ceiling_s=0.5):
        reg = MetricsRegistry()
        sampler = TimeSeriesSampler(reg, period_s=1.0)
        burn = BurnRateEvaluator(
            sampler, [BurnTarget("start", "s", "lat", ceiling_s)],
            horizons=(5.0, 60.0), registry=reg)
        return reg, sampler, burn

    def test_construction_preregisters_gauges(self):
        reg, _, _ = self._rig()
        assert reg.gauge_value(m.SCOPE_SLO, "burn-rate-start-5s") == 0.0
        assert reg.gauge_value(m.SCOPE_SLO, "burn-rate-start-60s") == 0.0
        assert reg.gauge_value(m.SCOPE_SLO, "alerting-start") == 0.0

    def test_sustained_violation_burns_and_alerts(self):
        reg, sampler, burn = self._rig()
        sampler.sample_once(now=0.0)
        for _ in range(100):
            reg.observe("s", "lat", 2.0)  # all over the 0.5s ceiling
        sampler.sample_once(now=2.0)
        doc = burn.evaluate(now=2.0)
        (row,) = doc["targets"]
        # fraction 1.0 against the p99 budget of 0.01 → burn rate 100
        assert row["windows"]["5s"] == {"over": 100, "total": 100,
                                        "fraction": 1.0, "burn_rate": 100.0}
        assert row["alerting"] and not doc["ok"]
        assert reg.gauge_value(m.SCOPE_SLO, "burn-rate-start-5s") == 100.0
        assert reg.gauge_value(m.SCOPE_SLO, "alerting-start") == 1.0
        assert reg.gauge_value(m.SCOPE_SLO, "alerting") == 1.0

    def test_under_ceiling_traffic_burns_nothing(self):
        reg, sampler, burn = self._rig()
        sampler.sample_once(now=0.0)
        for _ in range(100):
            reg.observe("s", "lat", 0.1)
        sampler.sample_once(now=2.0)
        doc = burn.evaluate(now=2.0)
        (row,) = doc["targets"]
        assert row["windows"]["5s"]["burn_rate"] == 0.0
        assert doc["ok"] and not row["alerting"]
        assert reg.gauge_value(m.SCOPE_SLO, "alerting") == 0.0

    def test_observations_at_ceiling_are_under(self):
        """0.5s is a DEFAULT_BUCKETS bound, so 'p99 <= 500ms' is exact at
        the ceiling: observations landing in the le=0.5 bucket are
        provably within budget."""
        reg, sampler, burn = self._rig(ceiling_s=0.5)
        sampler.sample_once(now=0.0)
        for _ in range(50):
            reg.observe("s", "lat", 0.5)
        sampler.sample_once(now=1.0)
        doc = burn.evaluate(now=1.0)
        assert doc["targets"][0]["windows"]["5s"]["over"] == 0

    def test_multi_window_blip_does_not_page(self):
        """A burst that has LEFT the short horizon: the long window still
        burns but the short one is quiet — multi-window alerting stays
        down (a blip can't page; only a sustained burn trips both)."""
        reg, sampler, burn = self._rig()
        sampler.sample_once(now=0.0)
        for _ in range(100):
            reg.observe("s", "lat", 2.0)
        sampler.sample_once(now=2.0)   # the burst window, t=2
        sampler.sample_once(now=30.0)  # quiet window, t=30
        doc = burn.evaluate(now=30.0)
        (row,) = doc["targets"]
        assert row["windows"]["5s"]["total"] == 0
        assert row["windows"]["60s"]["burn_rate"] == 100.0
        assert not row["alerting"] and doc["ok"]

    def test_proportional_burn_math(self):
        """2% of requests over a p99 ceiling = burn rate 2.0."""
        reg, sampler, burn = self._rig()
        sampler.sample_once(now=0.0)
        for _ in range(98):
            reg.observe("s", "lat", 0.1)
        for _ in range(2):
            reg.observe("s", "lat", 2.0)
        sampler.sample_once(now=1.0)
        doc = burn.evaluate(now=1.0)
        window = doc["targets"][0]["windows"]["5s"]
        assert window == {"over": 2, "total": 100, "fraction": 0.02,
                          "burn_rate": 2.0}


# ---------------------------------------------------------------------------
# host-runtime attribution
# ---------------------------------------------------------------------------

class TestHostProfiler:
    def test_subsystem_prefix_table(self):
        assert subsystem_for("cadence-pack-3") == "feeder-pack"
        assert subsystem_for("wirec-pack-0") == "feeder-pack"
        assert subsystem_for("cadence-serving-drain") == "serving-drain"
        assert subsystem_for("cadence-rpc-dispatch") == "rpc-dispatch"
        assert subsystem_for("cadence-task-worker-2") == "task-workers"
        assert subsystem_for("cadence-timeseries") == "telemetry"
        assert subsystem_for("MainThread") == "main"
        assert subsystem_for("Thread-17") == "other"

    def _spin_threads(self):
        """One runnable spinner + one parked waiter, both framework-named
        (the shapes the profiler must tell apart)."""
        stop = threading.Event()

        def spin():
            while not stop.is_set():
                sum(range(2000))

        spinner = threading.Thread(target=spin, daemon=True,
                                   name="cadence-pack-0")
        waiter = threading.Thread(target=stop.wait, daemon=True,
                                  name="cadence-serving-drain")
        spinner.start()
        waiter.start()
        return stop, spinner, waiter

    @staticmethod
    def _exclude_strangers(monkeypatch):
        """Hide from the sampler the threads this PROCESS had before the
        test began and that carry no framework name: the test runner's
        own — a pytest-xdist worker keeps an execnet I/O thread that was
        started below `threading` (it has a frame but no Thread object,
        so it samples as "tid-N"), one of only three threads sampled
        here — and any daemon an earlier test of the same worker left
        parked. The gate below is about the threads the FRAMEWORK
        starts; a host process has no such strangers in it."""
        named = {t.ident: t.name for t in threading.enumerate()}
        real_frames = sys._current_frames
        strangers = {ident for ident in real_frames()
                     if subsystem_for(named.get(ident, "")) == "other"}
        monkeypatch.setattr(
            sys, "_current_frames",
            lambda: {ident: frame for ident, frame in real_frames().items()
                     if ident not in strangers})

    def test_attribution_gate_on_named_threads(self, monkeypatch):
        """The ISSUE acceptance gate: >= 90% of sampled wall time lands
        on named subsystems when the process's threads are named."""
        reg = MetricsRegistry()
        prof = HostProfiler(reg, period_s=0.01)
        self._exclude_strangers(monkeypatch)
        stop, spinner, waiter = self._spin_threads()
        try:
            for _ in range(40):
                prof.sample_once()
                time.sleep(0.005)
        finally:
            stop.set()
            spinner.join(timeout=2)
            waiter.join(timeout=2)
        assert prof.attributed_share() >= 0.9
        rollup = prof.rollup()
        assert rollup["samples"] == 40
        # >= not ==: other suites may leave parked framework threads
        # behind (executor pack pools are process-lived daemons), and
        # those share the spinner's/waiter's subsystems by design
        assert rollup["subsystems"]["feeder-pack"]["samples"] >= 40
        assert rollup["subsystems"]["serving-drain"]["samples"] >= 40
        assert 0.0 <= rollup["gil_contention"] <= 1.0
        # the spinner burned real CPU; the parked waiter did not
        assert rollup["subsystems"]["feeder-pack"]["cpu_s"] > 0.01
        assert rollup["subsystems"]["serving-drain"]["cpu_s"] < \
            rollup["subsystems"]["feeder-pack"]["cpu_s"]
        # the top-of-stack table points into the spinner's hot frame
        assert any(row["subsystem"] == "feeder-pack"
                   for row in rollup["top"])

    def test_waiting_threads_are_not_runnable(self):
        reg = MetricsRegistry()
        prof = HostProfiler(reg, period_s=0.01)
        stop = threading.Event()
        waiter = threading.Thread(target=stop.wait, daemon=True,
                                  name="cadence-serving-drain")
        waiter.start()
        try:
            runnable_before = prof.rollup()["runnable_samples"]
            for _ in range(10):
                prof.sample_once()
                time.sleep(0.002)
            # a parked Event.wait thread contributes wall samples but no
            # runnable ones; the pytest main thread may or may not be
            # mid-wait, so only assert the waiter's subsystem landed
            assert prof.rollup()["subsystems"]["serving-drain"][
                "samples"] >= 10
            assert runnable_before == 0
        finally:
            stop.set()
            waiter.join(timeout=2)

    def test_publishes_hostprof_gauges(self):
        reg = MetricsRegistry()
        prof = HostProfiler(reg, period_s=0.01)
        prof.sample_once()
        assert reg.gauge_value(m.SCOPE_HOSTPROF, "samples") == 1.0
        assert reg.gauge_value(m.SCOPE_HOSTPROF, "threads") >= 1.0
        assert 0.0 <= reg.gauge_value(
            m.SCOPE_HOSTPROF, "attributed-share") <= 1.0

    def test_thread_lifecycle(self):
        prof = HostProfiler(MetricsRegistry(), period_s=0.005)
        prof.start()
        try:
            deadline = time.monotonic() + 5
            while prof.samples < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert prof.samples >= 3
        finally:
            prof.stop()
        assert not any(t.name == "cadence-hostprof"
                       for t in threading.enumerate())


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_bounds_and_dropped_accounting(self):
        rec = FlightRecorder(capacity=16)
        for i in range(40):
            rec.emit("tick", i=i)
        stats = rec.stats()
        assert stats == {"capacity": 16, "ring": 16, "events": 40,
                         "dropped": 24, "dumps": 0}
        events = rec.snapshot()
        assert [e["i"] for e in events] == list(range(24, 40))
        assert rec.snapshot(last_n=3)[0]["i"] == 37
        # seq is a stable total order across drops
        assert [e["seq"] for e in events] == list(range(25, 41))

    def test_payload_clamping(self):
        rec = FlightRecorder(capacity=8)
        rec.emit("wide", s="x" * 1000, lst=list(range(100)),
                 d={f"k{i}": i for i in range(30)},
                 obj=object())
        (event,) = rec.snapshot()
        assert len(event["s"]) == MAX_STR + 1 and event["s"].endswith("…")
        assert len(event["lst"]) == 32
        assert len(event["d"]) == 16
        assert isinstance(event["obj"], str)
        rec.emit("too-many", **{f"f{i}": i for i in range(40)})
        event = rec.snapshot()[-1]
        # kind/t/seq + at most MAX_FIELDS payload fields
        assert len(event) <= flightrecorder.MAX_FIELDS + 3

    def test_dump_writes_jsonl_with_header(self, tmp_path):
        rec = FlightRecorder(capacity=8)
        rec.emit("a", n=1)
        rec.emit("b", n=2)
        path = rec.dump(str(tmp_path / "flight.jsonl"), reason="test")
        lines = [json.loads(l) for l in
                 open(path, encoding="utf-8").read().splitlines()]
        header = lines[0]
        assert header["schema"] == flightrecorder.SCHEMA
        assert header["reason"] == "test"
        assert header["events"] == 2 and header["dropped"] == 0
        assert [e["kind"] for e in lines[1:]] == ["a", "b"]
        assert rec.stats()["dumps"] == 1
        # atomic write: no temp litter next to the dump
        assert os.listdir(tmp_path) == ["flight.jsonl"]

    def test_metrics_attach_counts_events_and_dumps(self, tmp_path):
        reg = MetricsRegistry()
        rec = FlightRecorder(capacity=8)
        rec.metrics = reg
        rec.emit("a")
        rec.emit("b")
        rec.dump(str(tmp_path / "f.jsonl"))
        assert reg.counter("flightrec", "events") == 2
        assert reg.counter("flightrec", "dumps") == 1

    def test_env_knob_disables_emit(self, monkeypatch):
        monkeypatch.setenv(flightrecorder.ENV_ENABLED, "0")
        rec = FlightRecorder(capacity=8)
        rec.emit("a")
        assert rec.stats()["events"] == 0

    def test_default_recorder_reset_isolates(self):
        flightrecorder.emit("leak-check", x=1)
        assert flightrecorder.DEFAULT_RECORDER.stats()["events"] >= 1
        flightrecorder.reset_all()
        assert flightrecorder.DEFAULT_RECORDER.stats()["events"] == 0

    def test_sigterm_dumps_flight_record(self, tmp_path):
        """A SIGTERM'd process leaves its black box behind: the handler
        dumps, then the default disposition still kills the process."""
        dump = tmp_path / "term.jsonl"
        script = (
            "import os, signal, time\n"
            "from cadence_tpu.utils import flightrecorder as fr\n"
            "assert fr.install_dump_handlers()\n"
            "fr.emit('boot-event', step=1)\n"
            "os.kill(os.getpid(), signal.SIGTERM)\n"
            "time.sleep(30)\n"  # never reached: the re-raise kills us
        )
        proc = subprocess.run(
            [sys.executable, "-c", script], cwd=REPO, timeout=60,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": REPO,
                 "CADENCE_TPU_FLIGHTREC_DUMP": str(dump)})
        assert proc.returncode == -signal.SIGTERM
        lines = [json.loads(l) for l in dump.read_text().splitlines()]
        assert lines[0]["schema"] == flightrecorder.SCHEMA
        assert lines[0]["reason"] == "sigterm"
        kinds = [e["kind"] for e in lines[1:]]
        assert "boot-event" in kinds and "sigterm" in kinds

    def test_kill_mode_crashpoint_dumps_before_sigkill(self, tmp_path):
        """SIGKILL runs no handler — the black box must write out at the
        crashpoint trigger itself, so the post-mortem keeps the dead
        process's timeline (arm + fire events included)."""
        dump = tmp_path / "crash.jsonl"
        script = (
            "from cadence_tpu.engine import crashpoints\n"
            "from cadence_tpu.utils import flightrecorder as fr\n"
            "fr.emit('pre-crash', step=1)\n"
            "crashpoints.install(crashpoints.CrashPoint(\n"
            "    site=crashpoints.SITE_AFTER_WRITE, mode='kill'))\n"
            "crashpoints.fire(crashpoints.SITE_AFTER_WRITE)\n"
            "raise SystemExit('crashpoint did not fire')\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", script], cwd=REPO, timeout=60,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": REPO,
                 "CADENCE_TPU_FLIGHTREC_DUMP": str(dump)})
        assert proc.returncode == -signal.SIGKILL
        lines = [json.loads(l) for l in dump.read_text().splitlines()]
        assert lines[0]["reason"] == "crash"
        kinds = [e["kind"] for e in lines[1:]]
        assert kinds == ["pre-crash", "crashpoint-arm", "crashpoint-fire"]


# ---------------------------------------------------------------------------
# scrape-handler consistency under concurrent reset
# ---------------------------------------------------------------------------

class TestScrapeConsistency:
    def test_prometheus_rendering_vs_concurrent_reset(self):
        """Regression for the shallow-copy race: to_prometheus() now
        renders from raw_series()'s single-lock snapshot, so a reset (or
        observe) landing mid-render can never produce an exposition whose
        +Inf bucket disagrees with its own _count line."""
        reg = MetricsRegistry()
        stop = threading.Event()

        def writer():
            while not stop.is_set():
                for _ in range(5):
                    reg.observe("s", "lat", 0.01)
                    reg.inc("s", "reqs")
                reg.reset()

        thread = threading.Thread(target=writer, daemon=True)
        thread.start()
        try:
            for _ in range(300):
                text = reg.to_prometheus()
                inf = count = None
                for line in text.splitlines():
                    if line.startswith("cadence_lat_bucket") and \
                            'le="+Inf"' in line:
                        inf = float(line.rsplit(" ", 1)[1])
                    elif line.startswith("cadence_lat_count"):
                        count = float(line.rsplit(" ", 1)[1])
                if inf is not None or count is not None:
                    assert inf == count, text
        finally:
            stop.set()
            thread.join(timeout=5)


# ---------------------------------------------------------------------------
# admin verbs + fleet rollup math
# ---------------------------------------------------------------------------

class TestAdminTelemetry:
    def test_top_onebox(self, box):
        _run_one_workflow(box)
        doc = AdminHandler(box).top()
        summary = doc["hosts"]["onebox"]
        # the box's sampler anchored at construction: the admin sample
        # folds the whole build→now span into one window
        assert summary["windows"] >= 1
        assert summary["utilization"] >= 0.0
        assert "hostprof" in summary
        assert doc["cluster"]["hosts"] == 1
        assert doc["cluster"]["spread"]["hot_host"] == "onebox"

    def test_timeseries_verb_sees_workflow_traffic(self, box):
        _run_one_workflow(box)
        doc = AdminHandler(box).timeseries()
        rates = doc["windows"][-1]["rates"]
        assert any(key.startswith(m.SCOPE_FRONTEND_START)
                   for key in rates), rates

    def test_hostprof_verb_burst_samples(self, box):
        rollup = AdminHandler(box).hostprof(duration_s=0.05)
        assert rollup["samples"] >= 1
        assert "attributed_share" in rollup and "subsystems" in rollup

    def test_flightrec_verb_snapshot_and_dump(self, box, tmp_path):
        _run_one_workflow(box)
        doc = AdminHandler(box).flightrec(
            last_n=50, dump=str(tmp_path / "adm.jsonl"))
        kinds = {e["kind"] for e in doc["events"]}
        assert "txn-commit" in kinds  # the commit path's wide event
        assert doc["stats"]["events"] >= 1
        lines = (tmp_path / "adm.jsonl").read_text().splitlines()
        assert json.loads(lines[0])["reason"] == "admin"

    def test_summarize_windows_rollup_math(self):
        doc = {"windows": [
            {"utilization": 0.2, "binding_resource": "kernel",
             "legs": {"kernel": 0.2}, "saturation": {"queue_fill": 0.1},
             "gauges": {}},
            {"utilization": 0.6, "binding_resource": "pack",
             "legs": {"kernel": 0.1, "pack": 0.5},
             "saturation": {"queue_fill": 0.9},
             "gauges": {"slo/alerting": 1.0,
                        "slo/burn-rate-start-5s": 14.0,
                        "timeseries/windows": 2.0}},
        ]}
        summary = summarize_windows(doc)
        assert summary["windows"] == 2
        assert summary["utilization"] == pytest.approx(0.4)
        assert summary["legs"]["kernel"] == pytest.approx(0.3)
        assert summary["saturation"] == {"queue_fill": 0.9}  # latest wins
        # slo/* gauges surface with the prefix stripped; others don't leak
        assert summary["burn"] == {"alerting": 1.0,
                                   "burn-rate-start-5s": 14.0}
        assert summary["alerting"] is True
        empty = summarize_windows({"windows": []})
        assert empty["windows"] == 0 and empty["binding_resource"] == "idle"

    def test_cluster_rollup_spread_and_error_rows(self):
        hosts = {
            "host-0": {"utilization": 0.8, "legs": {"kernel": 3.0},
                       "alerting": False},
            "host-1": {"utilization": 0.1, "legs": {"pack": 1.0},
                       "alerting": True},
            "host-2": {"error": "URLError: refused"},
        }
        rollup = _cluster_rollup(hosts)
        assert rollup["hosts"] == 2  # the error row is excluded
        assert rollup["binding_resource"] == "kernel"  # summed-legs argmax
        assert rollup["alerting"] is True
        assert rollup["spread"] == {
            "hot_host": "host-0", "hot_utilization": 0.8,
            "cold_host": "host-1", "cold_utilization": 0.1,
            "utilization_delta": 0.7}
        assert _cluster_rollup({"h": {"error": "x"}})["hosts"] == 0

    def test_fleet_top_tolerates_dead_endpoint(self):
        doc = fleet_top({"dead": "127.0.0.1:1"}, timeout=0.5)
        assert "error" in doc["hosts"]["dead"]
        assert doc["cluster"]["hosts"] == 0


# ---------------------------------------------------------------------------
# scrape surface routes (onebox HTTP)
# ---------------------------------------------------------------------------

def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=10) as resp:
        assert resp.status == 200
        return resp.read()


@pytest.mark.smoke
class TestTelemetryScrapeSurface:
    def test_http_telemetry_routes(self, box):
        _run_one_workflow(box, "scrape-tel-wf")
        server = box.scrape_server().start()
        try:
            base = f"http://127.0.0.1:{server.port}"
            ts = json.loads(_get(f"{base}/timeseries"))
            assert ts["samples"] >= 2 and ts["windows"]
            hp = json.loads(_get(f"{base}/hostprof"))
            assert "attributed_share" in hp and "subsystems" in hp
            fr = json.loads(_get(f"{base}/flightrec"))
            assert {e["kind"] for e in fr["events"]} >= {"txn-commit"}
            # the flat /metrics scrape carries the plane's own health
            body = _get(f"{base}/metrics").decode()
            assert 'cadence_windows{scope="timeseries"}' in body
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# fleet `admin top` over a live wire cluster
# ---------------------------------------------------------------------------

@pytest.mark.smoke
class TestFleetTelemetryWire:
    def test_admin_top_over_live_cluster(self):
        """Two service hosts under real traffic: every host's /timeseries
        serves windows with burn-rate gauges, fleet_top aggregates them,
        and the wire admin ops answer."""
        from cadence_tpu.rpc.cluster import launch
        cluster = launch(num_hosts=2, num_shards=4,
                         env_extra={"CADENCE_TPU_TS_PERIOD_S": "0.2"})
        try:
            fe = cluster.frontend(0)
            fe.register_domain(DOMAIN)
            for i in range(6):
                fe.start_workflow_execution(DOMAIN, f"top-wf-{i}", "t", TL)
            time.sleep(1.2)  # >= 4 sampler ticks at 0.2s
            endpoints = {name: f"127.0.0.1:{port}"
                         for name, port in cluster.http_ports.items()}
            raw = scrape_timeseries(next(iter(endpoints.values())))
            assert raw["windows"] and raw["samples"] >= 2
            assert raw["slo"]["targets"]  # burn verdict rides the doc
            doc = fleet_top(endpoints)
            assert doc["cluster"]["hosts"] == 2
            for name, row in doc["hosts"].items():
                assert "error" not in row, row
                assert row["windows"] >= 2
                # the evaluator's gauges landed in the windows (one-tick
                # lag): every host reports its burn keys
                assert any(key.startswith("burn-rate-")
                           for key in row["burn"]), row["burn"]
            assert doc["cluster"]["spread"]["hot_host"] in doc["hosts"]

            name = sorted(cluster.hosts)[0]
            ts = cluster.admin(name, "admin_timeseries", 50)
            assert ts["windows"] and ts["host"] == name
            hp = cluster.admin(name, "admin_hostprof", 0.2)
            assert hp["samples"] >= 1
            assert hp["attributed_share"] >= 0.9  # every host thread named
            fr = cluster.admin(name, "admin_flightrec", 100, None)
            assert "host-boot" in {e["kind"] for e in fr["events"]}
        finally:
            cluster.stop()

    def test_cli_admin_top_wire_arm(self, capsys):
        """`cadence-tpu admin top --http` against a live host exits 0 and
        prints the fleet rollup JSON."""
        from cadence_tpu import cli
        from cadence_tpu.rpc.cluster import launch
        cluster = launch(num_hosts=1, num_shards=4,
                         env_extra={"CADENCE_TPU_TS_PERIOD_S": "0.2"})
        try:
            time.sleep(0.6)
            (name, port), = cluster.http_ports.items()
            rc = cli.main(["admin", "top", "--http",
                           f"{name}=127.0.0.1:{port}"])
            assert rc == 0
            doc = json.loads(capsys.readouterr().out)
            assert doc["cluster"]["hosts"] == 1
            assert name in doc["hosts"]
            # a dead endpoint in the fleet flips the exit code
            rc = cli.main(["admin", "top", "--http",
                           f"{name}=127.0.0.1:{port}",
                           "--http", "dead=127.0.0.1:1"])
            assert rc == 1
            doc = json.loads(capsys.readouterr().out)
            assert "error" in doc["hosts"]["dead"]
        finally:
            cluster.stop()

    def test_sigterm_host_dumps_own_flight_record(self, tmp_path):
        """The acceptance scenario: a SIGTERM'd host dumps its own flight
        record; a SIGKILL'd host's last interactions survive in its
        peers' rings (their events name the dead host's lifecycle)."""
        from cadence_tpu.rpc.cluster import launch
        dump = tmp_path / "host0-flight.jsonl"
        cluster = launch(
            num_hosts=2, num_shards=4,
            env_per_role={"host-0": {
                "CADENCE_TPU_FLIGHTREC_DUMP": str(dump)}})
        try:
            victim = sorted(cluster.hosts)[0]
            cluster.kill_host(victim, sig=signal.SIGTERM)
            deadline = time.monotonic() + 15
            while not dump.exists() and time.monotonic() < deadline:
                time.sleep(0.1)
            assert dump.exists(), "SIGTERM'd host left no flight record"
            # the dump may still be mid-replace; poll until it parses
            lines = []
            while time.monotonic() < deadline:
                try:
                    lines = [json.loads(l)
                             for l in dump.read_text().splitlines()]
                    break
                except ValueError:
                    time.sleep(0.1)
            assert lines[0]["schema"] == flightrecorder.SCHEMA
            assert lines[0]["reason"] == "sigterm"
            kinds = {e["kind"] for e in lines[1:]}
            assert "host-boot" in kinds and "sigterm" in kinds
            # the survivor's ring still answers and holds its own boot
            survivor = sorted(cluster.hosts)[1]
            fr = cluster.admin(survivor, "admin_flightrec", 200, None)
            assert "host-boot" in {e["kind"] for e in fr["events"]}
        finally:
            cluster.stop()
