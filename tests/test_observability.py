"""Observability layer (ISSUE 1): histogram bucket math + percentiles,
Prometheus text rendering, trace propagation (in-process nesting, wire
envelope round-trip, cross-process stitching), the /metrics + /health
scrape surface, and the replay profiler's leg decomposition.
"""
import json
import socket
import urllib.request

import pytest

from cadence_tpu.engine.onebox import Onebox
from cadence_tpu.models.deciders import CompleteDecider
from cadence_tpu.utils import metrics as m
from cadence_tpu.utils import tracing
from cadence_tpu.utils.metrics import HistogramStat, MetricsRegistry
from cadence_tpu.utils.profiler import ReplayProfiler
from tests.taskpoller import TaskPoller

DOMAIN = "obs-domain"
TL = "obs-tl"


@pytest.fixture()
def box():
    b = Onebox(num_hosts=2, num_shards=8)
    b.frontend.register_domain(DOMAIN)
    return b


def _run_one_workflow(b: Onebox, workflow_id: str = "obs-wf") -> None:
    b.frontend.start_workflow_execution(DOMAIN, workflow_id, "t", TL)
    TaskPoller(b, DOMAIN, TL, {workflow_id: CompleteDecider()}).drain()


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_bucket_math_le_semantics(self):
        h = HistogramStat(bounds=(0.005, 0.01, 0.05))
        h.observe(0.003)   # <= 0.005
        h.observe(0.005)   # boundary lands in its own bucket (le semantics)
        h.observe(0.02)    # <= 0.05
        h.observe(99.0)    # +Inf overflow
        assert h.count == 4
        assert h.bucket_counts == [2, 0, 1, 1]
        assert h.cumulative() == [("0.005", 2), ("0.01", 2),
                                  ("0.05", 3), ("+Inf", 4)]

    def test_percentile_interpolation(self):
        h = HistogramStat(bounds=(0.025, 0.05, 0.1))
        for _ in range(100):
            h.observe(0.03)  # all in the (0.025, 0.05] bucket
        # p50 target = 50th of 100 obs, halfway through the bucket:
        # 0.025 + (0.05 - 0.025) * 0.5
        assert h.percentile(0.5) == pytest.approx(0.0375)
        assert h.percentile(0.0) == pytest.approx(0.025, abs=0.025)
        # overflow clamps to the top finite bound
        h2 = HistogramStat(bounds=(0.01,))
        h2.observe(5.0)
        assert h2.percentile(0.99) == 0.01

    def test_empty_histogram_is_safe(self):
        h = HistogramStat()
        assert h.count == 0 and h.percentile(0.5) == 0.0

    def test_registry_record_feeds_histogram(self):
        r = MetricsRegistry()
        r.record("s", m.M_LATENCY, 0.004)
        r.record("s", m.M_LATENCY, 0.004)
        hist = r.histogram("s", m.M_LATENCY)
        assert hist.count == 2
        assert r.percentiles("s", m.M_LATENCY)["p50"] > 0
        snap = r.snapshot()["s"]
        assert snap["latency.count"] == 2
        assert snap["latency.p50"] > 0

    def test_registry_reset(self):
        r = MetricsRegistry()
        r.inc("s", "requests")
        r.record("s", "latency", 0.1)
        r.gauge("s", "g", 1.0)
        r.observe("s", "h", 2.0)
        r.reset()
        assert r.snapshot() == {}
        assert r.counter("s", "requests") == 0


# ---------------------------------------------------------------------------
# prometheus rendering
# ---------------------------------------------------------------------------

class TestPrometheus:
    def test_exact_text_format(self):
        r = MetricsRegistry()
        r.inc("history.start-workflow-execution", "requests", 3)
        r.gauge("tpu.replay-engine", "replay-events-per-sec", 12.5)
        r.observe("tpu.replay-engine", "latency", 0.004,
                  buckets=(0.005, 0.01))
        lines = r.to_prometheus().splitlines()
        assert "# TYPE cadence_requests_total counter" in lines
        assert ('cadence_requests_total'
                '{scope="history.start-workflow-execution"} 3') in lines
        assert "# TYPE cadence_replay_events_per_sec gauge" in lines
        assert ('cadence_replay_events_per_sec'
                '{scope="tpu.replay-engine"} 12.5') in lines
        assert "# TYPE cadence_latency histogram" in lines
        assert ('cadence_latency_bucket'
                '{scope="tpu.replay-engine",le="0.005"} 1') in lines
        assert ('cadence_latency_bucket'
                '{scope="tpu.replay-engine",le="0.01"} 1') in lines
        assert ('cadence_latency_bucket'
                '{scope="tpu.replay-engine",le="+Inf"} 1') in lines
        assert ('cadence_latency_sum'
                '{scope="tpu.replay-engine"} 0.004') in lines
        assert ('cadence_latency_count'
                '{scope="tpu.replay-engine"} 1') in lines

    def test_name_sanitization_and_type_dedup(self):
        r = MetricsRegistry()
        r.inc("a", "tasks-dropped-entity-not-exists")
        r.inc("b", "tasks-dropped-entity-not-exists")
        text = r.to_prometheus()
        assert text.count(
            "# TYPE cadence_tasks_dropped_entity_not_exists_total counter") == 1
        assert 'cadence_tasks_dropped_entity_not_exists_total{scope="a"} 1' in text

    def test_empty_registry_renders_empty(self):
        assert MetricsRegistry().to_prometheus() == ""


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

class TestTracing:
    def test_nested_spans_share_trace_and_parent(self):
        tr = tracing.Tracer()
        with tr.start_span("outer") as outer:
            with tr.start_span("inner") as inner:
                pass
        assert inner.context.trace_id == outer.context.trace_id
        assert inner.parent_id == outer.context.span_id
        assert outer.parent_id is None
        assert {s.operation for s in tr.finished_spans()} == {"outer", "inner"}
        assert all(s.duration_s >= 0 for s in tr.finished_spans())

    def test_error_tagging(self):
        tr = tracing.Tracer()
        with pytest.raises(ValueError):
            with tr.start_span("boom"):
                raise ValueError("x")
        (span,) = tr.finished_spans()
        assert span.tags["error"] == "ValueError"

    def test_inject_passthrough_without_active_span(self):
        tr = tracing.Tracer()
        assert tracing.inject(("ping",), tracer=tr) == ("ping",)
        assert tracing.extract(("ping",)) == (None, ("ping",))

    def test_wire_envelope_round_trip(self):
        """Inject → length-prefixed frame over a real socket → extract:
        the carrier survives the wire byte-for-byte."""
        from cadence_tpu.rpc import wire

        tr = tracing.Tracer()
        request = ("frontend", "start_workflow_execution", ("d", "w"), {})
        client, server = socket.socketpair()
        try:
            with tr.start_span("client.call") as span:
                wire.send_frame(client, tracing.inject(request, tracer=tr))
            ctx, inner = tracing.extract(wire.recv_frame(server))
        finally:
            client.close()
            server.close()
        assert inner == request
        assert ctx is not None
        assert ctx.trace_id == span.context.trace_id
        assert ctx.span_id == span.context.span_id
        # a server span parented on the extracted context stitches into
        # the client's trace
        tr2 = tracing.Tracer()
        with tr2.start_span("rpc.frontend", child_of=ctx) as server_span:
            pass
        assert server_span.context.trace_id == span.context.trace_id
        assert server_span.parent_id == span.context.span_id

    def test_malformed_carrier_is_tolerated(self):
        assert tracing.extract(("traced", "garbage", ("ping",))) == \
            (None, ("ping",))
        assert tracing.SpanContext.from_carrier({"trace_id": ""}) is None


class TestOneboxTraces:
    def test_frontend_history_matching_single_trace(self, box):
        """The acceptance trace: one poll chains frontend → matching →
        history synchronously, yielding ≥3 spans under one trace_id."""
        box.frontend.start_workflow_execution(DOMAIN, "tr-wf", "t", TL)
        box.pump_once()
        resp = box.frontend.poll_for_decision_task(DOMAIN, TL)
        assert resp is not None and resp.token is not None
        traces = box.tracer.traces()
        poll_traces = [spans for spans in traces.values()
                       if any(s.operation == m.SCOPE_FRONTEND_POLL_DECISION
                              for s in spans)]
        assert len(poll_traces) == 1
        ops = {s.operation for s in poll_traces[0]}
        assert {m.SCOPE_FRONTEND_POLL_DECISION,
                m.SCOPE_MATCHING_POLL_DECISION,
                m.SCOPE_HISTORY_RECORD_STARTED} <= ops
        assert len(poll_traces[0]) >= 3
        # the start call stitched its own frontend→history trace
        start_traces = [spans for spans in traces.values()
                        if any(s.operation == m.SCOPE_FRONTEND_START
                               for s in spans)]
        assert {m.SCOPE_FRONTEND_START, m.SCOPE_HISTORY_START_WORKFLOW} <= {
            s.operation for s in start_traces[0]}

    def test_traced_methods_record_latency_histograms(self, box):
        _run_one_workflow(box, "lat-wf")
        hist = box.metrics.histogram(m.SCOPE_HISTORY_START_WORKFLOW,
                                     m.M_LATENCY)
        assert hist.count >= 1 and hist.total > 0


# ---------------------------------------------------------------------------
# replay profiler
# ---------------------------------------------------------------------------

class TestReplayProfiler:
    def test_verify_all_records_leg_histograms(self, box):
        _run_one_workflow(box, "prof-wf")
        assert box.tpu.verify_all().ok
        for leg in (m.M_PROFILE_PACK, m.M_PROFILE_H2D,
                    m.M_PROFILE_KERNEL, m.M_PROFILE_READBACK):
            hist = box.metrics.histogram(m.SCOPE_TPU_REPLAY, leg)
            assert hist.count >= 1, f"missing {leg} leg"
        assert box.metrics.counter(m.SCOPE_TPU_REPLAY, m.M_H2D_BYTES) > 0
        summary = ReplayProfiler(box.metrics).summary()
        assert summary["kernel_launches"] >= 1
        assert summary["h2d_bytes"] > 0
        assert summary[m.M_PROFILE_KERNEL]["count"] >= 1
        assert summary[m.M_PROFILE_KERNEL]["total_s"] > 0

    def test_latency_histogram_decomposes(self, box):
        """The end-to-end replay latency carries a histogram (acceptance:
        a tpu.replay-engine latency histogram with non-zero counts)."""
        _run_one_workflow(box, "prof-wf2")
        box.tpu.verify_all()
        hist = box.metrics.histogram(m.SCOPE_TPU_REPLAY, m.M_LATENCY)
        assert hist.count >= 1


# ---------------------------------------------------------------------------
# scrape surface (the smoke target: deploy/smoke_observability.sh)
# ---------------------------------------------------------------------------

def _get(url: str) -> bytes:
    with urllib.request.urlopen(url, timeout=10) as resp:
        assert resp.status == 200
        return resp.read()


#: metric substrings /metrics MUST contain after one workflow + one replay
REQUIRED_METRICS = (
    'cadence_requests_total{scope="history.start-workflow-execution"}',
    'cadence_requests_total{scope="frontend.start-workflow-execution"}',
    'cadence_latency_bucket{scope="tpu.replay-engine"',
    'cadence_latency_count{scope="tpu.replay-engine"}',
    'cadence_kernel_launches_total{scope="tpu.replay-engine"}',
)


@pytest.mark.smoke
class TestScrapeSurface:
    def test_onebox_metrics_and_health_scrape(self, box):
        """Boot a cluster, run one workflow, replay it on device, scrape
        /metrics, fail on missing required metric names."""
        _run_one_workflow(box, "scrape-wf")
        assert box.tpu.verify_all().ok
        server = box.scrape_server().start()
        try:
            body = _get(
                f"http://127.0.0.1:{server.port}/metrics").decode()
            for required in REQUIRED_METRICS:
                assert required in body, f"/metrics missing {required}"
            # the tpu.replay-engine latency histogram has non-zero counts
            assert ('cadence_latency_count{scope="tpu.replay-engine"} 0'
                    not in body)
            health = json.loads(_get(
                f"http://127.0.0.1:{server.port}/health"))
            assert health["status"] == "ok"
            assert health["hosts"]
            traces = json.loads(_get(
                f"http://127.0.0.1:{server.port}/traces"))
            assert any(
                any(s["operation"] == m.SCOPE_FRONTEND_START for s in spans)
                for spans in traces.values())
        finally:
            server.stop()

    def test_admin_metrics_surface(self, box):
        from cadence_tpu.engine.admin import AdminHandler
        _run_one_workflow(box, "adm-wf")
        result = AdminHandler(box).metrics()
        assert result["snapshot"][m.SCOPE_HISTORY_START_WORKFLOW][
            m.M_REQUESTS] == 1
        assert "cadence_requests_total" in result["prometheus"]


# ---------------------------------------------------------------------------
# cross-process propagation (real sockets, real processes)
# ---------------------------------------------------------------------------

class TestCrossProcessTraces:
    def test_wire_cluster_stitches_one_trace(self, tmp_path, monkeypatch):
        """A traced client call crosses the wire: the ServiceHost parents
        its rpc.frontend span (and the in-host frontend/history spans) on
        the client's span — every process exports spans to
        CADENCE_TPU_TRACE_EXPORT and they stitch by trace_id. Also scrapes
        a real ServiceHost /metrics over HTTP."""
        monkeypatch.setenv("CADENCE_TPU_TRACE_EXPORT", str(tmp_path))
        from cadence_tpu.rpc.cluster import launch
        cluster = launch(num_hosts=1, num_shards=4)
        try:
            fe = cluster.frontend(0)
            fe.register_domain(DOMAIN)
            with tracing.DEFAULT_TRACER.start_span("client.start") as cs:
                fe.start_workflow_execution(DOMAIN, "mp-wf", "t", TL)
            trace_id = cs.context.trace_id
            # the ring is written out on dump() (and at exit), not per
            # span: ask every process for its spans
            from cadence_tpu.rpc import wire
            tracing.DEFAULT_TRACER.dump(str(tmp_path))
            for name in cluster.procs:
                cluster.admin(name, "admin_trace_dump")
            wire.call(("127.0.0.1", cluster.store_port),
                      ("admin_trace_dump",), timeout=10)
            spans = []
            for path in tmp_path.glob("spans-*.jsonl"):
                with open(path, "r", encoding="utf-8") as fh:
                    spans.extend(json.loads(line) for line in fh)
            stitched = [s for s in spans if s["trace_id"] == trace_id]
            ops = {s["operation"] for s in stitched}
            assert "rpc.frontend" in ops
            assert m.SCOPE_FRONTEND_START in ops
            assert m.SCOPE_HISTORY_START_WORKFLOW in ops
            # spans from another PROCESS joined the client's trace
            assert len({s["pid"] for s in stitched}) >= 3  # + the store
            # the server span parents directly on the client span
            rpc_span = next(s for s in stitched
                            if s["operation"] == "rpc.frontend")
            assert rpc_span["parent_id"] == cs.context.span_id
            # a running ServiceHost serves prometheus text over HTTP
            (name, http_port), = cluster.http_ports.items()
            body = _get(f"http://127.0.0.1:{http_port}/metrics").decode()
            assert ('cadence_requests_total'
                    '{scope="history.start-workflow-execution"} 1') in body
            health = json.loads(
                _get(f"http://127.0.0.1:{http_port}/health"))
            assert health["status"] == "ok" and health["name"] == name
        finally:
            cluster.stop()
