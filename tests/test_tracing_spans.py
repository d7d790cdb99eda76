"""The one span recorder (utils/tracing.py) and what reads its spans.

- a profiler leg is a span and still lands in its histogram;
- spans cost no system call and never import jax;
- the served path puts a span at each boundary (`rpc.<op>` for an
  untraced client, `store.*` a round trip, `history.commit` and its
  `history.lock-wait`, `serving.*`), and the serving tier's stats carry
  the two totals;
- in a process with jax imported a span is an event of a live profiler
  session, on the profiler's clock;
- the benchmark's span readers (`benchmarks/layer_metrics/_spans.py` and
  the ten readers) on a hand-built trace.
"""
import glob
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from cadence_tpu.utils import metrics as m
from cadence_tpu.utils import tracing
from cadence_tpu.utils.profiler import SPAN_NAMES, ReplayProfiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = os.path.join(ROOT, "benchmarks", "layer_metrics")
DOMAIN, TL = "spans-domain", "spans-tl"


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

class TestRecorder:
    def test_a_leg_is_a_span_and_lands_in_its_histogram(self):
        reg = m.MetricsRegistry()
        prof = ReplayProfiler(reg, scope="tpu.replay-engine")
        with prof.leg(m.M_PROFILE_PACK) as leg:
            time.sleep(0.002)
        spans = tracing.DEFAULT_TRACER.finished_spans()
        assert [s.operation for s in spans] == ["pack"]
        hist = reg.histogram("tpu.replay-engine", m.M_PROFILE_PACK)
        # one clock: the histogram holds the span's own duration
        assert hist.count == 1
        assert hist.total == leg.duration_s == spans[0].duration_s
        assert leg.duration_s >= 0.002

    def test_the_kernel_leg_reads_device_wait_on_the_timeline(self):
        reg = m.MetricsRegistry()
        prof = ReplayProfiler(reg)
        with prof.leg(m.M_PROFILE_KERNEL):
            pass
        (span,) = tracing.DEFAULT_TRACER.finished_spans()
        assert span.operation == SPAN_NAMES[m.M_PROFILE_KERNEL] \
            == "device-wait"
        assert reg.histogram(prof.scope, m.M_PROFILE_KERNEL).count == 1
        assert reg.histogram(prof.scope, "device-wait").count == 0

    def test_the_programs_own_traces_cannot_push_a_kept_one_out(self):
        """Two rings: a trace rooted by an always-on site (a leg, a
        `tracing.span`, an untraced `rpc.<op>`) is background, one rooted
        through `Tracer.start_span` is kept with its whole subtree."""
        tr = tracing.Tracer(max_spans=4)
        with tr.start_span("client-op") as root:
            with tracing.Span(tr, "rpc.frontend"):
                with tracing.Span(tr, "store.history.append_batch"):
                    pass
        for i in range(50):
            with tracing.Span(tr, f"rpc.poll{i}"):
                pass
        kept = [s for s in tr.finished_spans()
                if s.trace_id == root.trace_id]
        assert [s.operation for s in kept] == \
            ["store.history.append_batch", "rpc.frontend", "client-op"]
        assert [s.operation for s in tr.finished_spans()][-4:] == \
            [f"rpc.poll{i}" for i in range(46, 50)]
        assert len(tr.finished_spans()) == 3 + 4

    def test_the_carrier_says_which_ring_the_remote_side_uses(self):
        tr, remote = tracing.Tracer(), tracing.Tracer()
        with tracing.Span(tr, "rpc.frontend"):            # always-on root
            env = tracing.inject(("store", "x"), tr)
        with tr.start_span("client-op"):                  # a caller's root
            kept_env = tracing.inject(("store", "x"), tr)
        assert env[1]["bg"] == 1 and "bg" not in kept_env[1]
        for envelope in (env, kept_env):
            ctx, _req = tracing.extract(envelope)
            with remote.start_span("rpc.store", child_of=ctx,
                                   background=True):
                pass
        assert [s.operation for s in remote._background] == ["rpc.store"]
        assert [s.trace_id for s in remote._finished] == \
            [kept_env[1]["trace_id"]]

    def test_h2d_counts_bytes_and_keeps_no_size_histogram(self):
        reg = m.MetricsRegistry()
        ReplayProfiler(reg).h2d(4096)
        assert reg.counter(m.SCOPE_TPU_REPLAY, m.M_H2D_BYTES) == 4096
        assert reg.histogram(m.SCOPE_TPU_REPLAY,
                             m.M_H2D_BYTES + "-per-transfer").count == 0

    def test_traced_records_the_spans_own_duration(self):
        class Service:
            metrics = m.MetricsRegistry()

            @tracing.traced("svc.op")
            def op(self):
                time.sleep(0.001)
                return 7

        svc = Service()
        assert svc.op() == 7
        (span,) = tracing.DEFAULT_TRACER.finished_spans()
        hist = svc.metrics.histogram("svc.op", "latency")
        assert hist.count == 1 and hist.total == span.duration_s

    def test_no_system_call_for_ids(self, monkeypatch):
        def boom(_n):
            raise AssertionError("os.urandom called for a span")

        monkeypatch.setattr(os, "urandom", boom)
        tr = tracing.Tracer()
        with tr.start_span("a") as a:
            with tr.start_span("b") as b:
                pass
        with tr.start_span("c") as c:
            pass
        ids = {a.span_id, b.span_id, c.span_id}
        assert len(ids) == 3 and all(len(i) == 16 for i in ids)
        assert a.trace_id == b.trace_id != c.trace_id
        assert b.parent_id == a.span_id

    def test_ids_are_unique_across_threads(self):
        tr = tracing.Tracer(max_spans=100_000)

        def work():
            for _ in range(2000):
                with tr.start_span("t"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        spans = tr.finished_spans()
        assert len(spans) == 8000
        assert len({s.span_id for s in spans}) == 8000

    def test_start_is_the_wall_clock_and_duration_the_monotonic(self):
        tr = tracing.Tracer()
        before = time.time()
        with tr.start_span("x") as span:
            time.sleep(0.003)
        assert before <= span.start_time <= time.time()
        assert 0.003 <= span.duration_s < 1.0
        assert span.to_dict()["start_time"] == round(span.start_ns / 1e9, 6)

    def test_the_ring_keeps_the_newest(self):
        tr = tracing.Tracer(max_spans=5)
        for i in range(12):
            with tr.start_span(f"s{i}"):
                pass
        assert [s.operation for s in tr.finished_spans()] == \
            [f"s{i}" for i in range(7, 12)]

    def test_dump_writes_each_span_once(self, tmp_path):
        tr = tracing.Tracer()
        assert tr.dump() is None   # no directory configured
        with tr.start_span("one"):
            pass
        path = tr.dump(str(tmp_path))
        with tr.start_span("two"):
            pass
        assert tr.dump(str(tmp_path)) == path
        tr.dump(str(tmp_path))   # nothing new: nothing written
        with open(path) as fh:
            ops = [json.loads(line)["operation"] for line in fh]
        assert ops == ["one", "two"]

    def test_dump_misses_no_span_that_ends_on_another_thread(self, tmp_path):
        """What is written is marked on the span, not counted beside the
        ring: spans that end while other threads end theirs are each
        written once."""
        tr = tracing.Tracer(max_spans=100_000)

        def work():
            for _ in range(1500):
                with tr.start_span("t"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            tr.dump(str(tmp_path))
        path = tr.dump(str(tmp_path))
        with open(path) as fh:
            ids = [json.loads(line)["span_id"] for line in fh]
        assert len(ids) == len(set(ids)) == 6000

    def test_export_is_on_dump_not_per_span(self, tmp_path, monkeypatch):
        monkeypatch.setenv("CADENCE_TPU_TRACE_EXPORT", str(tmp_path))
        tr = tracing.Tracer()
        with tr.start_span("buffered"):
            pass
        assert list(tmp_path.glob("spans-*.jsonl")) == []
        tr.dump()
        (path,) = tmp_path.glob("spans-*.jsonl")
        assert "buffered" in path.read_text()

    def test_tracing_never_imports_jax(self):
        code = (
            "import sys\n"
            "from cadence_tpu.utils import tracing\n"
            "from cadence_tpu.utils.profiler import ReplayProfiler\n"
            "with tracing.span('a'):\n"
            "    with ReplayProfiler().leg('pack'):\n"
            "        pass\n"
            "assert len(tracing.DEFAULT_TRACER.finished_spans()) == 2\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
        done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr

    def test_a_span_is_an_event_of_a_live_profiler_session(self, tmp_path):
        """The clock check: with jax imported a span is a TraceAnnotation,
        so the xplane's host plane holds it under its own name, on the
        thread that ran it. The xplane counts nanoseconds of the wall
        clock from the session's start, so a span's recorded start less
        its event's start is that one offset for every span, to within
        1 ms, and lies inside the call that started the trace."""
        import jax
        from jax.profiler import ProfileData

        jax.block_until_ready(jax.numpy.zeros(8) + 1)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        before_ns = time.time_ns()
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        after_ns = time.time_ns()
        try:
            with tracing.span("clock.outer") as outer:
                with tracing.span("clock.inner") as inner:
                    time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("clock."):
                        found[e.name] = (line.name, e.start_ns,
                                         e.start_ns + e.duration_ns)
        assert set(found) == {"clock.outer", "clock.inner"}
        offsets = []
        for span in (outer, inner):
            _line, lo, hi = found[span.operation]
            offsets.append(span.start_ns - lo)
            # the event encloses the span's own clock pair
            assert (hi - lo) >= span.duration_ns
        assert abs(offsets[0] - offsets[1]) < 1e6, offsets
        assert before_ns <= offsets[0] <= after_ns, (
            before_ns, offsets, after_ns)
        assert found["clock.outer"][0] == found["clock.inner"][0]
        assert found["clock.outer"][1] <= found["clock.inner"][1]
        assert found["clock.inner"][2] <= found["clock.outer"][2]


# ---------------------------------------------------------------------------
# the served path's spans
# ---------------------------------------------------------------------------

class TestStoreCallSpans:
    def _pool(self, replies):
        from cadence_tpu.rpc.client import _Pool
        from cadence_tpu.utils.backoff import RetryPolicy

        reg = m.MetricsRegistry()
        pool = _Pool(("127.0.0.1", 1), metrics=reg, retry_policy=RetryPolicy(
            init_interval_s=0.001, max_interval_s=0.002, max_attempts=4,
            expiration_s=5.0))
        calls = []

        def call_once(request):
            calls.append(request)
            reply = replies.pop(0)
            if isinstance(reply, BaseException):
                raise reply
            return reply

        pool._call_once = call_once
        return pool, reg, calls

    def test_one_span_a_round_trip_named_by_store_and_method(self):
        pool, _reg, _calls = self._pool(["a", "b"])
        assert pool.call(("store", "execution", "get_workflow", (), {})) == "a"
        assert pool.call(("store", "history", "append_batch", (), {})) == "b"
        assert [s.operation for s in
                tracing.DEFAULT_TRACER.finished_spans()] == \
            ["store.execution.get_workflow", "store.history.append_batch"]

    def test_a_retry_stays_inside_the_one_span(self):
        from cadence_tpu.engine.faults import TransientStoreError

        pool, reg, calls = self._pool([TransientStoreError("x"), "ok"])
        assert pool.call(("store", "shard", "get_or_create", (1,), {})) == "ok"
        assert len(calls) == 2
        assert reg.counter("rpc.client", "retries") == 1
        (span,) = tracing.DEFAULT_TRACER.finished_spans()
        assert span.operation == "store.shard.get_or_create"

    def test_other_ops_of_the_pool_are_no_store_span(self):
        pool, _reg, _calls = self._pool(["pong"])
        assert pool.call(("ping",)) == "pong"
        assert tracing.DEFAULT_TRACER.finished_spans() == []


@pytest.fixture(scope="module")
def wire_cluster():
    from cadence_tpu.rpc.cluster import launch

    cluster = launch(num_hosts=1, num_shards=4)
    try:
        fe = cluster.frontend(0)
        fe.register_domain(DOMAIN)
        yield cluster, fe
    finally:
        cluster.stop()


def _host_spans(cluster):
    (_name, http_port), = cluster.http_ports.items()
    with urllib.request.urlopen(
            f"http://127.0.0.1:{http_port}/traces", timeout=10) as rsp:
        traces = json.loads(rsp.read())
    return [s for spans in traces.values() for s in spans]


class TestWireSpans:
    def test_rpc_span_for_an_untraced_client(self, wire_cluster):
        """No span is active in this process, so the request carries no
        trace envelope: the host records `rpc.frontend` all the same, from
        the decoded frame to the reply sent, with `rpc.reply` its child."""
        cluster, fe = wire_cluster
        assert tracing.DEFAULT_TRACER.active_context() is None
        fe.start_workflow_execution(DOMAIN, "untraced-wf", "t", TL)
        time.sleep(0.05)   # the span closes after the reply is sent
        spans = _host_spans(cluster)
        start = next(s for s in spans
                     if s["operation"] == m.SCOPE_FRONTEND_START)
        by_id = {s["span_id"]: s for s in spans}
        rpc = by_id[start["parent_id"]]
        assert rpc["operation"] == "rpc.frontend"
        assert rpc["parent_id"] is None   # the client sent no context
        replies = [s for s in spans if s["operation"] == "rpc.reply"
                   and s["parent_id"] == rpc["span_id"]]
        assert len(replies) == 1
        assert rpc["duration_s"] >= start["duration_s"]

    def test_the_start_crosses_every_boundary_under_one_trace(
            self, wire_cluster):
        cluster, fe = wire_cluster
        fe.start_workflow_execution(DOMAIN, "boundary-wf", "t", TL)
        fe.signal_workflow_execution(DOMAIN, "boundary-wf", "go")
        time.sleep(0.05)
        spans = _host_spans(cluster)
        signal = next(s for s in spans
                      if s["operation"] == m.SCOPE_FRONTEND_SIGNAL)
        ops = [s["operation"] for s in spans
               if s["trace_id"] == signal["trace_id"]]
        assert "rpc.frontend" in ops and "rpc.reply" in ops
        assert m.SCOPE_HISTORY_SIGNAL in ops
        assert "history.commit" in ops and "history.lock-wait" in ops
        stores = [op for op in ops if op.startswith("store.")]
        assert "store.execution.update_workflow" in stores
        assert "store.history.append_batch" in stores
        # the lock wait is inside the commit, the store calls under it
        by_id = {s["span_id"]: s for s in spans}
        wait = next(s for s in spans if s["operation"] == "history.lock-wait"
                    and s["trace_id"] == signal["trace_id"])
        assert by_id[wait["parent_id"]]["operation"] == "history.commit"

    def test_signal_with_start_has_its_own_frontend_span(self, wire_cluster):
        cluster, fe = wire_cluster
        fe.signal_with_start_workflow_execution(DOMAIN, "sws-wf", "sig",
                                                "t", TL)
        time.sleep(0.05)
        ops = {s["operation"] for s in _host_spans(cluster)}
        assert m.SCOPE_FRONTEND_SIGNAL_WITH_START in ops

    def test_a_long_poll_parks_under_poll_wait(self, wire_cluster):
        cluster, fe = wire_cluster
        assert fe.poll_for_decision_task(DOMAIN, "spans-empty-tl",
                                         wait_seconds=0.1) is None
        time.sleep(0.05)
        spans = _host_spans(cluster)
        wait = next(s for s in spans
                    if s["operation"] == "matching.poll-wait")
        assert wait["duration_s"] >= 0.09
        by_id = {s["span_id"]: s for s in spans}
        assert by_id[wait["parent_id"]]["operation"] == \
            m.SCOPE_MATCHING_POLL_DECISION

    def test_the_host_profiles_only_while_asked(self, wire_cluster):
        """No sampler thread runs in a service host: `/hostprof` and
        `admin_hostprof` sample for the duration the request gives."""
        cluster, _fe = wire_cluster
        (name,) = cluster.procs
        (_n, http_port), = cluster.http_ports.items()

        def get(query=""):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/hostprof{query}",
                    timeout=10) as rsp:
                return json.loads(rsp.read())

        assert get()["samples"] == 0
        assert cluster.admin(name, "admin_hostprof", 0.0)["samples"] == 0
        asked = get("?duration_s=0.1")
        assert asked["samples"] >= 2 and asked["host"] == name
        assert "cadence-hostprof" not in json.dumps(asked["subsystems"])
        time.sleep(0.2)
        assert get()["samples"] == asked["samples"]   # and none since
        more = cluster.admin(name, "admin_hostprof", 0.05)
        assert more["samples"] > asked["samples"]

    def test_the_device_trace_verb_puts_spans_on_the_timeline(
            self, wire_cluster, tmp_path):
        """`admin_device_trace` start/stop on the host: the xplane it
        writes holds the program's spans by name, on the dispatch thread."""
        from jax.profiler import ProfileData

        cluster, fe = wire_cluster
        (name,) = cluster.procs
        started = cluster.admin(name, "admin_device_trace", "start",
                                str(tmp_path), timeout=120)
        assert started["tracing"] is True
        fe.start_workflow_execution(DOMAIN, "device-trace-wf", "t", TL)
        time.sleep(0.05)   # rpc.frontend closes after the reply is sent
        stopped = cluster.admin(name, "admin_device_trace", "stop",
                                timeout=300)
        assert stopped["tracing"] is False and stopped["window_s"] > 0
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        names = set()
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    names.update(e.name for e in line.events)
        assert {"rpc.frontend", m.SCOPE_FRONTEND_START,
                m.SCOPE_HISTORY_START_WORKFLOW, "history.commit",
                "rpc.reply"} <= names
        assert any(n.startswith("store.") for n in names)
        with pytest.raises(Exception):
            cluster.admin(name, "admin_device_trace", "bogus")


class TestServingTierSpans:
    def test_a_flush_is_a_span_with_its_phases_inside(self):
        from tests.test_serving import _Harness

        h = _Harness(workflows=2)
        k = h.keys[0]
        h.counts[k] = len(h.by_key[k]) - 1
        h.submit(k)
        with h.sched._prof.leg(m.M_PROFILE_SERVING, span="serving.flush"):
            h.flush()   # cold admit
        h.counts[k] += 1
        ticket = h.submit(k)
        tracing.DEFAULT_TRACER.reset()
        with h.sched._prof.leg(m.M_PROFILE_SERVING, span="serving.flush"):
            h.flush()   # suffix append
        assert ticket.result(timeout=1).path == "suffix"
        spans = tracing.DEFAULT_TRACER.finished_spans()
        flush = next(s for s in spans if s.operation == "serving.flush")
        inside = {s.operation for s in spans
                  if s.trace_id == flush.trace_id}
        # the suffix append's wait is the resident cache's own span
        assert {"serving.route", "serving.pack", "resident.device-wait",
                "serving.parity"} <= inside
        assert not {"serving.launch", "serving.device-wait"} & inside

    def test_a_cold_flush_launches_and_waits_under_its_own_spans(self):
        from tests.test_serving import _Harness

        h = _Harness(workflows=1)
        k = h.keys[0]
        h.counts[k] = len(h.by_key[k])
        ticket = h.submit(k)
        with h.sched._prof.leg(m.M_PROFILE_SERVING, span="serving.flush"):
            h.flush()
        assert ticket.result(timeout=1).path == "cold"
        spans = tracing.DEFAULT_TRACER.finished_spans()
        flush = next(s for s in spans if s.operation == "serving.flush")
        by_id = {s.span_id: s for s in spans}
        for name in ("serving.route", "serving.pack", "serving.launch",
                     "serving.device-wait", "serving.parity"):
            span = next(s for s in spans if s.operation == name)
            assert by_id[span.parent_id] is flush

    def test_stats_carry_the_two_totals_and_they_only_grow(self):
        from tests.test_serving import _Harness

        h = _Harness(workflows=3)
        first = h.sched.stats()
        assert first["queue_wait_s_total"] == 0.0 == first["flush_s_total"]
        seen = [first]
        for k in h.keys:
            h.counts[k] = len(h.by_key[k]) - 1
            h.submit(k)
            time.sleep(0.002)
            with h.sched._prof.leg(m.M_PROFILE_SERVING,
                                   span="serving.flush"):
                h.flush()
            seen.append(h.sched.stats())
        for before, after in zip(seen, seen[1:]):
            assert after["queue_wait_s_total"] > before["queue_wait_s_total"]
            assert after["flush_s_total"] > before["flush_s_total"]
        assert seen[-1]["queue_wait_s_total"] >= 3 * 0.002

    def test_the_drain_thread_idles_under_its_own_span(self):
        from tests.test_serving import _Harness

        h = _Harness(workflows=1)
        del h.sched._ensure_thread   # the real drain thread
        k = h.keys[0]
        h.counts[k] = len(h.by_key[k]) - 1
        h.submit(k)
        assert h.sched.drain(timeout=60)
        time.sleep(0.25)   # the drain goes back to waiting for work
        h.sched.stop()
        ops = [s.operation for s in tracing.DEFAULT_TRACER.finished_spans()]
        assert "serving.flush" in ops and "serving.idle-wait" in ops


class TestBulkPathSpans:
    def test_a_feed_call_lays_its_legs_and_waits_on_the_timeline(self):
        from cadence_tpu.gen.corpus import generate_history
        from cadence_tpu.native.feeder import feed_corpus_wirec

        hists = [generate_history("basic", seed=5, workflow_index=i,
                                  target_events=24) for i in range(12)]
        before = m.DEFAULT_REGISTRY.histogram(m.SCOPE_TPU_REPLAY,
                                              m.M_PROFILE_PACK_WAIT).total
        _crc, err, report = feed_corpus_wirec(hists, chunk_workflows=4)
        assert not err.any() and report.chunks == 3
        spans = tracing.DEFAULT_TRACER.finished_spans()
        call = next(s for s in spans if s.operation == "feed.call")
        names = [s.operation for s in spans if s.trace_id == call.trace_id]
        # per call, not per chunk
        for once in ("feed.setup", "feed.first-chunk-wait", "feed.gather"):
            assert names.count(once) == 1
        # per chunk: the legs, the first wait under its own name
        assert names.count("pack-queue-wait") == 2
        assert names.count("h2d") == names.count("device-wait") == \
            names.count("readback") == 3
        # the pack pool's threads root their own traces
        pool = [s.operation for s in spans if s.trace_id != call.trace_id]
        assert pool.count("pack") == 3
        assert pool.count("pack.first-profile-wait") == 2
        # chunk 0's measure pass (the native encoder's second decode of
        # the chunk) has a name of its own inside its `pack`
        assert pool.count("pack.measure") == (1 if report.native_wirec
                                              else 0)
        assert report.decode_passes == 3 + (1 if report.native_wirec else 0)
        # the legs' histograms hold what the spans measured, and the
        # report's own wait is the sum of the two kinds of wait span
        waits = [s.duration_s for s in spans if s.operation in
                 ("pack-queue-wait", "feed.first-chunk-wait")]
        assert report.pack_queue_wait_s == pytest.approx(sum(waits))
        after = m.DEFAULT_REGISTRY.histogram(m.SCOPE_TPU_REPLAY,
                                             m.M_PROFILE_PACK_WAIT).total
        assert after - before == pytest.approx(sum(waits))


# ---------------------------------------------------------------------------
# the benchmark's span readers, on a hand-built trace
# ---------------------------------------------------------------------------

def _reader(name: str):
    for path in (os.path.dirname(READERS), READERS):   # as run.py does
        if path not in sys.path:
            sys.path.insert(0, path)
    path = os.path.join(READERS, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ms(lo: float, hi: float):
    return lo * 1e6, hi * 1e6


def _op(t0: float, frontend: str, lock_wait: float, trips: int):
    """One served op on a dispatch thread, times in ms from `t0`:
    rpc.frontend 20 ms = 1 before the frontend call, 16 in it, 1 between,
    1.5 rpc.reply, 0.5 after; the frontend call is 2 ms of its own, a
    2 ms store read, and a 12 ms history call holding a 9 ms commit with
    `lock_wait` ms waiting and `trips` store calls of 1.5 ms."""
    def at(lo, hi):
        return _ms(t0 + lo, t0 + hi)

    history = "history." + frontend.split(".", 1)[1]
    events = [
        ("rpc.frontend", *at(0, 20)),
        (frontend, *at(1, 17)),
        ("store.domain.by_name", *at(1.5, 3.5)),
        (history, *at(4, 16)),
        ("history.commit", *at(5, 14)),
        ("history.lock-wait", *at(5, 5 + lock_wait)),
        ("history.hand-to-serving", *at(14.5, 15.5)),
        ("rpc.reply", *at(18, 19.5)),
        # the profiler's own events never count as spans
        ("frontend.py:237 start_workflow_execution", *at(1, 17)),
        ("PjitFunction(slice_row)", *at(6, 6.5)),
    ]
    cursor = 5 + lock_wait
    for i in range(trips):
        events.append((f"store.execution.call{i}", *at(cursor, cursor + 1.5)))
        cursor += 1.5
    return events


START = "frontend.start-workflow-execution"
SIGNAL = "frontend.signal-workflow-execution"
POLL = "frontend.poll-for-decision-task"


def _serve_ctx():
    dispatch_a = _op(0, START, 1.0, 4) + _op(100, SIGNAL, 0.0, 2)
    # a long poll is an op of no measured kind
    dispatch_b = _op(50, START, 2.0, 4) + [
        ("rpc.frontend", *_ms(200, 450)), (POLL, *_ms(201, 449)),
        ("matching.poll-wait", *_ms(210, 440))]
    drain = [
        ("serving.idle-wait", *_ms(0, 30)),
        ("serving.flush", *_ms(30, 50)),
        ("serving.route", *_ms(30, 32)),
        ("serving.launch", *_ms(32, 35)),
        ("serving.device-wait", *_ms(35, 41)),
        ("serving.flush", *_ms(60, 70)),
        ("resident.device-wait", *_ms(62, 64)),
    ]
    return {
        "kind": "serve",
        "trace": {"_host_lines": [("python3", dispatch_a),
                                  ("python3", dispatch_b),
                                  ("python3", drain)]},
        "serving_before": {"transactions": 100, "queue_wait_s_total": 1.0,
                           "batched_launches": 10, "flush_s_total": 0.5},
        "serving_after": {"transactions": 300, "queue_wait_s_total": 2.5,
                          "batched_launches": 50, "flush_s_total": 1.5},
    }


def _replay_ctx():
    main = [
        ("feed.call", *_ms(0, 1000)),
        ("feed.setup", *_ms(0, 20)),
        ("feed.first-chunk-wait", *_ms(21, 121)),
        ("h2d", *_ms(121, 125)),
        ("pack-queue-wait", *_ms(125, 130)),
        ("device-wait", *_ms(130, 900)),
        ("feed.gather", *_ms(990, 1000)),
        ("feed.call", *_ms(1100, 2100)),
        ("feed.first-chunk-wait", *_ms(1120, 1190)),
    ]
    pack = [("pack", *_ms(22, 120)),
            ("pack.first-profile-wait", *_ms(22, 119))]
    return {"kind": "replay",
            "trace": {"_host_lines": [("python3", main),
                                      ("python3", pack)]}}


#: reader -> (the hand-built trace's value, which ctx holds its spans)
EXPECTED = {
    # rpc.frontend self 20 - 16 - 1.5 = 2.5, rpc.reply self 1.5: 4.0 an op
    "rpc.dispatch_self_p50_ms": (4.0, "serve"),
    # 16 - 2 (store) - 12 (history)
    "frontend.self_p50_ms": (2.0, "serve"),
    # history call 12 - 9 - 1 = 2; commit 9 - wait - 1.5 * trips; hand-off 1:
    # 4.0, 5.0 and 9.0 for the three ops
    "history.self_p50_ms": (5.0, "serve"),
    "history.lock_wait_ms_per_op": (1.0, "serve"),
    # 2 + 1.5 * trips: 8, 8, 5
    "store.rpc_p50_ms_per_op": (8.0, "serve"),
    "store.round_trips_per_op": ((5 + 5 + 3) / 3, "serve"),
    # (20 - 6) and (10 - 2), over two flushes
    "serving.flush_host_ms_per_launch": (11.0, "serve"),
    "serving.ticket_wait_ms_mean": (7.5, "serve"),
    # 1.0 s of flushes in 40 launches
    "serving.flush_ms_per_launch": (25.0, "serve"),
    # (20 + 100 + 10) + 70 of 2000 ms
    "feed.first_chunk_share_pct": (10.0, "replay"),
}


class TestSpanReaders:
    def test_nesting_and_self_time(self):
        spans = _reader("_spans")
        (root,) = spans.trees(_op(0, START, 1.0, 4))
        assert root.name == "rpc.frontend"
        assert [c.name for c in root.children] == [START, "rpc.reply"]
        assert root.seconds == pytest.approx(0.020)
        assert root.self_seconds == pytest.approx(0.0025)
        frontend = root.children[0]
        assert [c.name for c in frontend.children] == \
            ["store.domain.by_name", "history.start-workflow-execution"]
        commit = next(n for n in root.walk() if n.name == "history.commit")
        assert [c.name for c in commit.children][:2] == \
            ["history.lock-wait", "store.execution.call0"]
        assert commit.self_seconds == pytest.approx(0.009 - 0.001 - 0.006)

    def test_the_parts_of_an_op_add_up_to_its_root(self):
        spans = _reader("_spans")
        ops = spans.measured_ops(_serve_ctx())
        assert len(ops) == 3   # the long poll is no measured op
        for op in ops:
            parts = spans.op_parts(op)
            assert parts["unexplained"] == pytest.approx(0.0, abs=1e-12)
            assert parts.pop("store_trips") in (3, 5)
            assert sum(parts.values()) == pytest.approx(op.seconds)

    def test_a_missing_span_shows_as_self_time_of_the_span_around_it(self):
        """`unexplained` cannot see a span the program lacks; the largest
        self time can: take the commit's store calls off the trace and the
        commit's own time is a third of the op."""
        spans = _reader("_spans")
        whole = _op(0, START, 1.0, 4)
        (op,) = spans.trees(whole)
        top = spans.largest_self(op)
        assert top.name == "rpc.frontend"
        assert top.self_seconds / op.seconds == pytest.approx(0.125)
        (op,) = spans.trees([e for e in whole
                             if not e[0].startswith("store.execution.")])
        assert spans.op_parts(op)["unexplained"] == pytest.approx(0.0)
        top = spans.largest_self(op)
        assert top.name == "history.commit"
        assert top.self_seconds / op.seconds == pytest.approx(0.4)

    def test_only_the_programs_spans_count(self):
        spans = _reader("_spans")
        assert spans.is_span("rpc.frontend") and spans.is_span("device-wait")
        assert spans.is_span("store.history.append_batch")
        assert not spans.is_span("frontend.py:237 start_workflow_execution")
        assert not spans.is_span("PjitFunction(slice_row)")
        assert not spans.is_span("$<unknown> acquire")
        assert not spans.is_span("history_engine.py:1722 commit")

    @pytest.mark.parametrize("name", sorted(EXPECTED))
    def test_reader_gives_the_expected_value(self, name):
        value, kind = EXPECTED[name]
        ctx = _serve_ctx() if kind == "serve" else _replay_ctx()
        assert _reader(name).read(ctx) == pytest.approx(value)

    @pytest.mark.parametrize("name", sorted(EXPECTED))
    def test_reader_gives_none_without_its_spans(self, name):
        """The parent commit's trace holds no span and its stats no total:
        nothing to read, and no error."""
        read = _reader(name).read
        bare = [("PjitFunction(slice_row)", *_ms(0, 5)),
                ("$<unknown> get", *_ms(5, 9))]
        for kind in ("serve", "replay"):
            ctx = {"kind": kind, "calls": [], "window_s": 30.0,
                   "trace": {"_host_lines": [("python3", bare)]},
                   "serving_before": {"transactions": 1,
                                      "batched_launches": 1},
                   "serving_after": {"transactions": 9,
                                     "batched_launches": 4}}
            assert read(ctx) is None
            assert read({"kind": kind, "trace": None}) is None

    def test_every_new_reader_is_a_benchmark_entry(self):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
            bench = json.load(fh)
        cells = {c["name"] for c in bench["workloads"]}
        entries = {e["name"]: e for e in bench["per_layer"]}
        for name, (_value, kind) in EXPECTED.items():
            entry = entries[name]
            assert os.path.isfile(os.path.join(READERS, name + ".py"))
            assert set(entry["workloads"]) <= cells
            assert all(w.startswith(kind) for w in entry["workloads"])
