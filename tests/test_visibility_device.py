"""Device-visibility tier (ISSUE 12): columnar scan vs host parity.

The contract under test (engine/visibility_device.py + ops/scan.py):

- PARITY: for every query the device path serves, the result-id set
  (and for pages, the exact sequence + resume token) must equal the
  host `VisibilityStore` evaluation — fuzzed over random queries (all
  six ops, AND/OR nesting, custom search attributes, numeric + string
  values) and randomized record sets. Queries the kernels can't express
  fall back to the host and are COUNTED (never silently divergent).
- FRESHNESS: writes enqueue column deltas; a query flushes the backlog
  when it exceeds the staleness bound (and records the backlog it saw),
  or serves the stale view inside the bound.
- LIFECYCLE: capacity growth restages, attr columns past the budget or
  type-poisoned fall back, the kill switch routes straight to the host,
  and the admin rollup + tpu.visibility series surface all of it.
"""
import random

import pytest

from cadence_tpu.engine import visibility_device as vd
from cadence_tpu.engine.persistence import (
    VisibilityRecord,
    VisibilityStore,
)
from cadence_tpu.engine.visibility_query import (
    compile_query_with_hints,
    parse_query,
)
from cadence_tpu.utils import metrics as m

DOMAIN = "d-test"


@pytest.fixture
def vis_env(monkeypatch):
    monkeypatch.setenv("CADENCE_TPU_VISIBILITY", "1")
    monkeypatch.setenv("CADENCE_TPU_VISIBILITY_PARITY", "1")
    # a wide appender window: tests drive drains deterministically
    # through the query-path flush, never by racing the thread
    monkeypatch.setenv("CADENCE_TPU_VISIBILITY_WAIT_US", "5000000")
    yield


def _mk_record(rng: random.Random, i: int, attr_pool) -> VisibilityRecord:
    attrs = {}
    for name, kind in attr_pool:
        r = rng.random()
        if r < 0.4:
            continue  # absent on this record
        if kind == "num":
            attrs[name] = (rng.randrange(-5, 15) if rng.random() < 0.7
                           else round(rng.uniform(-2, 8), 2))
        elif kind == "str":
            attrs[name] = f"v{rng.randrange(6)}"
        else:  # mixed: poisons the device column, host handles per-row
            attrs[name] = (rng.randrange(4) if rng.random() < 0.5
                           else f"m{rng.randrange(3)}")
    rec = VisibilityRecord(
        domain_id=DOMAIN, workflow_id=f"wf-{i}", run_id=f"run-{i}",
        workflow_type=f"type-{rng.randrange(5)}",
        start_time=rng.randrange(0, 50) * 1_000 + rng.randrange(3),
        search_attrs=attrs)
    return rec


def _seed_store(rng: random.Random, n: int, attr_pool) -> VisibilityStore:
    store = VisibilityStore()
    for i in range(n):
        store.record_started(_mk_record(rng, i, attr_pool))
        if rng.random() < 0.45:
            store.record_closed(DOMAIN, f"wf-{i}", f"run-{i}",
                                close_time=rng.randrange(1, 10**6),
                                close_status=rng.randrange(0, 6))
    return store


_FIELDS = ("WorkflowID", "WorkflowType", "RunID", "CloseStatus",
           "StartTime", "CloseTime", "Num", "Str", "Mixed", "Absent")
_OPS = ("=", "!=", "<", "<=", ">", ">=")


def _rand_value(rng: random.Random, field: str):
    r = rng.random()
    if field == "WorkflowType" and r < 0.6:
        return f"'type-{rng.randrange(6)}'"
    if field in ("WorkflowID", "RunID") and r < 0.6:
        kind = "wf" if field == "WorkflowID" else "run"
        return f"'{kind}-{rng.randrange(40)}'"
    if field == "CloseStatus" and r < 0.4:
        return rng.choice(["'Completed'", "'Failed'", "-1", "0", "5"])
    if field == "Str" and r < 0.7:
        return f"'v{rng.randrange(8)}'"
    if r < 0.25:
        return f"'s{rng.randrange(4)}'"  # cross-type string
    if r < 0.5:
        return str(round(rng.uniform(-3, 12), 2))  # float
    if r < 0.6:
        return str(rng.randrange(0, 50) * 1_000)  # start-time-shaped
    return str(rng.randrange(-5, 15))


def _rand_query(rng: random.Random, depth: int = 2) -> str:
    if depth <= 0 or rng.random() < 0.45:
        field = rng.choice(_FIELDS)
        return f"{field} {rng.choice(_OPS)} {_rand_value(rng, field)}"
    left = _rand_query(rng, depth - 1)
    right = _rand_query(rng, depth - 1)
    joiner = "AND" if rng.random() < 0.5 else "OR"
    q = f"{left} {joiner} {right}"
    return f"({q})" if rng.random() < 0.3 else q


def _host_truth(store: VisibilityStore, query: str):
    """Ground truth WITHOUT the device tier: the compiled predicate
    over the raw record map (no index planner, no device)."""
    pred, _ = compile_query_with_hints(query)
    with store._lock:
        return {(r.workflow_id, r.run_id)
                for r in store._records.values()
                if r.domain_id == DOMAIN and pred(r)}


class TestFuzzParity:
    """The acceptance fuzz: random queries over random record sets must
    return identical result-id sets from the host predicate path and
    the device mask path — fallbacks counted, divergence pinned at 0."""

    ATTR_POOL = (("Num", "num"), ("Str", "str"), ("Mixed", "mixed"))

    @pytest.mark.parametrize("seed", [11, 23])
    def test_random_queries_identical_id_sets(self, vis_env, seed):
        rng = random.Random(seed)
        store = _seed_store(rng, 150, self.ATTR_POOL)
        reg = m.DEFAULT_REGISTRY
        queries = 0
        # shape pool: a bounded set of structures reused with fresh
        # values, so the run also proves variant-cache reuse
        shapes = [_rand_query(rng) for _ in range(18)]
        corpus = shapes + [_rand_query(rng) for _ in range(12)]
        for q in corpus:
            try:
                parse_query(q)
            except Exception:
                continue
            device_ids = {(r.workflow_id, r.run_id)
                          for r in store.query(DOMAIN, q)}
            assert device_ids == _host_truth(store, q), q
            queries += 1
            assert store.count(DOMAIN, q) == len(device_ids), q
        assert queries >= 25
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_DIVERGENCE) == 0
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_PARITY_CHECKS) > 0
        # the Mixed attr column poisons → those queries are COUNTED
        # fallbacks, not silent divergence
        served = reg.counter(m.SCOPE_TPU_VISIBILITY,
                             m.M_VIS_DEVICE_SERVED)
        fallbacks = reg.counter(m.SCOPE_TPU_VISIBILITY,
                                m.M_VIS_HOST_FALLBACKS)
        assert served > 0
        assert served + fallbacks >= 2 * queries
        store._device.stop()

    def test_string_ordering_falls_back_counted(self, vis_env):
        store = _seed_store(random.Random(5), 40, self.ATTR_POOL)
        reg = m.DEFAULT_REGISTRY
        ids = {(r.workflow_id, r.run_id)
               for r in store.query(DOMAIN, "WorkflowType > 'type-2'")}
        assert ids == _host_truth(store, "WorkflowType > 'type-2'")
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_FALLBACK_PREDICATE) >= 1
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_DIVERGENCE) == 0
        store._device.stop()


class TestIncrementalAppends:
    def test_writes_visible_through_device_path(self, vis_env):
        store = VisibilityStore()
        assert store.query(DOMAIN, "") == []  # empty store, staged view
        rec = VisibilityRecord(DOMAIN, "wf-a", "r-1", "order", 100)
        store.record_started(rec)
        assert store.count(DOMAIN, "CloseStatus = -1") == 1
        store.record_closed(DOMAIN, "wf-a", "r-1", close_time=200,
                            close_status=0)
        assert store.count(DOMAIN, "CloseStatus = -1") == 0
        assert store.count(DOMAIN, "CloseStatus = 0") == 1
        store.upsert_search_attributes(DOMAIN, "wf-a", "r-1",
                                       {"Priority": 7})
        assert [r.workflow_id
                for r in store.query(DOMAIN, "Priority >= 7")] == ["wf-a"]
        store.delete_record(DOMAIN, "wf-a", "r-1")
        assert store.count(DOMAIN, "") == 0
        assert m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_VISIBILITY,
                                          m.M_VIS_DIVERGENCE) == 0
        store._device.stop()

    def test_nan_attr_value_poisons_column(self, vis_env):
        """A NaN VALUE would alias the float column's null sentinel
        (host: nan != 3 matches; a device presence guard would drop
        the row) — the column must poison and fall back, counted."""
        store = VisibilityStore()
        store.record_started(VisibilityRecord(
            DOMAIN, "w0", "r0", "t", 1,
            search_attrs={"P": float("nan")}))
        store.record_started(VisibilityRecord(
            DOMAIN, "w1", "r1", "t", 2, search_attrs={"P": 3.0}))
        for q in ("P != 3", "P = 3", "P > 1"):
            got = {(r.workflow_id, r.run_id)
                   for r in store.query(DOMAIN, q)}
            assert got == _host_truth(store, q), q
        reg = m.DEFAULT_REGISTRY
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_FALLBACK_COLUMN) >= 1
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_DIVERGENCE) == 0
        assert not store._device._quarantined
        store._device.stop()

    def test_deleted_rows_are_reused(self, vis_env):
        """Churn (retention deletes + new starts) must not grow the
        table: freed rows go back to the pool."""
        store = VisibilityStore()
        for i in range(8):
            store.record_started(VisibilityRecord(
                DOMAIN, f"w{i}", f"r{i}", "t", i))
        assert store.count(DOMAIN, "") == 8
        view = store._device
        high_water = view._rows
        for i in range(4):
            store.delete_record(DOMAIN, f"w{i}", f"r{i}")
        for i in range(8, 12):
            store.record_started(VisibilityRecord(
                DOMAIN, f"w{i}", f"r{i}", "t", i))
        assert store.count(DOMAIN, "") == 8
        assert {r.workflow_id for r in store.query(DOMAIN, "")} == \
            {f"w{i}" for i in range(4, 12)}
        assert view._rows == high_water  # reused, not appended
        assert m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_VISIBILITY,
                                          m.M_VIS_DIVERGENCE) == 0
        view.stop()

    def test_capacity_growth_restages(self, vis_env, monkeypatch):
        monkeypatch.setenv("CADENCE_TPU_VISIBILITY_CAPACITY", "64")
        store = _seed_store(random.Random(3), 300,
                            (("Num", "num"),))
        assert store.count(DOMAIN, "") == 300
        view = store._device
        assert view.capacity >= 300
        assert store.count(DOMAIN, "CloseStatus = -1") == \
            len(_host_truth(store, "CloseStatus = -1"))
        assert m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_VISIBILITY,
                                          m.M_VIS_DIVERGENCE) == 0
        view.stop()

    def test_attr_named_like_builtin_never_aliases(self, vis_env):
        """A search attribute literally named "domain"/"start_time"
        must get its own prefixed device column — it can never alias
        the builtin column it shadows by name."""
        store = VisibilityStore()
        for i in range(30):
            store.record_started(VisibilityRecord(
                DOMAIN, f"w{i}", f"r{i}", "t", start_time=100 + i,
                search_attrs={"domain": i, "start_time": f"s{i % 3}"}))
        for q in ("domain > 15", "start_time = 's1'", "StartTime > 110",
                  "domain > 15 AND StartTime > 110"):
            got = {(r.workflow_id, r.run_id) for r in store.query(DOMAIN, q)}
            assert got == _host_truth(store, q), q
        assert m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_VISIBILITY,
                                          m.M_VIS_DIVERGENCE) == 0
        store._device.stop()

    def test_attr_budget_overflow_falls_back(self, vis_env, monkeypatch):
        monkeypatch.setenv("CADENCE_TPU_VISIBILITY_ATTR_COLUMNS", "2")
        store = VisibilityStore()
        for i in range(6):
            store.record_started(VisibilityRecord(
                DOMAIN, f"wf-{i}", f"r-{i}", "t", i,
                search_attrs={"A": i, "B": i * 2, "C": f"c{i}"}))
        # A and B claim the two columns; C overflows → host fallback
        assert store.count(DOMAIN, "A >= 3") == 3
        reg = m.DEFAULT_REGISTRY
        pre = reg.counter(m.SCOPE_TPU_VISIBILITY, m.M_VIS_FALLBACK_COLUMN)
        ids = {r.workflow_id for r in store.query(DOMAIN, "C = 'c2'")}
        assert ids == {"wf-2"}
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_FALLBACK_COLUMN) == pre + 1
        assert reg.counter(m.SCOPE_TPU_VISIBILITY, m.M_VIS_DIVERGENCE) == 0
        store._device.stop()

    def test_attr_budget_lfu_replacement_stops_permanent_fallback(
            self, vis_env, monkeypatch):
        """ISSUE 15 satellite: a repeatedly-queried over-budget attr
        out-demands the least-queried column and takes its slot — the
        fallback is transient, not permanent. The swap is counted under
        tpu.visibility/attr-column-replacements, the promoted column
        backfills the values already staged, and parity stays clean
        (the evicted column now falls back instead)."""
        monkeypatch.setenv("CADENCE_TPU_VISIBILITY_ATTR_COLUMNS", "2")
        store = VisibilityStore()
        for i in range(6):
            store.record_started(VisibilityRecord(
                DOMAIN, f"wf-{i}", f"r-{i}", "t", i,
                search_attrs={"A": i, "B": i * 2, "C": f"c{i}"}))
        reg = m.DEFAULT_REGISTRY
        # A earns use; B never queried; C (overflowed) accrues demand
        assert store.count(DOMAIN, "A >= 3") == 3
        assert {r.workflow_id for r in store.query(DOMAIN, "C = 'c2'")} \
            == {"wf-2"}  # fallback #1: demand C=1 > use B=0
        pre_swaps = reg.counter(m.SCOPE_TPU_VISIBILITY,
                                m.M_VIS_ATTR_REPLACEMENTS)
        pre_fb = reg.counter(m.SCOPE_TPU_VISIBILITY,
                             m.M_VIS_FALLBACK_COLUMN)
        # the next query triggers the swap (B evicted, C admitted with
        # backfill) and serves from the DEVICE
        assert {r.workflow_id for r in store.query(DOMAIN, "C = 'c4'")} \
            == {"wf-4"}
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_ATTR_REPLACEMENTS) == pre_swaps + 1
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_FALLBACK_COLUMN) == pre_fb
        view = store._device
        assert set(view._attr_cols) == {"A", "C"}
        assert "B" in view._overflow_attrs
        # the evicted column's use became its comeback demand, and
        # LATE WRITES to the promoted column keep applying
        store.record_started(VisibilityRecord(
            DOMAIN, "wf-9", "r-9", "t", 9, search_attrs={"C": "c9"}))
        assert {r.workflow_id for r in store.query(DOMAIN, "C = 'c9'")} \
            == {"wf-9"}
        # B now falls back (transiently, until it out-demands someone)
        assert {r.workflow_id for r in store.query(DOMAIN, "B = 4")} \
            == {"wf-2"}
        assert reg.counter(m.SCOPE_TPU_VISIBILITY, m.M_VIS_DIVERGENCE) == 0
        assert view.stats()["attr_overflow_demand"].get("B", 0) >= 1
        view.stop()


class TestStaleness:
    def test_bound_zero_flushes_before_serving(self, vis_env):
        store = VisibilityStore()
        store.record_started(VisibilityRecord(DOMAIN, "w0", "r0", "t", 1))
        assert store.count(DOMAIN, "") == 1
        view = store._device
        # writes queue behind the (wide) appender window...
        for i in range(1, 9):
            store.record_started(VisibilityRecord(DOMAIN, f"w{i}",
                                                  f"r{i}", "t", i))
        # ...and the next query flushes them inline (bound 0)
        assert store.count(DOMAIN, "") == 9
        assert view.staleness_max >= 1
        assert m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_VISIBILITY,
                                          m.M_VIS_DIVERGENCE) == 0
        view.stop()

    def test_bounded_staleness_serves_stale_then_flushes(self, vis_env,
                                                         monkeypatch):
        monkeypatch.setenv("CADENCE_TPU_VISIBILITY_STALENESS", "100")
        store = VisibilityStore()
        store.record_started(VisibilityRecord(DOMAIN, "w0", "r0", "t", 1))
        assert store.count(DOMAIN, "") == 1  # attaches + drains
        view = store._device
        store.record_started(VisibilityRecord(DOMAIN, "w1", "r1", "t", 2))
        # inside the bound: the device view may lag (served without a
        # flush; parity is skipped because the views differ by design)
        stale = store.count(DOMAIN, "")
        assert stale in (1, 2)  # 2 only if the appender raced the query
        view.flush()
        assert store.count(DOMAIN, "") == 2
        assert m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_VISIBILITY,
                                          m.M_VIS_DIVERGENCE) == 0
        view.stop()


class TestPagination:
    def _walk(self, store, query: str, page_size: int):
        out, token, pages = [], None, 0
        while True:
            recs, token = store.query_page(DOMAIN, query, page_size,
                                           token)
            out.extend((r.workflow_id, r.run_id) for r in recs)
            pages += 1
            if token is None or pages > 100:
                return out, pages

    def test_page_walk_identical_to_host(self, vis_env, monkeypatch):
        rng = random.Random(9)
        store = _seed_store(rng, 120, (("Num", "num"),))
        dev_walk, _ = self._walk(store, "CloseStatus = -1", 7)
        store._device.stop()
        monkeypatch.setenv("CADENCE_TPU_VISIBILITY", "0")
        host_walk, _ = self._walk(store, "CloseStatus = -1", 7)
        assert dev_walk == host_walk
        assert m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_VISIBILITY,
                                          m.M_VIS_DIVERGENCE) == 0

    def test_start_time_ties_escalate_to_bitmap(self, vis_env,
                                                monkeypatch):
        # 200 records ALL sharing one start_time: the device argsort
        # cannot resolve the (workflow_id, run_id) tie order past the
        # top-k boundary — the page path must escalate, and the walk
        # must still be byte-identical to the host
        store = VisibilityStore()
        for i in range(200):
            store.record_started(VisibilityRecord(
                DOMAIN, f"wf-{i:03d}", f"r-{i:03d}", "t", 777))
        reg = m.DEFAULT_REGISTRY
        dev_walk, pages = self._walk(store, "", 10)
        assert pages >= 20
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_TOPK_ESCALATIONS) > 0
        store._device.stop()
        monkeypatch.setenv("CADENCE_TPU_VISIBILITY", "0")
        host_walk, _ = self._walk(store, "", 10)
        assert dev_walk == host_walk
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_DIVERGENCE) == 0

    def test_topk_fast_path_serves_distinct_times(self, vis_env):
        store = VisibilityStore()
        for i in range(300):
            store.record_started(VisibilityRecord(
                DOMAIN, f"wf-{i:03d}", f"r-{i:03d}", "t", 1000 + i))
        reg = m.DEFAULT_REGISTRY
        recs, token = store.query_page(DOMAIN, "", 10, None)
        assert [r.start_time for r in recs] == list(
            range(1299, 1289, -1))
        assert token is not None
        assert reg.counter(m.SCOPE_TPU_VISIBILITY, m.M_VIS_TOPK) >= 1
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_DIVERGENCE) == 0
        store._device.stop()


class TestRoutingAndOps:
    def test_kill_switch_routes_host(self, vis_env, monkeypatch):
        store = _seed_store(random.Random(2), 30, ())
        assert store.count(DOMAIN, "") == 30
        view = store._device
        reg = m.DEFAULT_REGISTRY
        served = reg.counter(m.SCOPE_TPU_VISIBILITY, m.M_VIS_DEVICE_SERVED)
        monkeypatch.setenv("CADENCE_TPU_VISIBILITY", "0")
        assert store.count(DOMAIN, "") == 30
        assert reg.counter(m.SCOPE_TPU_VISIBILITY,
                           m.M_VIS_DEVICE_SERVED) == served
        view.stop()

    def test_warm_queries_recompile_nothing(self, vis_env):
        """Warm repeats of a seen query shape compile no new kernel
        variant, and every answer still passes the host parity check."""
        store = _seed_store(random.Random(77), 400, (("P", "num"),))
        queries = ["", "CloseStatus = -1", "WorkflowType = 'type-2'",
                   "P >= 5 AND CloseStatus = 0",
                   "StartTime > 12000 OR P < 2"]
        reg = m.DEFAULT_REGISTRY

        def counters():
            return [reg.counter(m.SCOPE_TPU_VISIBILITY, name)
                    for name in (m.M_LADDER_CACHE_MISSES, m.M_VIS_DIVERGENCE,
                                 m.M_VIS_PARITY_CHECKS)]

        for q in queries:  # cold pass compiles each shape once
            store.count(DOMAIN, q)
            store.query(DOMAIN, q)
        misses0, diverged0, checks0 = counters()
        for _ in range(3):
            for q in queries:
                store.count(DOMAIN, q)
                store.query(DOMAIN, q)
        misses, diverged, checks = counters()
        assert misses == misses0, \
            "warm visibility queries recompiled kernel variants"
        assert diverged == diverged0
        assert checks >= checks0 + 4 * len(queries)
        store._device.stop()

    def test_onebox_frontend_and_admin_rollup(self, vis_env):
        from cadence_tpu.engine.admin import AdminHandler
        from cadence_tpu.engine.onebox import Onebox

        box = Onebox(num_hosts=1, num_shards=2)
        box.frontend.register_domain("vis-box")
        box.frontend.start_workflow_execution("vis-box", "wf-1", "order",
                                              "tl")
        box.pump_once()
        recs = box.frontend.list_workflow_executions(
            "vis-box", "WorkflowType = 'order'")
        assert [r.workflow_id for r in recs] == ["wf-1"]
        assert box.frontend.count_workflow_executions(
            "vis-box", "CloseStatus = -1") == 1
        rollup = AdminHandler(box).visibility()
        assert rollup["enabled"] and rollup["attached"]
        assert rollup["parity_divergence"] == 0
        assert rollup["device_served"] >= 1
        assert rollup["rows"] >= 1
        # the series ride the box registry, prometheus-exposable
        body = box.metrics.to_prometheus()
        assert "tpu.visibility" in str(box.metrics.snapshot()) or body
        view = box.stores.visibility._device
        assert view is not None
        view.stop()

    def test_query_heavy_loadgen_ops(self, vis_env):
        """QUERY_HEAVY_MIX drives list/scan/count through the open-loop
        generator against a live box with the device tier on: per-op
        loadgen scopes populated, zero divergence, zero errors."""
        from cadence_tpu.engine.onebox import Onebox
        from cadence_tpu.loadgen.generator import LoadGenerator
        from cadence_tpu.loadgen.mixes import (
            QUERY_HEAVY_MIX,
            VIS_OPS,
            DomainPlan,
            build_schedule,
            trace_digest,
        )

        plans = [DomainPlan("lg-q", 24.0, mix=QUERY_HEAVY_MIX,
                            pool_size=3)]
        schedule = build_schedule(plans, 1.5, seed=42)
        assert trace_digest(schedule) == trace_digest(
            build_schedule(plans, 1.5, seed=42))
        vis_ops = [op for op in schedule if op.kind in VIS_OPS]
        assert vis_ops and all(op.arg for op in vis_ops)
        box = Onebox(num_hosts=1, num_shards=2)
        gen = LoadGenerator([box.frontend], schedule, plans, workers=4,
                            pump=box.pump_once)
        gen.prepare(setup_deadline_s=60.0)
        load = gen.run()
        t = load.totals()
        assert t.errors == 0, load.as_dict()
        sent_vis = sum(load.stats[(k, "lg-q")].sent
                       for k in ("list", "scan", "count")
                       if (k, "lg-q") in load.stats)
        assert sent_vis > 0
        reg = box.metrics
        assert reg.counter(m.SCOPE_TPU_VISIBILITY, m.M_VIS_DIVERGENCE) == 0
        assert reg.counter(m.SCOPE_TPU_VISIBILITY, m.M_VIS_QUERIES) > 0
        view = box.stores.visibility._device
        if view is not None:
            view.stop()
