"""A history host restarted over its write-ahead log: `recover_stores(path)`
with nothing overridden (device rebuild, then device verify), on the CPU
backend at a small size.

- every recovered state against the benchmark's plain reference
  (`benchmarks/refimpl/replay.py`, which shares no code with the program);
- more jobs than a rebuild chunk holds give the states one chunk gives;
- the spans of a call: one a leg, nested as `recover_stores` says, the
  top-level legs covering `recover.call`;
- the counters under `tpu.recover/*` and the report's `events` and
  `seconds` against what the log holds;
- a recovery leaves the log's bytes alone, and a second cold one gives the
  same states;
- the verify pins every run as a view of its chunk's state and slices none.

The cell `recover.wal-1chip` times this path on the chip.
"""
from __future__ import annotations

import base64
import importlib.util
import json
import os
from collections import Counter

import pytest

from cadence_tpu.core.checksum import crc32_of_row, payload_row
from cadence_tpu.core.codec import serialize_history
from cadence_tpu.engine.durability import (
    current_run_record,
    open_durable_stores,
    recover_stores,
)
from cadence_tpu.engine.persistence import CurrentExecution, DomainInfo
from cadence_tpu.gen.corpus import SUITES, generate_history
from cadence_tpu.ops.encode import NUM_LANES
from cadence_tpu.utils import metrics as m
from cadence_tpu.utils import tracing

PER_SUITE, TARGET_EVENTS, SEED = 8, 24, 2**31 + 34
RUNS = PER_SUITE * len(SUITES)
DOMAIN_ID = "recover-domain-id"

#: span -> the span it lies directly under, where one thread runs both;
#: `rebuild.encode` and `verify.pack` run on the executor's pack threads
PARENT = {
    "recover.log-replay": "recover.call",
    "recover.rebuild": "recover.call",
    "recover.verify": "recover.call",
    "recover.reconcile": "recover.call",
    "recover.upsert": "recover.rebuild",
    "rebuild.snapshot-consult": "recover.rebuild",
    "rebuild.resident-prepass": "recover.rebuild",
    "rebuild.replay": "recover.rebuild",
    "rebuild.hydrate": "recover.rebuild",
    "verify.partition": "recover.verify",
    "verify.snapshot-consult": "verify.partition",
    "verify.replay": "recover.verify",
    "verify.seed-resident": "verify.replay",
    "verify.compare": "recover.verify",
}
PER_CHUNK = ("rebuild.encode", "verify.pack", "verify.seed-resident")
TOP_LEGS = ("log-replay", "rebuild", "verify", "reconcile")


def _reference():
    """`benchmarks/refimpl/replay.py` by path: it imports nothing."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "refimpl", "replay.py")
    spec = importlib.util.spec_from_file_location("refimpl_replay", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _key(history):
    return DOMAIN_ID, history[0].workflow_id, history[0].run_id


def _write_log(path: str):
    """One log of the five suites through the program's own writer, as a
    live host leaves it: the domain, every transaction with its serialized
    blob, and the current-run record its commit logs."""
    histories = [generate_history(suite, SEED, i, TARGET_EVENTS)
                 for i in range(PER_SUITE) for suite in SUITES]
    stores = open_durable_stores(path)
    stores.domain.register(DomainInfo(domain_id=DOMAIN_ID, name="recover"))
    for history in histories:
        key = _key(history)
        for batch in history:
            stores.history.append_batch(*key, batch.events,
                                        blob=serialize_history([batch]))
            stores.wal.append(current_run_record(
                key[0], key[1], CurrentExecution(key[2], 1, 0)))
    stores.wal.close()
    return histories


def _recover(path: str):
    stores, report = recover_stores(path)
    stores.wal.close()
    return stores, report


def _rows(stores, histories):
    return [payload_row(stores.execution.get_workflow(*_key(h)))
            for h in histories]


@pytest.fixture
def wal(tmp_path):
    path = str(tmp_path / "wal.jsonl")
    return path, _write_log(path)


def test_every_recovered_state_has_the_plain_references_crc(wal):
    path, histories = wal
    ref = _reference()
    stores, report = _recover(path)
    assert sorted(stores.execution.list_executions()) == \
        sorted(_key(h) for h in histories)
    for history, row in zip(histories, _rows(stores, histories)):
        assert crc32_of_row(row) & 0xFFFFFFFF == \
            ref.crc_of_history(ref.plain(history)), history[0].workflow_id
        assert [e.id for e in stores.history.read_events(*_key(history))] \
            == [e.id for b in history for e in b.events]
    # no silent fallback: both device passes held every run
    assert report.executions_rebuilt == report.device_rebuilt == RUNS
    assert report.device_verified == RUNS and report.ok
    assert report.rebuild_fallback == report.oracle_fallback == 0
    assert report.quarantined == [] and report.snapshot_hydrated == 0
    assert report.open_workflows == sum(
        row[1] != 2 for row in _rows(stores, histories))  # row[1]: state


def test_more_jobs_than_a_chunk_give_the_states_of_one_chunk(
        wal, monkeypatch):
    path, histories = wal
    whole, _report = _recover(path)
    assert m.DEFAULT_REGISTRY.counter(
        m.SCOPE_TPU_RECOVER, m.M_RECOVER_REBUILD_CHUNKS) == 1
    m.DEFAULT_REGISTRY.reset()
    tracing.DEFAULT_TRACER.reset()
    # 40 jobs in chunks of 16: two whole chunks and a partial last one
    monkeypatch.setenv("CADENCE_TPU_REBUILD_CHUNK", "16")
    chunked, report = _recover(path)
    assert m.DEFAULT_REGISTRY.counter(
        m.SCOPE_TPU_RECOVER, m.M_RECOVER_REBUILD_CHUNKS) == 3
    assert Counter(s.operation for s in
                   tracing.DEFAULT_TRACER.finished_spans())[
        "rebuild.encode"] == 3
    assert report.device_rebuilt == RUNS and report.rebuild_fallback == 0
    for one, many in zip(_rows(whole, histories), _rows(chunked, histories)):
        assert (one == many).all()


def test_a_call_lays_one_span_a_leg_and_the_legs_cover_it(wal):
    path, _histories = wal
    _stores, report = _recover(path)
    spans = [s for s in tracing.DEFAULT_TRACER.finished_spans()
             if s.operation.startswith(("recover.", "rebuild.", "verify."))]
    # one chunk a device pass at this size, no flagged row: no ladder leg
    names = set(PARENT) | {"recover.call", *PER_CHUNK}
    assert Counter(s.operation for s in spans) == Counter(
        {name: 1 for name in names})
    by_name = {s.operation: s for s in spans}
    call = by_name["recover.call"]
    for name, parent in PARENT.items():
        assert by_name[name].parent_id == by_name[parent].span_id, name
        assert by_name[name].trace_id == call.trace_id
    for name in ("rebuild.encode", "verify.pack"):
        s = by_name[name]  # on a pack thread: inside the call in time only
        assert s.trace_id != call.trace_id
        assert call.start_ns <= s.start_ns <= call.start_ns + call.duration_ns
    # the report's seconds are the spans' own, and the top-level legs add
    # up to the call but for what lies between them
    assert report.seconds == {
        "call": call.duration_s,
        **{leg: by_name["recover." + leg].duration_s
           for leg in TOP_LEGS + ("upsert",)}}
    legs = sum(report.seconds[leg] for leg in TOP_LEGS)
    assert 0.98 * report.seconds["call"] <= legs <= report.seconds["call"]
    assert report.seconds["upsert"] < report.seconds["rebuild"]


def test_the_verify_seeds_its_pool_with_views_and_slices_no_row(
        wal, monkeypatch):
    """`verify_all` hands the pool each chunk once: every run is pinned
    as a view of the chunk's state, and a recovery, which reads none of
    them, launches no `slice_row` program at all."""
    from cadence_tpu.engine import resident

    path, histories = wal
    sliced = []
    slice_row = resident._slice_row
    monkeypatch.setattr(
        resident, "_slice_row",
        lambda state, index: sliced.append(index) or slice_row(state, index))
    stores, report = _recover(path)
    assert report.device_verified == RUNS and report.ok

    def counter(name):
        return m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_RESIDENT, name)

    assert counter(m.M_CACHE_MISSES) == RUNS
    assert counter(m.M_RESIDENT_VIEW_ROWS) == RUNS
    assert counter(m.M_RESIDENT_VIEWS_MATERIALISED) == 0
    assert sliced == []
    assert m.DEFAULT_REGISTRY.gauge_value(
        m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_ENTRIES) == RUNS
    # the states are what the oracle alone recovers from the same log
    oracle, _report = recover_stores(path, verify_on_device=False,
                                     rebuild_on_device=False)
    oracle.wal.close()
    for one, two in zip(_rows(stores, histories), _rows(oracle, histories)):
        assert (one == two).all()


def test_counters_and_the_report_agree_with_the_log(wal):
    path, histories = wal
    with open(path, encoding="utf-8") as fh:
        records = [json.loads(line) for line in fh]
    by_type = Counter(rec["t"] for rec in records)
    batches = sum(len(h) for h in histories)
    events = sum(len(b.events) for h in histories for b in h)
    assert by_type == {"ver": 1, "d": 1, "h": batches, "cur": batches}
    _stores, report = _recover(path)
    assert report.events == events

    def counter(name):
        return m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_RECOVER, name)

    # the schema header is the log's own, not a record recovery replays
    assert counter(m.M_RECOVER_LOG_RECORDS) == len(records) - 1
    for record_type in ("d", "h", "cur"):
        assert counter(m.recover_records(record_type)) == by_type[record_type]
    assert counter(m.M_RECOVER_LOG_BYTES) == os.path.getsize(path)
    assert counter(m.M_RECOVER_HISTORY_BATCHES) == batches
    assert counter(m.M_RECOVER_HISTORY_EVENTS) == events
    assert counter(m.M_RECOVER_HISTORY_BYTES) == sum(
        len(base64.b64decode(rec["blob"])) for rec in records
        if rec["t"] == "h") == sum(
        len(serialize_history([b])) for h in histories for b in h)
    assert counter(m.M_RECOVER_EXECUTIONS) == RUNS
    assert counter(m.M_RECOVER_REBUILD_EVENTS) == events
    assert counter(m.M_RECOVER_REBUILD_CHUNKS) == 1
    assert counter(m.M_RECOVER_ROWS_VERIFIED) == RUNS
    assert counter(m.M_RECOVER_VERIFY_EVENTS) == events
    # a log with no `snap` record: nothing of the warm restart counts
    for name in (m.M_RECOVER_SNAPSHOT_RECORDS, m.M_RECOVER_SNAPSHOT_BYTES,
                 m.M_RECOVER_RUNS_HYDRATED, m.M_RECOVER_EXACT_ROWS,
                 m.M_RECOVER_SUFFIX_ROWS, m.M_RECOVER_SUFFIX_EVENTS):
        assert counter(name) == 0, name
    assert report.snapshot_records == report.verify_hydrated == 0
    assert report.exact_rows == report.suffix_rows == \
        report.suffix_events == {"rebuild": 0, "verify": 0}
    # dense int64 lanes: the rebuild's chunk at its longest history, the
    # verify's at the power of two above it (both longer than 16 events)
    longest = max(sum(len(b.events) for b in h) for h in histories)
    bucket = 1 << (longest - 1).bit_length()
    assert counter(m.M_RECOVER_DENSE_BYTES) == \
        8 * NUM_LANES * RUNS * (longest + bucket)
    assert counter(m.M_RECOVER_DENSE_BYTES) == sum(
        m.DEFAULT_REGISTRY.counter(scope, m.M_H2D_BYTES)
        for scope in (m.SCOPE_REBUILD, m.SCOPE_TPU_REPLAY))


def test_a_recovery_leaves_the_log_alone_and_a_second_gives_the_same(wal):
    path, histories = wal
    with open(path, "rb") as fh:
        before = fh.read()
    first, report = _recover(path)
    with open(path, "rb") as fh:
        assert fh.read() == before
    second, again = _recover(path)  # cold: nothing of the first is handed on
    with open(path, "rb") as fh:
        assert fh.read() == before
    for one, two in zip(_rows(first, histories), _rows(second, histories)):
        assert (one == two).all()
    report.seconds, again.seconds = {}, {}
    assert report == again


def test_the_oracle_path_opens_no_device_leg(wal):
    """What `rpc/storeserver.py` and `cli.py` run: both device passes off.
    The call and its host legs are spans all the same."""
    path, histories = wal
    stores, report = recover_stores(path, verify_on_device=False,
                                    rebuild_on_device=False)
    stores.wal.close()
    assert report.device_rebuilt == 0 and report.rebuild_fallback == RUNS
    assert report.device_verified == 0 and "verify" not in report.seconds
    names = {s.operation for s in tracing.DEFAULT_TRACER.finished_spans()}
    assert names == {"recover.call", "recover.log-replay", "recover.rebuild",
                     "recover.upsert", "recover.reconcile"}
    on_device, _report = _recover(path)
    for one, two in zip(_rows(stores, histories),
                        _rows(on_device, histories)):
        assert (one == two).all()
