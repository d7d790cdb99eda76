"""A history host restarted over a log that holds its snapshots: the warm
restart of `recover_stores(path)` with nothing overridden, on the CPU
backend at a small size.

The log is what a host at the defaults leaves: brought up, swept by `admin
snapshot --sweep` (one verify pass seeds the pool, `snapshot_sweep(force=
True)` persists a `snap` record a resident run) and served on until it was
killed. Every record is written by the program's own writers. Run `j`'s cut
is the last batch boundary that leaves at least `s_j` events after it (`s_j`
in 0..31, seeded); one run in six starts after the sweep and has no record.

- every recovered state against the oracle's `StateBuilder` over the whole
  history and against the cold recovery of the same log
  (`CADENCE_TPU_SNAPSHOT=0`);
- both device passes hydrate every eligible run; exact, suffix and
  no-record runs each occur and are counted as what they are;
- one span a leg, the new legs nested as the docstrings say, the top-level
  legs covering `recover.call`;
- the counters under `tpu.recover/*` against the report;
- a record that is stale (its run's tail rewritten after the sweep, or its
  address doctored in the log) or torn is passed over, counted, and the run
  still equals the oracle;
- an append chunk wider than one `stack` program is stacked in blocks;
  one of rows hydrated from records is stacked on the host and put on the
  device once a leaf, and every suffix row of both passes goes that way;
- both passes re-pin their suffix rows as views of the append's final
  state and the hydration reads them from it: no `slice_row` launch.

The cell `recover.wal-snap-1chip` times this path on the chip.
"""
from __future__ import annotations

import base64
import json
import random
import shutil
import weakref
from collections import Counter

import numpy as np
import pytest

from cadence_tpu.core.checksum import crc32_of_row, payload_row
from cadence_tpu.core.codec import serialize_history
from cadence_tpu.engine import resident
from cadence_tpu.engine import snapshot as snapshot_mod
from cadence_tpu.engine.durability import (
    current_run_record,
    open_durable_stores,
    recover_stores,
)
from cadence_tpu.engine.persistence import CurrentExecution, DomainInfo
from cadence_tpu.engine.tpu_engine import TPUReplayEngine
from cadence_tpu.gen.corpus import SUITES, generate_history
from cadence_tpu.oracle.state_builder import StateBuilder
from cadence_tpu.utils import metrics as m
from cadence_tpu.utils import tracing

PER_SUITE, TARGET_EVENTS, SEED = 24, 40, 2**31 + 36
RUNS = PER_SUITE * len(SUITES)
DOMAIN_ID = "recover-warm-domain-id"
TOP_LEGS = ("log-replay", "rebuild", "verify", "reconcile")

#: span -> the span it lies directly under in a warm call, where one
#: thread runs both (`rebuild.encode` and `verify.pack` run on the
#: executor's pack threads). `rebuild.hydrate` occurs twice: the prepass's
#: rows, then the rows of the runs with no record.
PARENT = {
    "recover.log-replay": "recover.call",
    "recover.rebuild": "recover.call",
    "recover.verify": "recover.call",
    "recover.reconcile": "recover.call",
    "recover.upsert": "recover.rebuild",
    "rebuild.snapshot-consult": "recover.rebuild",
    "rebuild.resident-prepass": "recover.rebuild",
    "rebuild.suffix-replay": "rebuild.resident-prepass",
    "rebuild.replay": "recover.rebuild",
    "verify.partition": "recover.verify",
    "verify.snapshot-consult": "verify.partition",
    "verify.suffix-replay": "recover.verify",
    "verify.replay": "recover.verify",
    "verify.seed-resident": "verify.replay",
    "verify.compare": "recover.verify",
}
NEW_SPANS = ("rebuild.suffix-replay", "verify.snapshot-consult",
             "verify.suffix-replay")


def _key(history):
    return DOMAIN_ID, history[0].workflow_id, history[0].run_id


def _cut(history, events_after: int) -> int:
    """Batches the log holds when the sweep runs: the last boundary that
    leaves at least `events_after` events after it, one batch at least."""
    left, cut = 0, len(history)
    while cut > 1 and left < events_after:
        cut -= 1
        left += len(history[cut].events)
    return cut


def _append(stores, history, lo, hi):
    key = _key(history)
    for batch in history[lo:hi]:
        stores.history.append_batch(*key, batch.events,
                                    blob=serialize_history([batch]))
        stores.wal.append(current_run_record(
            key[0], key[1], CurrentExecution(key[2], 1, 0)))


def _write_warm_log(path: str):
    """The log, the histories, each run's cut (0: started after the
    sweep) and the sweep's report."""
    histories = [generate_history(suite, SEED, i, TARGET_EVENTS)
                 for i in range(PER_SUITE) for suite in SUITES]
    rng = random.Random(SEED)
    cuts = []
    for j, history in enumerate(histories):
        s = 0 if j < 2 else rng.randrange(32)
        cuts.append(0 if j % 6 == 5 else _cut(history, s))
    stores = open_durable_stores(path)
    stores.domain.register(DomainInfo(domain_id=DOMAIN_ID, name="warm"))
    for history, cut in zip(histories, cuts):
        _append(stores, history, 0, cut)
    stores.wal.close()
    # what `admin snapshot --sweep` runs over the host brought up
    stores, report = recover_stores(path)
    assert report.ok and report.snapshot_records == 0
    engine = TPUReplayEngine(stores)
    assert engine.verify_all().ok
    sweep = engine.snapshot_sweep(force=True)
    del engine
    for history, cut in zip(histories, cuts):
        _append(stores, history, cut, len(history))
    stores.wal.close()
    return histories, cuts, sweep


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("warm") / "wal.jsonl")
    return (path,) + _write_warm_log(path)


@pytest.fixture
def wal(written, tmp_path):
    """A copy of the module's log: a test may doctor it."""
    path = str(tmp_path / "wal.jsonl")
    shutil.copyfile(written[0], path)
    return (path,) + written[1:]


def _recover(path: str):
    stores, report = recover_stores(path)
    stores.wal.close()
    return stores, report


def _crcs(stores, histories):
    return [crc32_of_row(payload_row(
        stores.execution.get_workflow(*_key(h)))) for h in histories]


def _oracle_crcs(histories):
    return [crc32_of_row(payload_row(StateBuilder().replay_history(h)))
            for h in histories]


def _kinds(histories, cuts):
    exact = sum(1 for h, c in zip(histories, cuts) if c == len(h))
    none = sum(1 for c in cuts if c == 0)
    return exact, RUNS - exact - none, none


def _snapshot_counter(name):
    return m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_SNAPSHOT, name)


def test_the_sweep_writes_one_record_an_eligible_run(written):
    path, histories, cuts, sweep = written
    exact, suffix, none = _kinds(histories, cuts)
    assert exact >= 2 and suffix > exact and none == RUNS // 6
    assert sweep.considered == sweep.written == RUNS - none
    assert sweep.skipped_checksum == sweep.skipped_not_at_tip == 0
    with open(path, encoding="utf-8") as fh:
        records = [json.loads(line) for line in fh]
    snaps = [rec for rec in records if rec["t"] == "snap"]
    assert len(snaps) == RUNS - none
    # every record sits at its run's cut, by batches
    at = {(rec["d"], rec["w"], rec["r"]): rec["n"] for rec in snaps}
    assert at == {_key(h): c for h, c in zip(histories, cuts) if c}


def test_every_state_equals_the_oracle_and_the_cold_recovery(
        wal, monkeypatch):
    path, histories, cuts, _sweep = wal
    warm, report = _recover(path)
    assert report.ok and report.snapshot_hydrated > 0
    monkeypatch.setenv(snapshot_mod.ENABLE_ENV, "0")
    cold, cold_report = _recover(path)
    assert cold_report.ok and cold_report.snapshot_hydrated == 0
    assert cold_report.snapshot_records == report.snapshot_records
    want = _oracle_crcs(histories)
    assert _crcs(warm, histories) == want
    assert _crcs(cold, histories) == want
    assert sorted(warm.execution.list_executions()) == \
        sorted(_key(h) for h in histories)
    for history in histories:
        assert [e.id for e in warm.history.read_events(*_key(history))] \
            == [e.id for b in history for e in b.events]


def test_both_passes_hydrate_every_eligible_run(wal):
    path, histories, cuts, sweep = wal
    exact, suffix, none = _kinds(histories, cuts)
    _stores, report = _recover(path)
    eligible = sweep.written
    assert report.snapshot_records == eligible
    assert report.snapshot_hydrated == report.verify_hydrated == eligible
    # no silent fallback: every run on the device, in both passes
    assert report.executions_rebuilt == report.device_rebuilt == RUNS
    assert report.device_verified == RUNS
    assert report.rebuild_fallback == report.oracle_fallback == 0
    by_pass = {"rebuild": exact, "verify": exact}
    assert report.exact_rows == by_pass
    assert report.suffix_rows == {"rebuild": suffix, "verify": suffix}
    after = sum(len(b.events) for h, c in zip(histories, cuts) if c
                for b in h[c:])
    assert report.suffix_events == {"rebuild": after, "verify": after}
    assert _snapshot_counter(m.M_SNAP_HYDRATES) == 2 * eligible
    assert _snapshot_counter(m.M_SNAP_IGNORED_STALE) == 0
    assert _snapshot_counter(m.M_SNAP_IGNORED_TORN) == 0


def test_a_warm_call_lays_one_span_a_leg_and_the_legs_cover_it(wal):
    path, _histories, _cuts, _sweep = wal
    _stores, report = _recover(path)
    spans = [s for s in tracing.DEFAULT_TRACER.finished_spans()
             if s.operation.startswith(("recover.", "rebuild.", "verify."))]
    count = Counter(s.operation for s in spans)
    # the runs with no record take the cold path inside the same call:
    # one rebuild chunk and one verify chunk at this size
    assert count == Counter({
        **{name: 1 for name in PARENT}, "recover.call": 1,
        "rebuild.hydrate": 2, "rebuild.encode": 1, "verify.pack": 1})
    by_name = {s.operation: s for s in spans}
    call = by_name["recover.call"]
    for name, parent in PARENT.items():
        assert by_name[name].parent_id == by_name[parent].span_id, name
        assert by_name[name].trace_id == call.trace_id
    under = Counter(
        {s.span_id: s.operation for s in spans}[s.parent_id]
        for s in spans if s.operation == "rebuild.hydrate")
    assert under == {"rebuild.resident-prepass": 1, "recover.rebuild": 1}
    for name in NEW_SPANS:
        assert by_name[name].duration_s > 0
    assert report.seconds == {
        "call": call.duration_s,
        **{leg: by_name["recover." + leg].duration_s
           for leg in TOP_LEGS + ("upsert",)}}
    legs = sum(report.seconds[leg] for leg in TOP_LEGS)
    assert 0.98 * report.seconds["call"] <= legs <= report.seconds["call"]


def test_the_counters_equal_the_report(wal):
    path, histories, cuts, sweep = wal
    with open(path, encoding="utf-8") as fh:
        snaps = [rec for rec in map(json.loads, fh) if rec["t"] == "snap"]
    _stores, report = _recover(path)

    def counter(name):
        return m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_RECOVER, name)

    assert counter(m.M_RECOVER_SNAPSHOT_RECORDS) == \
        report.snapshot_records == len(snaps)
    assert counter(m.recover_records("snap")) == len(snaps)
    assert counter(m.M_RECOVER_SNAPSHOT_BYTES) == sum(
        len(base64.b64decode(rec["blob"])) + len(base64.b64decode(rec["pay"]))
        for rec in snaps)
    assert counter(m.M_RECOVER_RUNS_HYDRATED) == \
        report.snapshot_hydrated + report.verify_hydrated
    assert counter(m.M_RECOVER_EXACT_ROWS) == sum(report.exact_rows.values())
    assert counter(m.M_RECOVER_SUFFIX_ROWS) == \
        sum(report.suffix_rows.values())
    assert counter(m.M_RECOVER_SUFFIX_EVENTS) == \
        sum(report.suffix_events.values()) == m.DEFAULT_REGISTRY.counter(
            m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_EVENTS_APPENDED)
    # the full replay took the runs with no record, and only them
    cold = sum(len(b.events) for h, c in zip(histories, cuts) if not c
               for b in h)
    assert counter(m.M_RECOVER_REBUILD_EVENTS) == cold
    assert counter(m.M_RECOVER_VERIFY_EVENTS) == cold
    assert counter(m.M_RECOVER_HISTORY_EVENTS) == report.events == sum(
        len(b.events) for h in histories for b in h)


def test_a_warm_recovery_leaves_the_log_alone_and_a_second_gives_the_same(
        wal):
    path, histories, _cuts, _sweep = wal
    with open(path, "rb") as fh:
        before = fh.read()
    first, report = _recover(path)
    second, again = _recover(path)
    with open(path, "rb") as fh:
        assert fh.read() == before
    assert _crcs(first, histories) == _crcs(second, histories)
    report.seconds, again.seconds = {}, {}
    assert report == again


def _doctor(path: str, key, change) -> None:
    """Rewrite the one `snap` line of `key` in place through `change`."""
    with open(path, encoding="utf-8") as fh:
        lines = fh.readlines()
    done = 0
    for n, line in enumerate(lines):
        rec = json.loads(line)
        if rec["t"] == "snap" and (rec["d"], rec["w"], rec["r"]) == key:
            change(rec)
            lines[n] = json.dumps(rec, separators=(",", ":")) + "\n"
            done += 1
    assert done == 1
    with open(path, "w", encoding="utf-8") as fh:
        fh.writelines(lines)


def _stale_address(rec):
    rec["crc"] ^= 0xDEAD  # the bytes under the record's address changed


def _torn_blob(rec):
    blob = base64.b64decode(rec["blob"])
    rec["blob"] = base64.b64encode(
        blob[:-7] + bytes(b ^ 0x7F for b in blob[-7:])).decode("ascii")


@pytest.mark.parametrize("change, ignored", [
    (_stale_address, m.M_SNAP_IGNORED_STALE),
    (_torn_blob, m.M_SNAP_IGNORED_TORN)], ids=["stale", "torn"])
def test_a_doctored_record_is_passed_over_and_counted(wal, change, ignored):
    path, histories, cuts, sweep = wal
    victim = next(j for j, c in enumerate(cuts) if 0 < c < len(histories[j]))
    _doctor(path, _key(histories[victim]), change)
    stores, report = _recover(path)
    assert report.ok and report.snapshot_records == sweep.written
    assert report.snapshot_hydrated == report.verify_hydrated == \
        sweep.written - 1
    # both consults met it, and neither served it
    assert _snapshot_counter(ignored) == 2
    assert report.device_rebuilt == report.device_verified == RUNS
    assert report.rebuild_fallback == report.oracle_fallback == 0
    assert _crcs(stores, histories) == _oracle_crcs(histories)


def test_a_tail_rewritten_after_the_sweep_drops_the_record(wal):
    """The derived invalidation: an `h` record that rewrites a batch the
    record covers drops it while the log replays, so the run has no
    record by the time either pass consults the store."""
    path, histories, cuts, sweep = wal
    victim = next(j for j, c in enumerate(cuts) if c == len(histories[j]))
    history = histories[victim]
    stores = open_durable_stores(path)
    _append(stores, history, len(history) - 1, len(history))
    stores.wal.close()
    stores, report = _recover(path)
    assert report.ok and report.snapshot_records == sweep.written
    assert stores.snapshot.get(_key(history)) is None
    assert report.snapshot_hydrated == report.verify_hydrated == \
        sweep.written - 1
    assert _snapshot_counter(m.M_SNAP_IGNORED_STALE) == 0
    assert _crcs(stores, histories) == _oracle_crcs(histories)


def test_the_rebuilders_pool_is_gone_before_the_verify(wal, monkeypatch):
    path, _histories, _cuts, sweep = wal
    pools, alive = [], []
    admit = resident.ResidentStateCache.admit
    verify_all = TPUReplayEngine.verify_all

    def note_admit(self, *args, **kwargs):
        if not any(ref() is self for ref in pools):
            pools.append(weakref.ref(self))
        return admit(self, *args, **kwargs)

    def note_verify(self, *args, **kwargs):
        alive.extend(ref() is not None for ref in pools)
        return verify_all(self, *args, **kwargs)

    monkeypatch.setattr(resident.ResidentStateCache, "admit", note_admit)
    monkeypatch.setattr(TPUReplayEngine, "verify_all", note_verify)
    _stores, report = _recover(path)
    assert report.snapshot_hydrated == sweep.written
    # the rebuilder's pool admitted its rows, and was dropped with the
    # rebuilder before the verify engine built its own
    assert alive == [False] and len(pools) == 2


def _rows(k: int, on_device):
    """k distinct W=1 rows: host arrays, or put on the device where
    `on_device(j)` says so."""
    import jax

    from cadence_tpu.ops.state import init_state

    base = init_state(1, resident.DEFAULT_LAYOUT)
    rows = [jax.tree_util.tree_map(
        lambda a, j=j: (np.asarray(a) + j).astype(a.dtype), base)
        for j in range(k)]
    return [jax.device_put(r) if on_device(j) else r
            for j, r in enumerate(rows)], base


def _assert_stacked(stacked, rows, filler, width):
    import jax

    for leaf, *parts in zip(jax.tree_util.tree_leaves(stacked),
                            *map(jax.tree_util.tree_leaves,
                                 rows + [filler] * (width - len(rows)))):
        assert leaf.shape[0] == width
        want = np.concatenate([np.asarray(p) for p in parts], axis=0)
        assert np.asarray(leaf).dtype == want.dtype
        assert (np.asarray(leaf) == want).all()


@pytest.mark.parametrize("rows, width, kind", [
    (3, 8, "device"), (64, 64, "device"), (70, 128, "device"),
    (130, 256, "device"), (3, 8, "mixed"), (70, 128, "mixed")])
def test_a_launch_wider_than_one_stack_program_is_stacked_in_blocks(
        rows, width, kind, monkeypatch):
    """Device rows (the serving tier's, `_readmit`'s), and a launch that
    mixes host and device rows, take the jitted stack."""
    operands = []
    stack_states = resident._stack_states
    monkeypatch.setattr(
        resident, "_stack_states",
        lambda states: operands.append(len(states)) or stack_states(states))
    states, base = _rows(rows, (lambda j: True) if kind == "device"
                         else (lambda j: j % 2 == 1))
    registry = m.MetricsRegistry()
    stacked = resident._stack_padded(
        states, width, scope=registry.scope(m.SCOPE_TPU_RESIDENT))
    _assert_stacked(stacked, states, base, width)
    assert registry.counter(
        m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_HOST_STACKED_ROWS) == 0
    # one program up to STACK_BLOCK rows, as a serving flush has it; past
    # that, blocks of STACK_BLOCK and one join of the blocks
    blocks = width // resident.STACK_BLOCK
    assert operands == ([width] if width <= resident.STACK_BLOCK
                        else [resident.STACK_BLOCK] * blocks + [blocks])


@pytest.mark.parametrize("rows, width", [(3, 8), (64, 64), (70, 128),
                                         (130, 256), (1556, 2048)])
def test_a_launch_of_host_rows_is_stacked_on_the_host(rows, width,
                                                      monkeypatch):
    """Rows hydrated from records hold host leaves: the launch state is
    built on the host, the filler a host initial-state row, and put on the
    device once a leaf; no stack program runs."""
    import jax

    monkeypatch.setattr(resident, "_stack_states", lambda states: pytest.fail(
        "a launch of host rows ran the jitted stack"))
    states, base = _rows(rows, lambda j: False)
    filler = jax.device_get(base)
    for device in (None, jax.devices()[-1]):
        registry = m.MetricsRegistry()
        stacked = resident._stack_padded(
            states, width, device, registry.scope(m.SCOPE_TPU_RESIDENT))
        _assert_stacked(stacked, states, filler, width)
        for leaf in jax.tree_util.tree_leaves(stacked):
            assert isinstance(leaf, jax.Array)
            if device is not None:
                assert leaf.devices() == {device}
        assert registry.counter(
            m.SCOPE_TPU_RESIDENT, m.M_RESIDENT_HOST_STACKED_ROWS) == rows


def _resident_counter(name):
    return m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_RESIDENT, name)


def _record_slices(monkeypatch):
    """The `slice_row` launches the pool makes from here on."""
    sliced = []
    slice_row = resident._slice_row
    monkeypatch.setattr(
        resident, "_slice_row",
        lambda state, index: sliced.append(index) or slice_row(state, index))
    return sliced


def test_a_warm_recovery_slices_no_row(wal, monkeypatch):
    """Both passes re-pin their suffix rows as views of the append's final
    state, and the rebuild's hydration reads them from it: no `slice_row`
    launch in the whole recovery, and no view materialised."""
    path, histories, cuts, _sweep = wal
    _exact, _suffix, none = _kinds(histories, cuts)
    sliced = _record_slices(monkeypatch)
    stores, report = _recover(path)
    assert report.ok
    assert _crcs(stores, histories) == _oracle_crcs(histories)
    assert sliced == []
    assert _resident_counter(m.M_RESIDENT_ROW_SLICES) == 0
    assert _resident_counter(m.M_RESIDENT_VIEWS_MATERIALISED) == 0
    # every suffix row of both passes is a view; beside them the verify's
    # cold chunk seeds the runs with no record as views of its own state
    # (`verify.seed-resident`, which does not fire where every run has a
    # record)
    suffix = m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_RECOVER,
                                        m.M_RECOVER_SUFFIX_ROWS)
    assert suffix == sum(report.suffix_rows.values()) > 0
    assert _resident_counter(m.M_RESIDENT_VIEW_ROWS) == suffix + none


def test_the_hydration_reads_views_from_their_chunk_and_leaves_them_views(
        monkeypatch):
    """A rebuild's prepass over rows of each kind the pool holds: appended
    rows (re-pinned as views), W=1 device rows and rows on the host, all
    hydrated to the oracle's state with no `slice_row` launch; the views
    stay views."""
    import jax
    import jax.numpy as jnp

    from cadence_tpu.engine.cache import content_address
    from cadence_tpu.engine.rebuild import DeviceRebuilder
    from cadence_tpu.ops.encode import (
        assemble_corpus,
        encode_batches_resumable,
    )
    from cadence_tpu.ops.payload import payload_rows
    from cadence_tpu.ops.replay import replay_events

    def _run(history):
        return (history[0].domain_id, history[0].workflow_id,
                history[0].run_id)

    histories = [generate_history(suite, SEED, 0, TARGET_EVENTS)
                 for suite in SUITES] + [
        generate_history("timer_retry", SEED, 1, TARGET_EVENTS)]
    appended, on_host = range(3), (5,)
    # what the pool holds: a prefix of the first three, the others whole
    pinned = [h[:-1] if j in appended else h
              for j, h in enumerate(histories)]
    rebuilder = DeviceRebuilder()
    pool = rebuilder.resident = resident.ResidentStateCache(
        ladder=rebuilder.ladder)
    lanes = [encode_batches_resumable(h)[0] for h in pinned]
    state = replay_events(jnp.asarray(
        assemble_corpus(lanes, max(r.shape[0] for r in lanes))))
    rows = np.asarray(payload_rows(state))
    branch = np.asarray(state.current_branch)
    for j, h in enumerate(pinned):
        row = pool.extract_row(state, j)
        assert pool.admit(_run(h), content_address(h),
                          jax.device_get(row) if j in on_host else row,
                          rows[j], int(branch[j]))
    sliced = _record_slices(monkeypatch)
    got = rebuilder.rebuild([(h, None) for h in histories])
    assert sliced == []
    assert rebuilder.stats.resident == len(histories)
    assert rebuilder.stats.suffix_rows == len(appended)
    for ms, h in zip(got, histories):
        assert crc32_of_row(payload_row(ms)) == \
            crc32_of_row(payload_row(StateBuilder().replay_history(h)))
    entries = [pool.entry_for(_run(h)) for h in histories]
    assert [e.is_view for e in entries] == [
        j in appended for j in range(len(histories))]
    assert _resident_counter(m.M_RESIDENT_VIEWS_MATERIALISED) == 0


@pytest.mark.parametrize("snapshots", ["1", "0"])
def test_the_suffix_rows_of_both_passes_are_stacked_on_the_host(
        wal, snapshots, monkeypatch):
    """A warm recovery stacks every suffix row of both passes on the host
    (the rows hydrated from records); a cold one stacks none."""
    path, histories, _cuts, _sweep = wal
    monkeypatch.setenv(snapshot_mod.ENABLE_ENV, snapshots)
    stores, report = _recover(path)
    assert report.ok
    assert _crcs(stores, histories) == _oracle_crcs(histories)
    stacked = m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_RESIDENT,
                                         m.M_RESIDENT_HOST_STACKED_ROWS)
    suffix = sum(report.suffix_rows.values())
    assert stacked == suffix
    assert (suffix > 0) == (snapshots == "1")
