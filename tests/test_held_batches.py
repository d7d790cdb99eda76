"""History batches recovered from a write-ahead log are held as the log's
bytes (`HistoryStore.append_serialized`, a `core.codec.HeldEvents` a batch)
until something reads their events, and are decoded then, once.

- a log recovered with held batches against the same log decoded eagerly
  (every `h` record's blob turned into events as it is read, the way the
  log replay did before batches were held): for every run the payload CRC,
  `history_size`, current pointer, visibility record and every batch's CRC
  are equal; the five suites, both write-ahead log backends, with and
  without `snap` records;
- the same over a log with a torn final record, a tail overwrite mid-batch,
  a fork, a `delw` record and a branch switch;
- the codec is canonical, `serialize_history(deserialize_history(b)) == b`
  over every suite's batches, so a CRC or a size read from held bytes is
  the one the decoded events give;
- `tpu.recover/batches-held-serialized` and `batches-decoded`: under a
  third decoded on a warm log; on a cold one every batch decoded, and none
  twice, through rebuild and verify;
- a planted blob whose ids are not consecutive is refused as a corrupt log.
"""
from __future__ import annotations

import base64
import json
import pickle
from collections import Counter

import pytest

from cadence_tpu.core.checksum import crc32_of_row, payload_row
from cadence_tpu.core.codec import (
    CorruptLogError,
    HeldEvents,
    deserialize_history,
    hold,
    serialize_history,
)
from cadence_tpu.core.events import HistoryBatch, HistoryEvent
from cadence_tpu.engine.cache import batch_crc
from cadence_tpu.engine.durability import (
    current_run_record,
    open_durable_stores,
    recover_stores,
)
from cadence_tpu.engine.persistence import (
    CurrentExecution,
    DomainInfo,
    HistoryStore,
)
from cadence_tpu.engine.tpu_engine import TPUReplayEngine
from cadence_tpu.gen.corpus import SUITES, generate_history
from cadence_tpu.utils import metrics as m

SEED = 2**31 + 41
DOMAIN_ID = "held-domain-id"
BACKENDS = ("wal.jsonl", "wal.db")


def _key(history):
    return DOMAIN_ID, history[0].workflow_id, history[0].run_id


def _histories(per_suite: int, target_events: int):
    return [generate_history(suite, SEED, i, target_events)
            for i in range(per_suite) for suite in SUITES]


def _append(stores, history, lo: int, hi: int) -> None:
    """Batches lo..hi of a run as a commit leaves them: the batch with its
    serialized blob, then the current-run record."""
    key = _key(history)
    for batch in history[lo:hi]:
        stores.history.append_batch(*key, batch.events,
                                    blob=serialize_history([batch]))
        stores.wal.append(current_run_record(
            key[0], key[1], CurrentExecution(key[2], 1, 0)))


def _open(path: str):
    stores = open_durable_stores(path)
    stores.domain.register(DomainInfo(domain_id=DOMAIN_ID, name="held"))
    return stores


def _cut(history, events_after: int) -> int:
    left, cut = 0, len(history)
    while cut > 1 and left < events_after:
        cut -= 1
        left += len(history[cut].events)
    return cut


def _write_warm(path: str, histories, lag: int):
    """A prefix of every run, the host brought up and swept (what `admin
    snapshot --sweep` runs), then the rest: every run at least `lag`
    events past its record."""
    cuts = [_cut(h, lag) for h in histories]
    stores = _open(path)
    for history, cut in zip(histories, cuts):
        _append(stores, history, 0, cut)
    stores.wal.close()
    stores, _report = recover_stores(path)
    engine = TPUReplayEngine(stores)
    assert engine.verify_all().ok
    sweep = engine.snapshot_sweep(force=True)
    del engine
    for history, cut in zip(histories, cuts):
        _append(stores, history, cut, len(history))
    stores.wal.close()
    return sweep


def _write_cold(path: str, histories) -> None:
    stores = _open(path)
    for history in histories:
        _append(stores, history, 0, len(history))
    stores.wal.close()


def _eager_append(self, domain_id, workflow_id, run_id, blob, branch=None):
    """The log replay's append as it was before batches were held: every
    blob decoded into events as it is read."""
    batches = deserialize_history(blob, domain_id, workflow_id, run_id)
    for batch in batches:
        self.append_batch(domain_id, workflow_id, run_id, batch.events,
                          branch=branch)
    return [batch.events for batch in batches]


def _recover(path: str, **kwargs):
    stores, report = recover_stores(path, **kwargs)
    stores.wal.close()
    return stores, report


def _recover_eager(path: str, monkeypatch, **kwargs):
    with monkeypatch.context() as patch:
        patch.setattr(HistoryStore, "append_serialized", _eager_append)
        return _recover(path, **kwargs)


def _look(stores) -> dict:
    """What a recovery leaves, run by run: the payload CRC and history
    size of its state, its pointer, its visibility records, every batch's
    CRC and every event of every branch, and the snapshot records."""
    hs = stores.history
    out = {}
    pointers = {key: (cur.run_id, cur.state, cur.close_status)
                for key, cur in stores.execution.list_current_pointers()}
    visible = {
        "open": [(r.workflow_id, r.run_id, r.workflow_type, r.start_time)
                 for r in stores.visibility.list_open(DOMAIN_ID)],
        "closed": [(r.workflow_id, r.run_id, r.workflow_type, r.start_time,
                    r.close_time, r.close_status)
                   for r in stores.visibility.list_closed(DOMAIN_ID)]}
    for key in sorted(hs.list_runs()):
        try:
            ms = stores.execution.get_workflow(*key)
            state = (crc32_of_row(payload_row(ms)), ms.history_size)
        except Exception as exc:  # a run the recovery left without state
            state = type(exc).__name__
        branches = []
        for b in range(hs.branch_count(*key)):
            batches = hs.as_history_batches(*key, branch=b)
            branches.append((
                [batch_crc(batch) for batch in batches],
                [[(e.id, int(e.event_type), e.version, e.timestamp,
                   e.task_id, sorted(e.attrs.items(), key=str))
                  for e in batch.events] for batch in batches]))
        out[key] = {
            "state": state,
            "current_branch": hs.get_current_branch(*key),
            "branches": branches,
            "pointer": pointers.get(key[:2]),
            **{kind: sorted(r for r in records if r[:2] == key[1:])
               for kind, records in visible.items()},
        }
    out["snapshots"] = sorted(
        (rec.key, rec.batch_count, rec.last_batch_crc)
        for rec in (stores.snapshot.get(k) for k in stores.snapshot.keys())
        if rec is not None)
    return out


def _codec_events(history):
    """A history's events as the codec carries them: the attributes that
    replay reads, and no others."""
    return [e for b in history
            for e in deserialize_history(serialize_history([b]))[0].events]


def _counter(name: str) -> int:
    return m.DEFAULT_REGISTRY.counter(m.SCOPE_TPU_RECOVER, name)


# -- the codec ---------------------------------------------------------------


@pytest.mark.parametrize("suite", SUITES)
def test_the_codec_round_trips_every_batch_to_the_same_bytes(suite):
    for i in range(4):
        for batch in generate_history(suite, SEED, i, 120):
            blob = serialize_history([batch])
            assert serialize_history(deserialize_history(blob)) == blob
            held = hold(blob)
            # a held batch writes its bytes back as they are, decoded or not
            assert serialize_history([HistoryBatch(
                batch.domain_id, batch.workflow_id, batch.run_id,
                held)]) == blob
            assert held.decoded() == deserialize_history(blob)[0].events
            assert serialize_history([HistoryBatch(
                batch.domain_id, batch.workflow_id, batch.run_id,
                list(held))]) == blob


# -- the store ----------------------------------------------------------------


def test_a_held_batch_is_read_from_its_bytes_and_decoded_once():
    history = generate_history("basic", SEED, 0, 40)
    key = _key(history)
    hs = HistoryStore()
    for batch in history:
        (held,) = hs.append_serialized(*key, serialize_history([batch]))
        assert type(held) is HeldEvents and len(held) == len(batch.events)
    stored = hs.read_batches(*key)
    assert all(type(b) is HeldEvents and not b.is_decoded for b in stored)
    # ids, length, CRC and size from the head and the bytes alone
    assert [(b.first_id, b.last_id) for b in stored] == [
        (b.events[0].id, b.events[-1].id) for b in history]
    batches = hs.as_history_batches(*key)
    assert [batch_crc(b) for b in batches] == [batch_crc(b) for b in history]
    assert hs.serialized_size(*key) == sum(
        len(serialize_history([b])) for b in history)
    assert hs.read_batches_range(*key, 1)[0] is stored[1]
    assert not any(b.is_decoded for b in stored)
    assert hs.held_tally.decoded == 0
    # the events, read twice: decoded once
    assert hs.read_events(*key) == _codec_events(history)
    assert hs.read_events(*key) == _codec_events(history)
    assert hs.held_tally.decoded == len(history)
    # a held batch crosses the wire as its bytes, still held
    copy = pickle.loads(pickle.dumps(hs.read_batches(*key)[-1]))
    assert type(copy) is HeldEvents and not copy.is_decoded
    assert copy == _codec_events(history[-1:]) and \
        copy.blob == stored[-1].blob


def test_a_held_batch_keeps_the_append_rules():
    """Contiguity, an overwrite at a batch boundary and mid-batch, a fork
    through a held batch and one across it, as `append_batch` has them."""
    history = generate_history("basic", SEED, 1, 60)
    key = _key(history)
    hs = HistoryStore()
    for batch in history:
        hs.append_serialized(*key, serialize_history([batch]))
    from cadence_tpu.engine.persistence import ConditionFailedError
    gap = history[-1].events[-1].id + 2
    with pytest.raises(ConditionFailedError):
        hs.append_serialized(*key, serialize_history([HistoryBatch(
            *key, [HistoryEvent(id=gap, event_type=history[-1].events[-1]
                                .event_type)])]))
    k = next(i for i, b in enumerate(history) if len(b.events) >= 2)
    before = [b.blob for b in hs.read_batches(*key)]
    # mid-batch: batch k keeps its first event, decoded; the rest held
    hs.append_serialized(*key, serialize_history([HistoryBatch(
        *key, history[k].events[1:])]))
    stored = hs.read_batches(*key)
    assert len(stored) == k + 2
    assert [b.blob for b in stored[:k]] == before[:k]
    assert type(stored[k]) is list and \
        stored[k] == _codec_events(history[k:k + 1])[:1]
    assert type(stored[k + 1]) is HeldEvents
    for batch in history[k + 1:]:
        hs.append_serialized(*key, serialize_history([batch]))
    assert hs.read_events(*key) == _codec_events(history)
    # a fork mid-batch splits the held batch; one at its end shares it
    last = history[-1].events
    mid = hs.fork_branch(*key, 0, last[0].id)
    whole = hs.fork_branch(*key, 0, history[-2].events[-1].id)
    assert hs.read_batches(*key, branch=whole)[-1] is \
        hs.read_batches(*key, branch=0)[-2]
    assert [e.id for e in hs.read_events(*key, branch=mid)] == \
        [e.id for b in history[:-1] for e in b.events] + [last[0].id]


def test_the_hydration_looks_an_id_up_in_its_batch_alone():
    from cadence_tpu.engine.rebuild import _EventsById
    history = generate_history("basic", SEED, 2, 60)
    key = _key(history)
    hs = HistoryStore()
    for batch in history:
        hs.append_serialized(*key, serialize_history([batch]))
    want = {e.id: e for e in _codec_events(history)}
    lookup = _EventsById(hs.as_history_batches(*key))
    middle = history[len(history) // 2].events[-1].id
    assert lookup.get(middle) == want[middle]
    assert lookup.get(0) is None and lookup.get(max(want) + 1) is None
    assert hs.held_tally.decoded == 1
    # batches whose ids skip are read whole, as a dict over them would be
    skipping = [HistoryBatch(*key, [e]) for e in _codec_events(history)[::2]]
    lookup = _EventsById(skipping)
    kept = {b.events[0].id: b.events[0] for b in skipping}
    assert all(lookup.get(i) == kept.get(i) for i in range(max(want) + 2))


def _write_edge_log(path: str, jsonl: bool):
    """Four runs of the basic suite through the store's own writers, each
    with one of what a log may hold besides appends: a tail overwritten
    mid-batch and written again, a fork mid-batch with a switch to it, a
    run deleted; and, on the JSONL backend, a torn final record."""
    histories = [generate_history("basic", SEED, i, 60) for i in range(4)]
    stores = _open(path)
    for history in histories:
        _append(stores, history, 0, len(history))
    overwritten, forked, deleted, _plain = histories
    key = _key(overwritten)
    k = next(i for i, b in enumerate(overwritten) if len(b.events) >= 2)
    stores.history.append_batch(*key, overwritten[k].events[1:])
    _append(stores, overwritten, k + 1, len(overwritten))
    key = _key(forked)
    at = next(b for b in forked if len(b.events) >= 2).events[0].id
    branch = stores.history.fork_branch(*key, 0, at)
    stores.history.set_current_branch(*key, branch)
    stores.history.delete_run(*_key(deleted))
    stores.wal.close()
    if jsonl:
        with open(path, encoding="utf-8") as fh:
            line = next(line for line in fh if line.startswith('{"t":"h"'))
        with open(path, "a", encoding="utf-8") as fh:
            fh.write(line[: len(line) // 2])
    return histories


@pytest.mark.parametrize("name", BACKENDS)
def test_an_edited_log_recovers_held_as_it_does_decoded(name, tmp_path,
                                                         monkeypatch):
    path = str(tmp_path / name)
    histories = _write_edge_log(path, name.endswith(".jsonl"))
    held, report = _recover(path)
    eager, eager_report = _recover_eager(path, monkeypatch)
    assert report.ok and eager_report.ok
    assert report.executions_rebuilt == eager_report.executions_rebuilt == 3
    assert _look(held) == _look(eager)
    forked = _key(histories[1])
    assert held.history.branch_count(*forked) == 2
    assert held.history.get_current_branch(*forked) == 1
    assert _key(histories[2]) not in held.history.list_runs()


# -- a recovery ---------------------------------------------------------------


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
@pytest.mark.parametrize("name", BACKENDS)
def test_a_log_recovers_held_as_it_does_decoded(name, warm, tmp_path,
                                                monkeypatch):
    path = str(tmp_path / name)
    histories = _histories(2, 40)
    if warm:
        sweep = _write_warm(path, histories, lag=8)
        assert sweep.written == len(histories)
    else:
        _write_cold(path, histories)
    held, report = _recover(path)
    eager, eager_report = _recover_eager(path, monkeypatch)
    assert report.ok and eager_report.ok
    assert report.snapshot_hydrated == eager_report.snapshot_hydrated == (
        len(histories) if warm else 0)
    assert report.device_rebuilt == report.device_verified == len(histories)
    looked = _look(held)
    assert looked == _look(eager)
    assert len(looked) == len(histories) + 1


def test_a_warm_recovery_decodes_under_a_third_of_its_batches(tmp_path):
    path = str(tmp_path / "wal.jsonl")
    histories = _histories(2, 120)
    _write_warm(path, histories, lag=8)
    m.DEFAULT_REGISTRY.reset()
    _stores, report = _recover(path)
    assert report.ok and report.snapshot_hydrated == len(histories)
    held = _counter(m.M_RECOVER_BATCHES_HELD)
    assert held == _counter(m.M_RECOVER_HISTORY_BATCHES) == sum(
        len(h) for h in histories)
    assert 0 < _counter(m.M_RECOVER_BATCHES_DECODED) < held / 3


def test_a_cold_recovery_decodes_every_batch_once(tmp_path, monkeypatch):
    path = str(tmp_path / "wal.jsonl")
    histories = _histories(2, 40)
    _write_cold(path, histories)
    decodes = Counter()
    decode = HeldEvents._decode

    def counted(self):
        decodes[id(self)] += 1
        return decode(self)

    monkeypatch.setattr(HeldEvents, "_decode", counted)
    m.DEFAULT_REGISTRY.reset()
    _stores, report = _recover(path)
    assert report.ok
    batches = sum(len(h) for h in histories)
    assert _counter(m.M_RECOVER_BATCHES_HELD) == batches
    assert _counter(m.M_RECOVER_BATCHES_DECODED) == batches
    assert sum(decodes.values()) == batches
    assert max(decodes.values()) == 1


# -- a corrupt blob -------------------------------------------------------------


def _planted(history, gap: int = 2) -> bytes:
    """The run's last batch with ids that skip: first, first + gap, ..."""
    events = history[-1].events
    first = events[0].id
    return serialize_history([HistoryBatch(*_key(history), [
        HistoryEvent(id=first + gap * j, event_type=e.event_type,
                     version=e.version, timestamp=e.timestamp,
                     task_id=e.task_id, attrs=dict(e.attrs))
        for j, e in enumerate(events)])])


def test_a_blob_whose_ids_are_not_consecutive_is_refused():
    history = next(h for h in _histories(1, 40) if len(h[-1].events) >= 2)
    hs = HistoryStore()
    key = _key(history)
    for batch in history[:-1]:
        hs.append_serialized(*key, serialize_history([batch]))
    hs.append_serialized(*key, _planted(history))
    with pytest.raises(CorruptLogError):
        hs.read_events(*key)


@pytest.mark.parametrize("name", BACKENDS)
def test_a_log_with_a_planted_blob_is_refused(name, tmp_path):
    path = str(tmp_path / name)
    histories = [h for h in _histories(1, 40) if len(h[-1].events) >= 2]
    stores = _open(path)
    for history in histories:
        _append(stores, history, 0, len(history) - 1)
    planted = histories[0]
    stores.history.append_batch(*_key(planted), planted[-1].events,
                                blob=_planted(planted))
    stores.wal.close()
    if name.endswith(".jsonl"):
        with open(path, encoding="utf-8") as fh:
            blobs = [json.loads(line).get("blob") for line in fh]
        assert base64.b64encode(_planted(planted)).decode() in blobs
    with pytest.raises(CorruptLogError):
        recover_stores(path)
