"""Tests of the cell `recover.wal-snap-1chip` (PR 36): a rehearsed run's last
line, the control, the three numbers of the warm restart each tripped by a
fault planted underneath the harness, the log's cut, the byte count by hand
and the four new readers on a planted span list.

    python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import json
import os
import zlib

import pytest
from test_benchmark import _run
from test_recover_cell import COMPARED as COLD_COMPARED
from test_recover_cell import _reader, _s

CELL = "recover.wal-snap-1chip"
RUNS = 5 * 24  # the rehearsal's corpus
COMPARED = COLD_COMPARED + ("snapshots_not_hydrated", "snapshots_ignored",
                            "snap_records_missing")
SPAN_READERS = {"recover.log_replay_share_pct", "recover.hydrate_share_pct",
                "recover.verify_share_pct",
                "recover.snapshot_consult_share_pct",
                "recover.suffix_replay_share_pct"}


def _json_lines(stderr: str, needle: str) -> list:
    return [json.loads(line) for line in stderr.splitlines()
            if line.startswith("{") and needle in line]


def test_rehearsed_warm_cell_prints_a_well_formed_last_line():
    proc = _run("--workload", CELL, "--seed", str(2**31 + 36), "--seconds",
                "1", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] % RUNS == 0 and last["attempted"] >= RUNS
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    compared = last["compared"]
    assert tuple(compared) == COMPARED
    assert all(c["value"] == 0 and c["limit"] == 0 for c in compared.values())
    # the span and counter metrics read on any backend; what needs a chip's
    # trace is left out of a rehearsal's line
    metrics = {name[len("rehearsal."):]: m["value"]
               for name, m in last["metrics"].items()}
    assert set(metrics) == SPAN_READERS | {"recover.replayed_events_pct"}
    assert all(0 < metrics[name] < 100 for name in SPAN_READERS)
    # set-up's record: one record a run, a handful of them at the tip
    (log,) = _json_lines(proc.stderr, '"snap_records"')[:1]
    assert log["snap_records"] == log["eligible_runs"] == RUNS
    assert 0 < log["exact_runs"] < RUNS // 4
    assert log["snap_bytes"] > RUNS * 3602 * 4 // 3  # the blob, base64
    (wrote,) = _json_lines(proc.stderr, '"sweep_s"')
    assert wrote["sweep"] == {"considered": RUNS, "written": RUNS,
                              "skipped_policy": 0, "skipped_checksum": 0,
                              "skipped_not_at_tip": 0}
    assert wrote["cold_history_bytes"] == 0
    assert 31 <= wrote["suffix_events_max"] < 31 + 16  # up to a boundary
    # twice the suffixes' events over the log's, exactly
    assert metrics["recover.replayed_events_pct"] == pytest.approx(
        200.0 * log["suffix_events"] / log["events"])
    legs = _json_lines(proc.stderr, '"legs_over_call"')
    assert legs and all(0.9 < one["legs_over_call"] <= 1.0 for one in legs)
    (series,) = _json_lines(proc.stderr, '"window_series"')
    window = series["window_series"]
    passes = last["attempted"] // RUNS
    assert window["tpu.snapshot"]["hydrates"] == 2 * RUNS * passes
    assert window["tpu.recover"]["snapshot-records"] == RUNS * passes


def _rehearse(monkeypatch, capsys, broken=None, control=""):
    """A rehearsed run in this process, `recover` (the timed entry, and
    set-up's bring-up of the cut log) wrapped by `broken(path, real)`
    underneath the harness."""
    import run

    if broken is not None:
        real_load = run.load_module

        def load(kind, name):
            module = real_load(kind, name)
            if kind == "drivers":
                real = module.recover
                monkeypatch.setattr(
                    module, "recover", lambda path: broken(path, real))
            return module

        monkeypatch.setattr(run, "load_module", load)
    argv = ["--workload", CELL, "--seed", "12345", "--seconds", "0.2",
            "--trace", "0", "--rehearse"]
    if control:
        argv += ["--control", control]
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _with_records_installed_through(path, real, change):
    """Recover with `change(record)` between a `snap` line's parse and its
    installation: the log's bytes stay as they are."""
    from cadence_tpu.engine import durability

    parse = durability.snapshot_from_record
    durability.snapshot_from_record = lambda rec: change(parse(rec))
    try:
        return real(path)
    finally:
        durability.snapshot_from_record = parse


ALTERED = 5


def _alter_a_state_blob_and_its_crc(path, real):
    """The first records' persisted states altered and their CRCs made
    good again: every gate of the program passes them, the rebuild and the
    verify agree on the wrong state of each run that has batches after its
    record, and the reference alone can tell."""
    import jax
    import numpy as np

    from cadence_tpu.core.checksum import DEFAULT_LAYOUT
    from cadence_tpu.engine import snapshot

    seen = []

    def change(rec):
        seen.append(rec.key)
        if len(seen) > ALTERED:
            return rec
        row = jax.tree_util.tree_map(
            np.array, snapshot.unpack_state_row(rec.state_blob,
                                                DEFAULT_LAYOUT))
        row = row._replace(signal_count=row.signal_count + 1)
        rec.state_blob = snapshot.pack_state_row(row)
        rec.blob_crc = zlib.crc32(rec.state_blob)
        return rec

    return _with_records_installed_through(path, real, change)


def _ignore_every_record(path, real):
    # every state comes out right, and none of them from a record
    os.environ["CADENCE_TPU_SNAPSHOT"] = "0"
    try:
        return real(path)
    finally:
        del os.environ["CADENCE_TPU_SNAPSHOT"]


def _drop_one_record(path, real):
    seen = []

    def change(rec):
        seen.append(rec.key)
        if len(seen) == 5:
            raise ValueError("planted: a record the log replay cannot read")
        return rec

    return _with_records_installed_through(path, real, change)


def _tear_one_record(path, real):
    seen = []

    def change(rec):
        seen.append(rec.key)
        if len(seen) == 5:  # the blob no longer has the CRC it was put with
            rec.state_blob = rec.state_blob[:-7] + bytes(
                b ^ 0x7F for b in rec.state_blob[-7:])
        return rec

    return _with_records_installed_through(path, real, change)


@pytest.mark.parametrize("fault,numbers", [
    (None, set()),
    (_alter_a_state_blob_and_its_crc, {"state_crc_mismatch"}),
    (_ignore_every_record, {"snapshots_not_hydrated"}),
    (_drop_one_record, {"snap_records_missing", "snapshots_not_hydrated"}),
    (_tear_one_record, {"snapshots_ignored", "snapshots_not_hydrated"}),
])
def test_correct_comes_out_false_with_the_warm_restart_broken(
        monkeypatch, capsys, fault, numbers):
    last = _rehearse(monkeypatch, capsys, fault)
    assert last["correct"] is (fault is None)
    assert tuple(last["compared"]) == COMPARED
    over = {name: c["value"] for name, c in last["compared"].items()
            if c["value"] > c["limit"]}
    assert set(over) == numbers
    passes = last["attempted"] // RUNS
    if fault is _alter_a_state_blob_and_its_crc:
        # a run whose record sits at its tip serves the record's own
        # payload row, finds the state at odds with it and replays whole
        assert 1 <= over["state_crc_mismatch"] <= ALTERED
    if fault is _ignore_every_record:
        # neither device pass hydrated a run, in any pass
        assert over["snapshots_not_hydrated"] == 2 * RUNS * passes
    if fault is _tear_one_record:
        # both consults meet it and pass it over; the run replays whole
        assert over["snapshots_ignored"] == 2 * passes
        assert over["snapshots_not_hydrated"] == 2 * passes
    if fault is _drop_one_record:
        assert over["snap_records_missing"] == passes
        assert over["snapshots_not_hydrated"] == 2 * passes


def test_the_control_comes_out_not_correct(monkeypatch, capsys):
    last = _rehearse(monkeypatch, capsys, control="drop-last-batch")
    assert last["correct"] is False
    over = {name for name, c in last["compared"].items()
            if c["value"] > c["limit"]}
    assert over == {"state_crc_mismatch"}
    assert last["compared"]["state_crc_mismatch"]["value"] == RUNS


def test_a_program_that_stacks_a_chunk_in_one_program_ends_in_set_up(
        monkeypatch, capsys):
    import run
    from cadence_tpu.engine import resident

    monkeypatch.delattr(resident, "STACK_BLOCK")
    with pytest.raises(SystemExit) as stopped:
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "0.2",
                  "--trace", "0", "--rehearse"])
    assert "cannot run on it" in str(stopped.value)
    assert capsys.readouterr().out == ""  # no result line


@pytest.mark.parametrize("batches,after,cut", [
    ([2, 3, 4, 5], 0, 4),    # at the tip: an exact hit
    ([2, 3, 4, 5], 1, 3),    # the last boundary leaving at least one
    ([2, 3, 4, 5], 5, 3),
    ([2, 3, 4, 5], 6, 2),
    ([2, 3, 4, 5], 12, 1),
    ([2, 3, 4, 5], 31, 1),   # fewer left than asked: the first batch's end
    ([7], 31, 1),
])
def test_the_cut_is_the_last_boundary_that_leaves_the_lag(batches, after,
                                                          cut):
    driver = _reader_of_driver()
    assert driver.cut_of(batches, after) == cut


def _reader_of_driver():
    import run

    return run.load_module("drivers", "recover_warm")


def test_warm_counts_against_three_runs_by_hand():
    import counts
    import counts_recover_warm

    # run A: a record, 100 B committed since; run B: a record at its tip;
    # run C: no record, a history of 5,000 B. Two state rows of 3,602 B and
    # 5,100 B of history in, three payload rows of 89 int64 out
    least = counts_recover_warm.warm_least_bytes(3602, 2, 100, 5000, 3)
    assert least == 2 * 3602 + 100 + 5000 + 3 * 712 == 14_440
    share = counts.roofline_share_pct(least, 0.001, "TPU v5 lite")
    assert share == pytest.approx(100 * (14_440 / 819e9) / 0.001)
    # the cell's size: 3,200 records, ~17 events of 57.7 B since each
    whole = counts_recover_warm.warm_least_bytes(
        3602, 3200, 3200 * 17 * 58, 0, 3200)
    assert whole == 11_526_400 + 3_155_200 + 2_278_400


# -- the readers, on a planted span list -------------------------------------


def _planted_trace(verify_legs: bool = True) -> dict:
    """One warm recovery of 10 s: log-replay 1; rebuild 4.99 (consult 0.5,
    prepass 4 with suffix-replay 3 and hydrate 0.8, upsert 0.2); verify 4
    (partition 0.7 with consult 0.6, suffix-replay 3.1); reconcile 0.01;
    the chip busy 5 ms."""
    main = [
        ("recover.call", *_s(0, 10)),
        ("recover.log-replay", *_s(0, 1)),
        ("recover.rebuild", *_s(1, 5.99)),
        ("rebuild.snapshot-consult", *_s(1.0, 1.5)),
        ("rebuild.resident-prepass", *_s(1.5, 5.5)),
        ("rebuild.suffix-replay", *_s(1.6, 4.6)),
        ("resident.device-wait", *_s(2.0, 2.1)),
        ("rebuild.hydrate", *_s(4.6, 5.4)),
        ("recover.upsert", *_s(5.6, 5.8)),
        ("recover.verify", *_s(5.99, 9.99)),
        ("verify.partition", *_s(6.0, 6.7)),
        ("verify.suffix-replay", *_s(6.8, 9.9)),
        ("recover.reconcile", *_s(9.99, 10)),
    ]
    if verify_legs:
        main.append(("verify.snapshot-consult", *_s(6.05, 6.65)))
    else:  # a program from before the verify's own legs were spans
        main = [e for e in main if e[0] != "verify.suffix-replay"]
    return {"_host_lines": [("python3", main)], "busy_s": 0.005,
            "modules": {}, "ops": {}}


def _ctx(kind: str = "recover", **over) -> dict:
    ctx = {"kind": kind, "device": {"kind": "TPU v5 lite"},
           "passes": [{"events": 1000, "traced": True},
                      {"events": 1000, "traced": False}],
           "window_s": 20.0, "trace": _planted_trace(), "runs": 10,
           "history_bytes": 50_000, "rehearse": False,
           "counters": {"history-events": 999_999},
           "window_counters": {"history-events": 2000, "suffix-events": 560,
                               "events-rebuilt": 20, "events-verified": 20},
           "warm": {"state_row_bytes": 3602, "eligible_runs": 9,
                    "suffix_bytes": 4000, "cold_history_bytes": 5000}}
    ctx.update(over)
    return ctx


PLANTED = {"recover.snapshot_consult_share_pct": 11.0,
           "recover.suffix_replay_share_pct": 61.0,
           "recover.replayed_events_pct": 30.0,
           # (9 x 3,602 + 4,000 + 5,000 + 10 x 712) B at 819 GB/s over 5 ms
           "recover_warm_roofline": 100 * (48_538 / 819e9) / 0.005,
           # the cold cell's readers the warm cell reports too
           "recover.log_replay_share_pct": 10.0,
           "recover.hydrate_share_pct": 8.0,
           "recover.verify_share_pct": 40.0,
           "recover.device_ns_per_event": 5000.0}


@pytest.mark.parametrize("name", sorted(PLANTED))
def test_reader_gives_the_planted_value(name):
    assert _reader(name).read(_ctx()) == pytest.approx(PLANTED[name])


@pytest.mark.parametrize("name", [
    "recover.snapshot_consult_share_pct", "recover.suffix_replay_share_pct",
    "recover.replayed_events_pct", "recover_warm_roofline"])
def test_new_reader_gives_none_where_there_is_nothing_to_read(name):
    read = _reader(name).read
    for kind in ("replay", "serve"):
        assert read(_ctx(kind)) is None
        assert read({"kind": kind, "trace": None}) is None
    # the parent: the rebuild's consult is a span, the verify's legs are
    # not, and the report's counters do not exist; half a share is not read
    parent = _ctx(trace=_planted_trace(verify_legs=False), window_counters={
        "history-events": 2000, "events-rebuilt": 20})
    if name != "recover_warm_roofline":
        assert read(parent) is None
    assert read(_ctx(trace=None, window_counters=None, warm=None)) is None
    # a CPU's trace is no chip's: nothing by a device metric's name
    if name == "recover_warm_roofline":
        assert read(_ctx(rehearse=True)) is None
        assert read(_ctx(warm=None)) is None
