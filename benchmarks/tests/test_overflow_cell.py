"""Tests of the cell `replay.overflow-1chip` (PR 27): its driver's three
compared numbers, each tripped by a fault planted underneath the harness,
the ladder's byte count, and the reference on fan-out histories.

    python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import json

import pytest
from test_benchmark import _rehearse_in_process, _run

CELL = "replay.overflow-1chip"
NEW_NUMBERS = ("escalated_crc_mismatch", "ladder_residual_rows",
               "flagged_rows_short_of_floor")


def test_rehearsed_overflow_cell_prints_a_well_formed_last_line():
    proc = _run("--workload", CELL, "--seed", str(2**31 + 27), "--seconds",
                "1", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    compared = last["compared"]
    assert list(compared)[-3:] == list(NEW_NUMBERS)
    assert all(c["value"] == 0 and c["limit"] == 0 for c in compared.values())
    # the counted and the span metrics read on any backend; what needs a
    # chip's trace is left out of a rehearsal's line
    metrics = last["metrics"]
    assert all(name.startswith("rehearsal.") for name in metrics)
    assert 0 < metrics["rehearsal.ladder.host_share_pct"]["value"] < 100
    assert 0 <= metrics["rehearsal.ladder.pad_rows_pct"]["value"] < 100
    assert "rehearsal.h2d.bytes_per_event" in metrics
    assert "rehearsal.ladder_wirec_roofline" not in metrics
    assert "rehearsal.ladder.kernel_ns_per_event" not in metrics
    tail = proc.stderr.strip().splitlines()[-len(compared):]
    assert all(line.startswith("compared ") for line in tail)


def _alter_the_ladders_answers(crc, err, rep):
    crc = crc.copy()
    crc[rep.ladder_indices] ^= 1  # where the feeder patches the rungs' CRCs in
    return crc, err, rep


def _leave_the_flagged_rows_flagged(crc, err, rep):
    import dataclasses

    err = err.copy()
    err[rep.ladder_indices] = 7  # a rung's rows come back unresolved
    n = len(rep.ladder_indices)
    return crc, err, dataclasses.replace(rep, ladder_resolved=0,
                                         ladder_residual=n)


@pytest.mark.parametrize("fault,numbers", [
    (None, ()),
    (_alter_the_ladders_answers, ("escalated_crc_mismatch",)),
    (_leave_the_flagged_rows_flagged, ("ladder_residual_rows",
                                       "error_flags")),
])
def test_correct_comes_out_false_with_the_ladder_broken(
        monkeypatch, capsys, fault, numbers):
    last = _rehearse_in_process(monkeypatch, capsys, CELL, fault)
    assert last["correct"] is (fault is None)
    over = {name for name, c in last["compared"].items()
            if c["value"] > c["limit"]}
    assert set(numbers) <= over
    if fault is _alter_the_ladders_answers:
        # every escalated row is compared, whatever the sample drew
        assert last["compared"]["escalated_crc_mismatch"]["value"] >= 2


def test_the_control_comes_out_not_correct(monkeypatch, capsys):
    last = _rehearse_in_process(monkeypatch, capsys, CELL,
                                control="drop-last-batch")
    assert last["correct"] is False
    assert last["compared"]["crc_mismatch_in_sample"]["value"] == 40
    assert last["compared"]["escalated_crc_mismatch"]["value"] >= 2


def test_a_corpus_without_fan_outs_is_short_of_the_floor(monkeypatch, capsys):
    """The cell measures the ladder or nothing: with the suite's fan-outs
    gone (the basic suite in its place) every answer is still right, and
    the run is not correct."""
    import run

    real = run.find_cell

    def find_cell(bench, workload):
        cell, config, traffic = real(bench, workload)
        return cell, config, dict(traffic, suites=["basic"])

    monkeypatch.setattr(run, "find_cell", find_cell)
    last = _rehearse_in_process(monkeypatch, capsys, CELL)
    assert last["correct"] is False
    over = {name for name, c in last["compared"].items()
            if c["value"] > c["limit"]}
    assert over == {"flagged_rows_short_of_floor"}


def test_ladder_counts_against_a_hand_worked_shape():
    import counts
    import counts_ladder

    # 2,200 gathered rows of 80 events at 14 B an event, 14 B of bases and
    # 4 B of count a row in; CRC, error and flag out
    wire = 2200 * (80 * 14 + 14 + 4)
    least = counts_ladder.ladder_wirec_least_bytes(wire, 2200)
    assert least == 2_503_600 + 19_800
    # moved at 819 GB/s that takes 3.08 us; a rung of 75 ms is at 0.0041 %
    share = counts.roofline_share_pct(least, 0.075, "TPU v5 lite")
    assert share == pytest.approx(100 * (2_523_400 / 819e9) / 0.075)
    assert 0.004 < share < 0.0042


def test_reference_agrees_with_the_program_on_fan_out_histories():
    """The reference has no table to outgrow. At the final boundary of
    fan-out histories (24 activities pending at once, drained before the
    close) it gives the CRC of the program's own state builder; in between
    the program's payload has no row for such a state at all."""
    import numpy as np
    from refimpl import replay as ref
    from refimpl.gen.corpus import generate_history

    from cadence_tpu.core.checksum import (STICKY_ROW_INDEX, crc32_of_row,
                                           payload_row)
    from cadence_tpu.gen import corpus as theirs
    from cadence_tpu.oracle.state_builder import StateBuilder

    fan_outs = 0
    for seed in (27, 2**31 + 27):
        for i in range(160):
            history = generate_history("overflow", seed, i, 120)
            program = theirs.generate_history("overflow", seed, i, 120)
            scheduled = max(sum(kind == "ActivityTaskScheduled"
                                for _id, kind, _v, _a in batch)
                            for batch in ref.plain(history))
            if scheduled < 24 and i % 16:
                continue  # every fan-out, and one plain history in 16
            fan_outs += scheduled == 24
            row = payload_row(StateBuilder().replay_history(program))
            row[STICKY_ROW_INDEX] = 0
            assert ref.crc_of_history(ref.plain(history)) == \
                int(np.uint32(crc32_of_row(row))), (seed, i)
            if scheduled == 24:
                k = next(k for k, batch in enumerate(program)
                         if len(batch.events) > 24)
                with pytest.raises(OverflowError):
                    payload_row(StateBuilder().replay_history(
                        program[:k + 1]))
    assert fan_outs >= 4
