"""Tests of the cell `recover.wal-1chip` (PR 34): a rehearsed run's last
line, its compared numbers each tripped by a fault planted underneath the
harness, the control, the recovery's byte count, and the seven readers on a
planted span list.

    python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import importlib.util
import json
import os

import pytest
from test_benchmark import BENCH, _run

CELL = "recover.wal-1chip"
RUNS = 5 * 24  # the rehearsal's corpus
COMPARED = ("state_crc_mismatch", "acked_batches_missing", "divergent",
            "executions_not_rebuilt", "open_workflows_differing",
            "pointer_or_visibility_missing", "rows_not_on_device",
            "compiles_in_window", "log_bytes_changed")
SPAN_READERS = {"recover.log_replay_share_pct": 10.0,
                "recover.pack_share_pct": 20.0,
                "recover.hydrate_share_pct": 7.0,
                "recover.verify_share_pct": 60.0,
                "recover.seed_resident_share_pct": 40.0}


def test_rehearsed_recover_cell_prints_a_well_formed_last_line():
    proc = _run("--workload", CELL, "--seed", str(2**31 + 34), "--seconds",
                "1", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] % RUNS == 0 and last["attempted"] >= RUNS
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    compared = last["compared"]
    assert tuple(compared) == COMPARED
    assert all(c["value"] == 0 and c["limit"] == 0 for c in compared.values())
    # the span metrics read on any backend; what needs a chip's trace is
    # left out of a rehearsal's line
    metrics = last["metrics"]
    assert set(metrics) == {"rehearsal." + name for name in SPAN_READERS}
    assert all(0 < m["value"] < 100 for m in metrics.values())
    assert metrics["rehearsal.recover.seed_resident_share_pct"]["value"] \
        < metrics["rehearsal.recover.verify_share_pct"]["value"]
    tail = proc.stderr.strip().splitlines()[-len(compared):]
    assert all(line.startswith("compared ") for line in tail)
    # the top-level legs of every pass are printed beside their call
    legs = [json.loads(line) for line in proc.stderr.splitlines()
            if line.startswith("{") and '"legs_over_call"' in line]
    assert legs and all(0.9 < one["legs_over_call"] <= 1.0 for one in legs)


def _rehearse(monkeypatch, capsys, broken=None, control=""):
    """A rehearsed run in this process, `recover` (the timed entry) wrapped
    by `broken(stores, report)` underneath the harness."""
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


def _alter_one_state(path, real):
    stores, report = real(path)
    key = sorted(stores.execution.list_executions())[7]
    stores.execution.get_workflow(*key).execution_info.signal_count += 1
    return stores, report


def _fall_back_to_the_oracle(path, _real):
    # every state comes out right, and none of them from the device
    from cadence_tpu.engine.durability import recover_stores

    return recover_stores(path, verify_on_device=False,
                          rebuild_on_device=False)


def _lose_an_acknowledged_batch(path, real):
    stores, report = real(path)
    key = sorted(stores.history.list_runs())[3]
    stores.history._branches[key][0].pop()  # the run's last transaction
    return stores, report


def _grow_the_log(path, real):
    stores, report = real(path)
    stores.wal.append({"t": "cfg", "k": "planted", "v": 1, "dom": None})
    return stores, report


@pytest.mark.parametrize("fault,numbers", [
    (None, set()),
    (_alter_one_state, {"state_crc_mismatch"}),
    (_fall_back_to_the_oracle, {"rows_not_on_device"}),
    (_lose_an_acknowledged_batch, {"acked_batches_missing"}),
    (_grow_the_log, {"log_bytes_changed"}),
])
def test_correct_comes_out_false_with_the_timed_entry_broken(
        monkeypatch, capsys, fault, numbers):
    last = _rehearse(monkeypatch, capsys, fault)
    assert last["correct"] is (fault is None)
    over = {name for name, c in last["compared"].items()
            if c["value"] > c["limit"]}
    assert over == numbers
    if fault is _alter_one_state:
        # one execution, however many passes saw it altered
        assert last["compared"]["state_crc_mismatch"]["value"] == 1
    if fault is _fall_back_to_the_oracle:
        passes = last["attempted"] // RUNS
        # rebuilt by the oracle, not rebuilt and not verified on the device
        assert last["compared"]["rows_not_on_device"]["value"] \
            == 3 * RUNS * passes


def test_the_control_comes_out_not_correct(monkeypatch, capsys):
    last = _rehearse(monkeypatch, capsys, control="drop-last-batch")
    assert last["correct"] is False
    over = {name for name, c in last["compared"].items()
            if c["value"] > c["limit"]}
    assert over == {"state_crc_mismatch"}
    # every history's last transaction moves its next event id: every
    # execution is compared, and every one differs
    assert last["compared"]["state_crc_mismatch"]["value"] == RUNS


def test_recover_counts_against_a_hand_worked_shape():
    import counts
    import counts_recover

    # 3,200 runs whose histories serialize to 56 B an event at 113 events,
    # one state row of 89 int64 a run out
    history = 3200 * 113 * 56
    least = counts_recover.recover_least_bytes(history, 3200)
    assert least == 20_249_600 + 2_278_400
    # moved at 819 GB/s that takes 27.5 us; 0.64 s of device time is 0.0043 %
    share = counts.roofline_share_pct(least, 0.64, "TPU v5 lite")
    assert share == pytest.approx(100 * (22_528_000 / 819e9) / 0.64)
    assert 0.0042 < share < 0.0044


# -- the readers, on a planted span list -------------------------------------


def _reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _s(lo: float, hi: float):
    return lo * 1e9, hi * 1e9


def _planted_trace() -> dict:
    """One recovery of 10 s: log-replay 1, rebuild 2.99 (hydrate 0.7,
    upsert 0.2), verify 6 (seed-resident 4 inside its replay), reconcile
    0.01; the packs on a pack thread, 0.5 and 1.5 s; the chip busy 2 ms."""
    main = [
        ("recover.call", *_s(0, 10)),
        ("recover.log-replay", *_s(0, 1)),
        ("recover.rebuild", *_s(1, 3.99)),
        ("rebuild.snapshot-consult", *_s(1.0, 1.001)),
        ("rebuild.replay", *_s(1.1, 3.0)),
        ("device-wait", *_s(2.0, 2.5)),
        ("rebuild.hydrate", *_s(3.0, 3.7)),
        ("recover.upsert", *_s(3.7, 3.9)),
        ("recover.verify", *_s(3.99, 9.99)),
        ("verify.replay", *_s(4.5, 9.5)),
        ("verify.seed-resident", *_s(5, 9)),
        ("PjitFunction(slice_row)", *_s(5.0, 5.001)),
        ("recover.reconcile", *_s(9.99, 10)),
    ]
    packers = [("pack", *_s(1.1, 1.7)), ("rebuild.encode", *_s(1.15, 1.65)),
               ("pack", *_s(4.0, 5.6)), ("verify.pack", *_s(4.05, 5.55))]
    return {"_host_lines": [("python3", main), ("cadence-pack_0", packers)],
            "busy_s": 0.002, "modules": {}, "ops": {}}


def _ctx(kind: str = "recover") -> dict:
    return {"kind": kind, "device": {"kind": "TPU v5 lite"},
            "passes": [{"events": 1000, "traced": True},
                       {"events": 1000, "traced": False}],
            "calls": [], "window_s": 20.0, "trace": _planted_trace(),
            "runs": 10, "history_bytes": 50_000, "rehearse": False}


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_reader_gives_the_planted_share(name):
    assert _reader(name).read(_ctx()) == pytest.approx(SPAN_READERS[name])


def test_device_readers_give_the_planted_values():
    assert _reader("recover.device_ns_per_event").read(_ctx()) \
        == pytest.approx(2000.0)
    # (50,000 + 10 x 712) B at 819 GB/s over 2 ms
    assert _reader("recover_replay_roofline").read(_ctx()) \
        == pytest.approx(100 * (57_120 / 819e9) / 0.002)
    # a CPU's trace is no chip's: nothing by a device metric's name
    rehearsal = dict(_ctx(), rehearse=True)
    assert _reader("recover.device_ns_per_event").read(rehearsal) is None
    assert _reader("recover_replay_roofline").read(rehearsal) is None


@pytest.mark.parametrize("name", sorted(SPAN_READERS) + [
    "recover.device_ns_per_event", "recover_replay_roofline"])
def test_reader_gives_none_on_another_cells_context_and_on_the_parent(name):
    read = _reader(name).read
    for kind in ("replay", "serve"):
        assert read(_ctx(kind)) is None
        assert read({"kind": kind, "trace": None}) is None
    # the parent commit's trace holds no recovery span: the span readers
    # find nothing to read, and say so without raising
    bare = dict(_ctx(), trace={
        "_host_lines": [("python3", [("PjitFunction(slice_row)", *_s(0, 1)),
                                     ("device-wait", *_s(1, 2))])],
        "busy_s": None, "modules": {}, "ops": {}})
    assert read(bare) is None
    assert read(dict(_ctx(), trace=None)) is None


def test_the_call_breakdown_names_every_leg_and_what_no_leg_covers():
    common = _reader("_recover_common")
    out = common.call_breakdown(_ctx())
    assert out["recover.call"] == pytest.approx(10.0)
    assert out["top_legs_over_call"] == pytest.approx(1.0)
    assert out["call_in_no_leg_s"] == pytest.approx(0.0, abs=1e-9)
    assert out["rebuild.encode"] == pytest.approx(0.5)
    assert "device-wait" not in out and "pack" not in out
    assert common.call_breakdown(_ctx("replay")) is None
