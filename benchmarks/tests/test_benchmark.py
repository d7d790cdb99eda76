"""Fast tests of the benchmark's own code: they call into `benchmarks/`
and launch no full-size cluster.

    python -m pytest benchmarks/tests -q
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from collections import Counter

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the trace reduction ----------------------------------------------------


def test_union_gaps_and_self_time_on_hand_made_intervals():
    import trace_reduce as tr

    spans = [(0, 10e9), (5e9, 12e9), (20e9, 21e9)]
    assert tr.union_seconds(spans) == pytest.approx(13.0)
    assert tr.gaps(spans) == [(12e9, 8.0)]
    # a while of 10 s that spans two body ops of 3 s and 4 s
    self_s = tr.self_seconds([("while", 0, 10e9), ("a", 1e9, 4e9),
                              ("b", 5e9, 9e9), ("c", 11e9, 12e9)])
    assert self_s == pytest.approx({"while": 3.0, "a": 3.0, "b": 4.0,
                                    "c": 1.0})


def test_reduction_of_a_trace_recorded_on_the_v5e():
    """Three runs of one small program with 20 ms sleeps between them
    (recorded on the chip, PR 24): one chip, three module runs, the busy
    union the sum of the three, two gaps of a sleep's length."""
    import trace_reduce as tr

    r = tr.reduce_trace(os.path.join(BENCH, "testdata",
                                     "small_v5e.xplane.pb"))
    assert [d["name"] for d in r["devices"]] == ["/device:TPU:0"]
    (name, module), = r["modules"].items()
    assert name.startswith("jit__lambda") and module["runs"] == 3
    assert r["busy_s"] == pytest.approx(module["seconds"], rel=0.01)
    assert 1e-5 < r["busy_s"] < 1e-3
    long_gaps = [g for g in r["devices"][0]["gaps"] if g[1] > 0.015]
    assert len(long_gaps) == 2
    assert max(r["ops"], key=r["ops"].get) == "%convolution_reduce_fusion"
    assert r["compilations"] == 0
    out = tr.breakdown(r)
    assert out["device_ops"][0][0] == "%convolution_reduce_fusion"
    assert out["idle_gaps"][0][0] == "$time sleep"


# -- counts and peaks -------------------------------------------------------


def test_counts_against_hand_worked_shapes():
    import counts

    # 4,096 workflows, 465,000 events at 14 B/event in; 8 B a workflow out
    least = counts.replay_wirec_least_bytes(465_000 * 14, 4096)
    assert least == 6_510_000 + 32_768
    # moved at 819 GB/s that takes 7.99 us; a kernel of 68.5 ms is at 0.0117 %
    share = counts.roofline_share_pct(least, 0.0685, "TPU v5 lite")
    assert share == pytest.approx(100 * (6_542_768 / 819e9) / 0.0685)
    assert 0.011 < share < 0.012
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


# -- BENCHMARK.json is data that resolves to files --------------------------


def test_every_name_resolves_to_a_file_and_uses_allowed_characters():
    b = bench()
    assert sorted(b) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"])
    assert b["command"] == ["python3", "benchmarks/run.py"]
    configs = {c["name"]: c for c in b["configs"]}
    files = [c["file"] for c in b["configs"]]
    assert len(set(files)) == len(files)
    used = set()
    for cell in b["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        config = configs[cell["config"]]
        used.add(cell["config"])
        with open(os.path.join(ROOT, config["file"])) as f:
            doc = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           doc["driver"] + ".py"))
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           cell["traffic"] + ".json"))
        for key in config["reduced"]:
            assert NAME.match(key) and key in doc, key
    assert used == set(configs)
    cells = {c["name"] for c in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    layers = set()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e
        # every cell that reads it reports the end-to-end metric it moves
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved)
        layers.add(m["layer"])
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md does not list the layer {layer!r}"
    four = [c for c in b["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 2)


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    import run

    b = bench()
    for cell in b["workloads"]:
        e2e = [m["name"] for m in run.metrics_of(b, "end_to_end",
                                                 cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metrics_of(b, "per_layer", cell["name"], e2e)


# -- the load generator's copy ----------------------------------------------


def test_schedule_copy_reproduces_the_programs_digest_for_uniform_draws():
    import loadgen as lg
    from cadence_tpu.loadgen import mixes

    theirs = [mixes.DomainPlan("a", 50.0, pool_size=8),
              mixes.DomainPlan("b", 20.0, mixes.QUERY_HEAVY_MIX,
                               pool_size=12, arrival="uniform")]
    ours = [lg.DomainPlan("a", 50.0, dict(mixes.STANDARD_MIX.weights), 8),
            lg.DomainPlan("b", 20.0, dict(mixes.QUERY_HEAVY_MIX.weights), 12,
                          arrival="uniform")]
    for seed in (0, 77, 2**31 + 5):
        assert lg.trace_digest(lg.build_schedule(ours, 20, seed)) == \
            mixes.trace_digest(mixes.build_schedule(theirs, 20, seed))


def test_zipf_draws_are_seed_stable_and_skewed_and_fixed_sets_are_fixed():
    import loadgen as lg

    with open(os.path.join(BENCH, "traffic", "standard-skewed.json")) as f:
        mix = json.load(f)["mix"]
    drawn = [lg.DomainPlan("a", 100.0, mix, 256, pool_draw="zipf")]
    assert lg.trace_digest(lg.build_schedule(drawn, 30, 5)) == \
        lg.trace_digest(lg.build_schedule(drawn, 30, 5))
    fixed = [lg.DomainPlan("a", 100.0, mix, 256, pool_draw="zipf",
                           fixed_set=True)]
    one, two = (lg.build_schedule(fixed, 30, s) for s in (1, 2**31 + 7))
    assert lg.trace_digest(one) != lg.trace_digest(two)
    assert len(one) == len(two) == 3000
    assert Counter(o.kind for o in one) == Counter(o.kind for o in two)
    assert Counter(o.kind for o in one)["start"] == 900
    pool = lambda s: Counter(  # noqa: E731
        o.workflow_id for o in s if o.kind in lg.POOL_OPS)
    hot = pool(one).most_common(1)[0]
    assert hot[0] == "lg-a-pool-0" and hot[1] > 5 * (1500 / 256)
    gaps = lambda s: sorted(round(b.at_s - a.at_s, 4)  # noqa: E731
                            for a, b in zip(s, s[1:]))
    assert gaps(one)[len(one) // 2] == pytest.approx(
        gaps(two)[len(two) // 2], abs=2e-4)
    # ids of the warm-up schedule never meet the window's
    warm = lg.build_schedule(fixed, 4, "1:warm", id_salt="w")
    assert not {o.workflow_id for o in warm if o.kind == "start"} & \
        {o.workflow_id for o in one}


def test_percentiles_from_raw_samples():
    from harness import percentile

    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(xs, 50) == 3.0
    assert percentile(xs, 95) == pytest.approx(4.8)
    assert percentile(xs, 0) == 1.0 and percentile(xs, 100) == 5.0
    with pytest.raises(ValueError):
        percentile([], 50)


# -- the plain reference ------------------------------------------------------


def test_reference_on_a_hand_worked_history():
    """Two transactions worked by hand from upstream's rules: the start
    with its first decision, then the decision started; and a decision
    that fails, whose successor has no event of its own."""
    from refimpl import replay as ref

    start = [(1, "WorkflowExecutionStarted", 7, {}),
             (2, "DecisionTaskScheduled", 7, {"attempt": 0})]
    started = [(3, "DecisionTaskStarted", 7, {"scheduled_event_id": 2})]
    row = ref.payload_row(ref.replay([start, started]))
    assert row[:11] == [0, 1, 3, 4, -23, 0, 0, 2, 3, 7, 0]
    assert row[11:14] == [1, 3, 7] and row[14] == ref.PAD  # one item (3, v7)
    assert len(row) == ref.ROW_WIDTH == 89
    assert [row[28], row[45], row[62], row[71], row[80]] == [0] * 5
    failed = [(4, "DecisionTaskFailed", 7, {}),
              (5, "TimerStarted", 9, {"timer_id": "t"}),
              (6, "WorkflowExecutionSignaled", 9, {})]
    row = ref.payload_row(ref.replay([start, started, failed]))
    # attempt 1, scheduled under the id the state held when it failed (4)
    assert row[:11] == [0, 1, 4, 7, -23, 1, 1, 4, -23, 7, 0]
    assert row[11:16] == [2, 4, 7, 6, 9]         # (4, v7), (6, v9)
    assert row[28:30] == [1, 5]                  # the timer, by started id
    assert ref.crc32(row) != ref.crc_of_history([start, started, failed],
                                                "drop-last-batch")
    with pytest.raises(KeyError):
        ref.replay([start, [(3, "TimerFired", 7, {"timer_id": "x"})]])
    with pytest.raises(LookupError):
        ref.replay([[(1, "NoSuchEvent", 0, {})]])


def test_reference_shares_no_code_with_the_program_and_agrees_with_it():
    """The reference imports nothing of the program, and on every
    transaction boundary of histories of every suite it gives the CRC the
    program's own state builder and checksum give: two implementations."""
    import numpy as np
    from refimpl import replay as ref
    from refimpl.gen.corpus import SUITES, generate_history

    from cadence_tpu.core.checksum import (STICKY_ROW_INDEX, crc32_of_row,
                                           payload_row)
    from cadence_tpu.core.enums import EventType
    from cadence_tpu.gen import corpus as theirs
    from cadence_tpu.oracle.state_builder import StateBuilder

    with open(ref.__file__) as f:
        assert "cadence_tpu" not in f.read()
    refdir = os.path.join(BENCH, "refimpl")
    assert sorted(os.listdir(refdir)) == sorted(
        ["__init__.py", "core", "gen", "replay.py"]
        + [d for d in os.listdir(refdir) if d == "__pycache__"])
    assert set(ref.TABLE) == {e.name for e in EventType}
    for suite in SUITES:
        for seed, i in ((2**31 + 3, 0), (11, 5), (11, 6)):
            history = generate_history(suite, seed, i, 120)
            program = theirs.generate_history(suite, seed, i, 120)
            plain = ref.plain(history)
            for k in range(1, len(history) + 1):
                row = payload_row(StateBuilder().replay_history(program[:k]))
                row[STICKY_ROW_INDEX] = 0
                assert ref.crc_of_history(plain[:k]) == \
                    int(np.uint32(crc32_of_row(row))), (suite, seed, i, k)


# -- a run, rehearsed ---------------------------------------------------------


def _run(*args, env=None):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, **(env or {})})
    return proc


def test_rehearsed_replay_cell_prints_a_well_formed_last_line():
    proc = _run("--workload", "replay.mixed-1chip", "--seed",
                str(2**31 + 11), "--seconds", "1", "--trace", "1",
                "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(last)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu" and last["rehearsal"] is True
    # nothing measured on a CPU goes by a device metric's name
    assert last["metrics"] and all(
        name.startswith("rehearsal.") for name in last["metrics"])
    assert "rehearsal.h2d.bytes_per_event" in last["metrics"]
    assert "rehearsal.replay_wirec_roofline" not in last["metrics"]
    assert {"device_ops", "idle_gaps"} <= set(last["breakdown"])
    tail = proc.stderr.strip().splitlines()[-len(last["compared"]):]
    assert all(line.startswith("compared ") for line in tail)


def test_a_cpu_is_never_called_a_tpu():
    proc = _run("--workload", "replay.mixed-1chip", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def _rehearse_in_process(monkeypatch, capsys, workload, broken_feed=None,
                         control=""):
    import run

    if broken_feed is not None:
        real_load = run.load_module

        def load(kind, name):
            module = real_load(kind, name)
            if kind == "drivers":
                real = module.feed
                monkeypatch.setattr(
                    module, "feed",
                    lambda *a, **k: broken_feed(*real(*a, **k)))
            return module

        monkeypatch.setattr(run, "load_module", load)
    argv = ["--workload", workload, "--seed", "12345", "--seconds", "0.3",
            "--trace", "0", "--rehearse"]
    if control:
        argv += ["--control", control]
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _alter_one_answer(crc, err, rep):
    crc = crc.copy()
    crc[:] ^= 1  # every CRC off by one bit, where the feeder hands them over
    return crc, err, rep


def _leave_half_the_batch_out(crc, err, rep):
    crc = crc.copy()
    crc[len(crc) // 2:] = 0  # the second half of the rows never replayed
    return crc, err, rep


def _leave_one_chips_slice_out(crc, err, rep):
    crc = crc.copy()
    for lo in range(0, len(crc), 64):  # chunk of 64 rows, four slices of 16
        crc[lo + 48:lo + 64] = 0       # the fourth device's rows are lost
    return crc, err, rep


@pytest.mark.parametrize("workload,fault,number", [
    ("replay.mixed-1chip", None, None),
    ("replay.mixed-1chip", _alter_one_answer, "crc_mismatch_in_sample"),
    ("replay.mixed-1chip", _leave_half_the_batch_out,
     "crc_mismatch_in_sample"),
    ("replay.mixed-4chip", _leave_one_chips_slice_out,
     "crc_mismatch_in_sample"),
])
def test_correct_comes_out_false_with_the_timed_path_broken(
        monkeypatch, capsys, workload, fault, number):
    """The harness's look for a chip skipped (`--rehearse`), the rest of a
    run driven with the program's entry broken underneath."""
    last = _rehearse_in_process(monkeypatch, capsys, workload, fault)
    assert last["correct"] is (fault is None)
    if fault is not None:
        assert last["compared"][number]["value"] > 0


def test_the_control_comes_out_not_correct(monkeypatch, capsys):
    last = _rehearse_in_process(monkeypatch, capsys, "replay.mixed-1chip",
                                control="drop-last-batch")
    assert last["correct"] is False
    hit = last["compared"]["crc_mismatch_in_sample"]
    assert hit["value"] == 40 and hit["limit"] == 0  # all of the sample


# -- the served cell, at a tiny size, with its answers broken ----------------


def _alter_twin_rows(monkeypatch):
    """An answer altered where it is handed over: every resident row's CRC
    as the host reports it, one bit off."""
    from cadence_tpu.rpc.cluster import Cluster

    real = Cluster.admin

    def admin(self, name, op, *args, **kw):
        doc = real(self, name, op, *args, **kw)
        if op == "admin_cluster" and args and args[0]:
            doc["resident_rows"] = {
                key: (crc ^ 1, branch, address)
                for key, (crc, branch, address) in doc["resident_rows"].items()}
        return doc

    monkeypatch.setattr(Cluster, "admin", admin)
    return "twin_crc_mismatch"


def _acknowledge_signals_never_sent(monkeypatch):
    """Half of the batch left out: every second signal is acknowledged to
    the generator without having been sent."""
    import loadgen

    real = loadgen.Sender._execute

    def execute(self, client, op):
        if op.kind == loadgen.OP_SIGNAL and op.index % 2:
            return None
        return real(self, client, op)

    monkeypatch.setattr(loadgen.Sender, "_execute", execute)
    return "acked_missing_from_history"


def _leave_the_twin_a_transaction_behind(monkeypatch):
    """A state returned unchanged: every resident row as it was one
    transaction ago, and staying there."""
    from cadence_tpu.rpc.cluster import Cluster

    real = Cluster.admin

    def admin(self, name, op, *args, **kw):
        doc = real(self, name, op, *args, **kw)
        if op == "admin_cluster" and args and args[0]:
            doc["resident_rows"] = {
                key: (crc, branch, (n_batches - 1, tail))
                for key, (crc, branch, (n_batches, tail))
                in doc["resident_rows"].items()}
        return doc

    monkeypatch.setattr(Cluster, "admin", admin)
    return "twin_rows_behind"


def _fail_every_ticket(monkeypatch):
    """A tier that gives up on every hand-off: each ticket the host counts
    as resolved ok is counted as resolved not-ok. One or two in a run are
    the tier's way out of a hand-off that overtook another; all of them
    are a tier that keeps no twin."""
    from cadence_tpu.rpc.cluster import Cluster

    real = Cluster.admin

    def admin(self, name, op, *args, **kw):
        doc = real(self, name, op, *args, **kw)
        if op == "admin_cluster":
            serving = doc["serving"]
            serving["tickets_failed"] += serving["tickets_ok"]
            serving["tickets_ok"] = 0
        return doc

    monkeypatch.setattr(Cluster, "admin", admin)
    return "tickets_failed"


@pytest.mark.parametrize("fault", [_alter_twin_rows,
                                   _acknowledge_signals_never_sent,
                                   _leave_the_twin_a_transaction_behind,
                                   _fail_every_ticket])
def test_served_cell_is_not_correct_with_its_answers_broken(
        monkeypatch, capsys, fault):
    """A tiny wire cluster on the CPU backend (8 pool workflows a domain,
    40 ops/s for 3 s): about 20 s a case."""
    import run

    number = fault(monkeypatch)
    assert run.main(["--workload", "serve.standard", "--seed", "4242",
                     "--seconds", "3", "--trace", "0", "--rehearse"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False
    assert last["compared"][number]["value"] > last["compared"][number]["limit"]
    assert last["attempted"] == 120
