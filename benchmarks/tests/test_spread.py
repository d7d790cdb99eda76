"""Tests of what PR 33 added: the arithmetic of a bound (`spread.py`), the
reader that puts the passes' spread in the ledger, and idle gaps named by
the program's spans.

    python -m pytest benchmarks/tests/test_spread.py -q
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "reader_under_test", os.path.join(BENCH, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- the arithmetic -----------------------------------------------------------


def test_spreads_on_hand_worked_runs():
    import spread

    runs = [100.0, 102.0, 98.0, 104.0, 96.0, 130.0]
    # statistics.quantiles(n=4) of six values: q1 = 97.5, q3 = 110.5
    assert spread.quartile_spread(runs) == pytest.approx(13.0 / 101.0)
    # the run farthest from the median (130) is left out
    assert spread.trimmed_range(runs) == pytest.approx(8.0 / 101.0)
    assert spread.trimmed_range([10.0, 11.0]) == pytest.approx(1.0 / 10.5)


@pytest.mark.parametrize("widest,bound", [
    (0.004, 0.05), (0.025, 0.05), (0.0251, 0.10), (0.071, 0.15),
    (0.10, 0.20), (0.11, 0.25), (0.125, 0.25), (0.55, 0.25)])
def test_the_bound_is_the_smallest_step_twice_the_widest_spread(widest, bound):
    import spread

    assert spread.bound_from([0.001, widest]) == bound


def test_block_standard_error_and_drift_on_planted_passes():
    import spread

    # three blocks of ten passes whose means are 0.9, 1.0, 1.1
    passes = [0.9] * 10 + [1.0] * 10 + [1.1] * 10
    assert spread.block_se(passes) == pytest.approx(
        (0.1 / 3 ** 0.5) / 1.0)
    assert spread.block_se([1.0] * 29) is None  # fewer than three blocks
    assert spread.drift([2.0] * 10 + [1.0] * 30) == pytest.approx(2.0)
    assert spread.drift([1.0] * 19) is None


def test_sets_are_read_from_a_directory_of_runs(tmp_path):
    import spread

    for n, (value, passes) in enumerate([(10.0, [1.0] * 40),
                                         (12.0, [0.8] * 40),
                                         (11.0, [0.9] * 40)]):
        line = {"correct": True, "metrics": {
            "replay_events_per_s": {"value": value, "unit": "events/s"},
            "setup_s": {"value": 17.0, "unit": "s"}},
            "device": {"memory_peak_bytes": 5}}
        (tmp_path / f"m1_S_{n}.out").write_text(json.dumps(line) + "\n")
        (tmp_path / f"m1_S_{n}.err").write_text(
            "a warning\n" + json.dumps({"driver": "replay", "max_events": 122})
            + "\n" + json.dumps({"driver": "replay", "passes": 40,
                                 "refits": 3, "pass_s": passes}) + "\n")
    (tmp_path / "notes.out").write_text("not a run\n")
    sets = spread.read_sets(str(tmp_path), "replay_events_per_s")
    assert list(sets) == ["m1_S"] and len(sets["m1_S"]) == 3
    assert sets["m1_S"][0]["max_events"] == 122
    assert sets["m1_S"][0]["refits"] == 3
    summary = spread.summarize(sets["m1_S"])
    assert summary["median"] == 11.0 and summary["n"] == 3
    assert summary["predicted_quartile_spread"] == 0.0
    assert summary["drift_median"] == 1.0


# -- BENCHMARK.json's bound is the one PERF.md derives ------------------------


def _sets_of_perf_md():
    """The rows of PERF.md 2's table of sets: (set, cell, trimmed range)."""
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    rows = re.findall(
        r"^\| `(D\d?[^`]*)` \| `(replay\.[^`]+)` \|.*\| ([0-9.]+) % \|$",
        text, flags=re.M)
    return [(name, cell, float(pct) / 100.0) for name, cell, pct in rows]


def test_the_bound_in_benchmark_json_is_the_one_perf_md_derives():
    import spread

    rows = _sets_of_perf_md()
    assert len(rows) >= 4, "PERF.md 2 lists the D sets the bound stands on"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    metric, = [m for m in bench["end_to_end"]
               if m["name"] == "replay_events_per_s"]
    assert {cell for _s, cell, _r in rows} <= set(metric["workloads"])
    assert metric["bound"] == spread.bound_from([r for _s, _c, r in rows])
    assert bench["run_seconds"] == 30


# -- the passes' spread, as a per-layer metric --------------------------------


def test_pass_spread_of_a_planted_list_and_none_for_a_served_cell():
    reader = _reader("replay.pass_spread_pct")
    walls = [0.30, 0.28, 0.36, 0.40, 0.32, 0.31, 0.29]
    calls = [{"wall_s": w, "traced": False} for w in walls]
    calls.insert(1, {"wall_s": 5.0, "traced": True})  # left out
    # quartiles of the seven untraced passes: 0.29 and 0.36; median 0.31
    assert reader.read({"kind": "replay", "calls": calls}) == pytest.approx(
        100.0 * 0.07 / 0.31)
    assert reader.read({"kind": "serve", "calls": calls}) is None
    assert reader.read({"kind": "replay", "calls": calls[:2]}) is None


# -- idle gaps under the program's names --------------------------------------


def _planted_trace():
    """Two host threads: a pack thread that recorded most, and the
    consumer, which launches. The device idles from 100 to 160 ms while
    the consumer waits for chunk 0, and from 400 to 410 ms between two
    calls, where no span of the consumer's covers it."""
    ms = 1e6
    pack = [("pack", 90 * ms, 170 * ms), ("pack.measure", 95 * ms, 120 * ms),
            ("Acquire semaphore", 99 * ms, 101 * ms),
            ("pack", 395 * ms, 405 * ms)] + \
        [("ParseArguments", t * ms, (t + 0.5) * ms) for t in range(200, 230)]
    consumer = [("feed.call", 80 * ms, 390 * ms),
                ("feed.first-chunk-wait", 85 * ms, 165 * ms),
                ("PjitFunction(replay_wirec_to_crc)", 166 * ms, 167 * ms),
                ("device-wait", 300 * ms, 380 * ms),
                ("feed.call", 420 * ms, 700 * ms),
                ("PjitFunction(replay_wirec_to_crc)", 500 * ms, 501 * ms)]
    return {"_host_lines": [("cadence-pack_0/77", pack),
                            ("python3", consumer)],
            "devices": [{"gaps": [(100 * ms, 0.060), (400 * ms, 0.010)]}],
            "ops": {"%fusion.1": 0.5}}


def test_a_gap_under_a_span_of_the_launching_thread_is_named_so():
    import trace_reduce as tr

    reduced = _planted_trace()
    assert tr.launching_lines(reduced) == [reduced["_host_lines"][1][1]]
    # the busiest thread was inside `pack.measure` / a runtime event then
    assert tr.host_at(reduced, 100e6) == "feed.first-chunk-wait"
    assert tr.host_at(reduced, 350e6) == "device-wait"
    assert tr.host_at(reduced, 200.2e6) == "feed.call"
    # between two calls the consumer is under no span of the program's:
    # the busiest thread's innermost event, or nothing
    assert tr.host_at(reduced, 400e6) == "pack"
    assert tr.host_at(reduced, 410e6) == "untraced"
    out = tr.breakdown(reduced)
    assert out["idle_gaps"] == [["feed.first-chunk-wait", 0.060],
                                ["pack", 0.010]]


def test_a_trace_with_no_launch_event_falls_back_to_the_busiest_thread():
    import trace_reduce as tr

    r = tr.reduce_trace(os.path.join(BENCH, "testdata",
                                     "small_v5e.xplane.pb"))
    # the recorded program has no span of this repo's: its gaps keep the
    # names they had (`test_reduction_of_a_trace_recorded_on_the_v5e`)
    assert tr.breakdown(r)["idle_gaps"][0][0] == "$time sleep"
    assert tr.launching_lines(r)  # the python thread made three calls


# -- every seed the same work -------------------------------------------------


def _rehearse_and_watch_the_feed(monkeypatch, capsys, workload, seed,
                                 traffic_edit=None):
    """A rehearsed run with the program's entry watched: the `max_events`
    the feeder was handed in every call."""
    import run

    seen = []
    real_load, real_find = run.load_module, run.find_cell

    def load(kind, name):
        module = real_load(kind, name)
        if kind == "drivers":
            real = module.feed

            def feed(blobs, max_events, chunk_workflows, mesh):
                seen.append(max_events)
                return real(blobs, max_events, chunk_workflows, mesh)

            monkeypatch.setattr(module, "feed", feed)
        return module

    def find_cell(bench, name):
        cell, config, traffic = real_find(bench, name)
        return cell, config, dict(traffic, **(traffic_edit or {}))

    monkeypatch.setattr(run, "load_module", load)
    monkeypatch.setattr(run, "find_cell", find_cell)
    assert run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                     "0.3", "--trace", "0", "--rehearse"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return last, seen


@pytest.mark.parametrize("workload,seed", [
    ("replay.mixed-1chip", 12345), ("replay.mixed-1chip", 2**31 + 33),
    ("replay.overflow-1chip", 12345), ("replay.mixed-4chip", 2**31 + 33)])
def test_every_seed_hands_the_feeder_the_traffic_files_shape(
        monkeypatch, capsys, workload, seed):
    """The feeder is handed the traffic file's `max_events`, whatever the
    seed's longest history: one shape, so one program, for every seed."""
    import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        _cell, _config, traffic = run.find_cell(json.load(f), workload)
    last, seen = _rehearse_and_watch_the_feed(monkeypatch, capsys, workload,
                                              seed)
    assert last["correct"] is True and len(seen) == last["attempted"] + 1
    assert set(seen) == {traffic["rehearse"]["max_events"]}
    assert traffic["max_events"] >= 119  # no suite's longest passes it: PERF.md 4


def test_a_history_over_the_ceiling_ends_the_run_in_set_up(monkeypatch,
                                                           capsys):
    with pytest.raises(SystemExit, match="max_events"):
        _rehearse_and_watch_the_feed(
            monkeypatch, capsys, "replay.mixed-1chip", 12345,
            traffic_edit={"rehearse": {"max_events": 20,
                                       "reference_sample_per_suite": 8,
                                       "slice_workflows": 48}})
