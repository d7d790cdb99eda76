#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data or a file of its own, found by
the names `BENCHMARK.json` gives: the cell's configuration
(`configs/<config>.json`, which names its driver, `drivers/<driver>.py`),
its traffic (`traffic/<traffic>.json`) and, in a `--trace 1` run, one
reader for each per-layer metric (`layer_metrics/<metric>.py`). A new cell,
configuration, mix, kind of deployment or per-layer metric is new files and
new entries; nothing here is edited.

A run: set-up (corpus or cluster, compile or cache load, warm-up of every
shape the window uses), the measured window of `--seconds`, the peak of the
device's memory, then the comparison with the plain reference that decides
`correct`. The last line of standard output is the result; everything else
goes to standard error, ending with each number compared beside its limit.

`--rehearse` runs the same control flow at the tiny sizes the files give
under "rehearse", on whatever JAX finds; its metrics carry the prefix
`rehearsal.` and are not measurements. `--control <name>` puts the
reference's control in the program's place: such a run must end with
`correct` false. Without `--rehearse` a device that is not a TPU, or fewer
chips than the cell asks for, ends the run with no result and a non-zero
exit code.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (os.path.join(HERE, "layer_metrics"), HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

from harness import say  # noqa: E402


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py`, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(bench: dict, workload: str):
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def metrics_of(bench: dict, group: str, workload: str, e2e_reported=None):
    """The metrics of `group` that this cell reports: those that list it
    under `workloads`, and those with no such key (for a per-layer metric
    with none: where the cell reports the end-to-end metric it moves)."""
    out = []
    for metric in bench[group]:
        cells = metric.get("workloads")
        if cells is not None:
            if workload in cells:
                out.append(metric)
        elif group == "end_to_end" or metric["moves"] in (e2e_reported or ()):
            out.append(metric)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--control", default="")
    opts = p.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = find_cell(bench, opts.workload)
    driver = load_module("drivers", config["driver"]).Driver(
        cell, config, traffic, opts)
    prefix = "rehearsal." if opts.rehearse else ""
    trace_dir = os.path.join(ROOT, ".bench_out", "trace", cell["name"]) \
        if opts.trace else None
    try:
        device = driver.setup()
        if not opts.rehearse and (device["platform"] != "tpu"
                                  or device["count"] < cell["chips"]):
            say(failure=f"needs {cell['chips']} TPU chip(s), found {device}")
            return 3
        setup_s = time.perf_counter() - T_START
        say(setup_s=setup_s)

        driver.run_window(opts.seconds, trace_dir)
        attempted, failed = driver.attempted_failed()
        e2e = dict(driver.end_to_end(), setup_s=setup_s)
        device["memory_peak_bytes"] = driver.memory_peak_bytes()

        metrics, breakdown = {}, None
        if opts.trace:
            import trace_reduce

            xplane = trace_reduce.find_xplane(trace_dir)
            reduced = trace_reduce.reduce_trace(xplane, opts.rehearse) \
                if xplane else None
            ctx = driver.context(device, reduced)
            if reduced and reduced["busy_s"]:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = ctx["traced_window_s"]
                breakdown = trace_reduce.breakdown(reduced)
            reported = [m["name"] for m in metrics_of(
                bench, "end_to_end", cell["name"])]
            for metric in metrics_of(bench, "per_layer", cell["name"],
                                     reported):
                value = load_module("layer_metrics", metric["name"]).read(ctx)
                if value is not None:  # nothing to read: left out
                    metrics[prefix + metric["name"]] = {
                        "value": float(value), "unit": metric["unit"]}
        else:
            for metric in metrics_of(bench, "end_to_end", cell["name"]):
                metrics[prefix + metric["name"]] = {
                    "value": float(e2e[metric["name"]]),
                    "unit": metric["unit"]}

        driver.release()
        compared = driver.check()
    finally:
        driver.close()

    correct = all(c.ok for c in compared)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if opts.rehearse:
        result["rehearsal"] = True
    if breakdown:
        result["breakdown"] = breakdown
    result["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in compared}
    for c in compared:
        print(f"compared {c.name}: {c.value} (limit {c.limit})"
              f"{'' if c.ok else '  <-- over its limit'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
