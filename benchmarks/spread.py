#!/usr/bin/env python3
"""The arithmetic behind a bound: how far the runs of one cell spread, and
where the spread comes from.

A set is some runs of one cell kept as `<set>_<n>.out` (the result line
last) and `<set>_<n>.err` (the drivers' record: one JSON object a line).
`python3 benchmarks/spread.py <dir> [metric]` prints one line a set and one
a run; PERF.md 2 says which sets the bound of `replay_events_per_s` was
taken from, and `bound_from` is the rule.

- `quartile_spread`: the distance between the first and third quartile
  (`statistics.quantiles(values, n=4)`) over the median: PERF.md's and the
  contract's spread.
- `trimmed_range`: the range over the median with the run farthest from
  the median left out: the check's, by its reason lines (ledger, PR 32).
- `block_se`: the standard error of a run's mean pass from the means of
  blocks of 10 passes (slow spells stay together), as a share of the mean:
  what the run means WOULD spread by if passes were all that varied.
- `drift`: the median of a run's first 10 passes over the median of the
  rest.
"""
from __future__ import annotations

import glob
import json
import math
import os
import statistics
import sys
from typing import Dict, List, Optional, Sequence

#: the bounds a metric may be given, and how many times the widest spread
#: read the bound has to be (ISSUE 33, step 3; the check refuses a bound as
#: too tight where its own runs spread by more than half of it)
BOUND_STEPS = (0.05, 0.10, 0.15, 0.20, 0.25)
BOUND_OVER_SPREAD = 2.0
#: a quartile distance as a share of a normal's standard deviation
IQR_OF_SIGMA = 1.349


def quartile_spread(values: Sequence[float]) -> float:
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_range(values: Sequence[float]) -> float:
    mid = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - mid))[:-1] \
        if len(values) > 2 else list(values)
    return (max(kept) - min(kept)) / mid


def bound_from(spreads: Sequence[float]) -> float:
    """The smallest step that is at least `BOUND_OVER_SPREAD` times the
    widest spread; the last step (the contract's cap) if none is."""
    need = BOUND_OVER_SPREAD * max(spreads)
    return next((b for b in BOUND_STEPS if b >= need), BOUND_STEPS[-1])


def block_se(pass_s: Sequence[float], block: int = 10) -> Optional[float]:
    """Standard error of the mean pass over the mean, from block means;
    None with fewer than three whole blocks."""
    means = [statistics.fmean(pass_s[i:i + block])
             for i in range(0, len(pass_s) - block + 1, block)]
    if len(means) < 3:
        return None
    return (statistics.stdev(means) / math.sqrt(len(means))
            / statistics.fmean(pass_s))


def drift(pass_s: Sequence[float], first: int = 10) -> Optional[float]:
    if len(pass_s) < 2 * first:
        return None
    return (statistics.median(pass_s[:first])
            / statistics.median(pass_s[first:]))


def read_run(out_path: str, metric: str) -> Optional[dict]:
    """One run: its metric, and what the drivers said of it."""
    try:
        with open(out_path) as f:
            result = json.loads(f.read().strip().splitlines()[-1])
        value = result["metrics"][metric]["value"]
    except (OSError, IndexError, KeyError, ValueError):
        return None
    run = {"value": value, "correct": result["correct"],
           "memory_peak_bytes": result["device"].get("memory_peak_bytes"),
           "setup_s": result["metrics"].get("setup_s", {}).get("value")}
    err_path = out_path[:-4] + ".err"
    if os.path.isfile(err_path):
        with open(err_path) as f:
            for line in f:
                if not line.startswith("{"):
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                for key in ("pass_s", "refits", "passes", "max_events",
                            "events", "flagged", "ladder_lanes",
                            "ladder_rows", "warm_pass_s", "slowest",
                            "legs_s"):
                    if key in rec and (key != "refits" or "pass_s" in rec):
                        run[key] = rec[key]
    return run


def summarize(runs: List[dict]) -> dict:
    values = [r["value"] for r in runs]
    out = {"n": len(values), "median": statistics.median(values)}
    if len(values) > 1:
        out["quartile_spread"] = quartile_spread(values)
        out["trimmed_range"] = trimmed_range(values)
    ses = [s for s in (block_se(r["pass_s"]) for r in runs if "pass_s" in r)
           if s is not None]
    if ses:
        # were the passes all that varied, the run means would be normal
        # about one mean with this standard error
        out["predicted_quartile_spread"] = IQR_OF_SIGMA * statistics.median(ses)
    drifts = [d for d in (drift(r["pass_s"]) for r in runs if "pass_s" in r)
              if d is not None]
    if drifts:
        out["drift_median"] = statistics.median(drifts)
        out["drift_range"] = [min(drifts), max(drifts)]
    return out


def read_sets(directory: str, metric: str) -> Dict[str, List[dict]]:
    sets: Dict[str, List[dict]] = {}
    for path in sorted(glob.glob(os.path.join(directory, "*.out"))):
        tag = os.path.basename(path)[:-4]
        name, _, n = tag.rpartition("_")
        if not name or not n.isdigit():
            continue
        run = read_run(path, metric)
        if run is not None:
            run["tag"] = tag
            sets.setdefault(name, []).append(run)
    return sets


def main(argv) -> int:
    metric = argv[2] if len(argv) > 2 else "replay_events_per_s"
    for name, runs in read_sets(argv[1], metric).items():
        print(name, json.dumps(summarize(runs)))
        for r in runs:
            p = r.get("pass_s") or []
            q = [round(x, 4) for x in statistics.quantiles(p, n=4)] \
                if len(p) > 1 else None
            print("   ", r["tag"], r["value"], "correct", r["correct"],
                  "passes", len(p), "pass_q", q,
                  "pass_min_max", [min(p), max(p)] if p else None,
                  "refits", r.get("refits"), "max_events",
                  r.get("max_events"), "peak", r["memory_peak_bytes"],
                  "block_se", block_se(p) if p else None,
                  "drift", drift(p) if p else None,
                  "flagged", (r.get("flagged") or [None])[0],
                  "lanes/rows", r.get("ladder_lanes"), r.get("ladder_rows"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
