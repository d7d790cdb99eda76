"""From a `jax.profiler` trace (`.xplane.pb`) to the numbers the metrics read.

What a TPU's trace holds (seen on the v5e, PR 24): one plane for each chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event for every operation
that ran on the chip (an operation with a body, such as a `while`, spans
the events of its body on the same line), and whose line `XLA Modules` has
one event for every run of a compiled program. Host threads are lines of
the plane `/host:CPU`.

`reduce_trace(path)` gives, for the traced window:

- `devices`: for each chip its busy seconds (the union of its operations'
  intervals), the first and last nanosecond at which one ran, and the
  longest gaps between operations;
- `busy_s`: busy seconds averaged over the chips;
- `ops`: device seconds per operation name, self time (an operation's time
  less that of the operations nested in it), summed over the chips;
- `modules`: device seconds and runs per compiled program, summed likewise;
- `compilations`: host events that are a compilation (`backend_compile`,
  `XlaCompile`), which a steady window must not have;
- `host_at(ns)`: what the host was doing at a time: the program's span on
  the thread that launches, else the busiest traced thread's event.

`python benchmarks/trace_reduce.py <file>` prints a summary to look at.
"""
from __future__ import annotations

import glob
import os
import sys
from typing import Dict, List, Optional, Tuple

_READERS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "layer_metrics")
if _READERS not in sys.path:
    sys.path.insert(0, _READERS)

import _spans  # noqa: E402  (what a program span is: the readers' own rule)

DEVICE_PLANE = "/device:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: XLA's CPU client runs its operations on host threads of these names: a
#: rehearsal reads them where a chip's plane would be, so the code that
#: follows runs; nothing read this way is a device number
CPU_CLIENT_LINES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen", "tf_XLATfrtCpuClient")
COMPILE_EVENTS = ("backend_compile", "XlaCompile", "xla_compile")
#: the runtime's own event around every call of a jitted function
LAUNCH_EVENT = "PjitFunction("

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def union_seconds(intervals: List[Interval]) -> float:
    """Seconds covered by at least one of the (start_ns, end_ns) intervals."""
    total, end = 0.0, None
    for lo, hi in sorted(intervals):
        if end is None or lo > end:
            total += hi - lo
            end = hi
        elif hi > end:
            total += hi - end
            end = hi
    return total / 1e9


def gaps(intervals: List[Interval], longest: int = 10) -> List[Interval]:
    """The longest stretches, (start_ns, seconds), covered by no interval."""
    out, end = [], None
    for lo, hi in sorted(intervals):
        if end is not None and lo > end:
            out.append((end, (lo - end) / 1e9))
        end = hi if end is None else max(end, hi)
    return sorted(out, key=lambda g: -g[1])[:longest]


def self_seconds(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Per name, the seconds of its events less those of the events nested
    inside them. `events` are (name, start_ns, end_ns) of one line."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end_ns, nanoseconds of its children]

    def close(entry) -> None:
        name, _end, child_ns, dur = entry
        out[name] = out.get(name, 0.0) + max(0.0, dur - child_ns) / 1e9

    for name, lo, hi in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= lo:
            close(stack.pop())
        if stack:
            stack[-1][2] += hi - lo
        stack.append([name, hi, 0.0, hi - lo])
    while stack:
        close(stack.pop())
    return out


def _line_events(line) -> List[Tuple[str, float, float]]:
    # a device operation's name is its whole HLO line: keep what stands
    # before " = ", the operation's own name
    return [(e.name.split(" = ", 1)[0], float(e.start_ns),
             float(e.start_ns + e.duration_ns))
            for e in line.events if e.duration_ns > 0]


def reduce_trace(path: str, rehearse: bool = False) -> dict:
    import warnings

    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = ProfileData.from_file(path)
        devices, ops, modules = [], {}, {}
        host_lines, compilations = [], 0
        for plane in data.planes:
            is_device = plane.name.startswith(DEVICE_PLANE)
            for line in plane.lines:
                events = _line_events(line)
                if not events:
                    continue
                as_device = is_device and line.name == OPS_LINE
                if plane.name == HOST_PLANE:
                    compilations += sum(1 for name, _lo, _hi in events if any(
                        key in name for key in COMPILE_EVENTS))
                    if rehearse and line.name.startswith(CPU_CLIENT_LINES):
                        events = [e for e in events
                                  if not e[0].startswith("ThreadpoolListener")]
                        as_device = bool(events)
                    else:
                        host_lines.append((line.name, events))
                if as_device:
                    spans = [(lo, hi) for _n, lo, hi in events]
                    devices.append({
                        "name": f"{plane.name}/{line.name}" if rehearse
                        else plane.name,
                        "busy_s": union_seconds(spans),
                        "first_ns": min(lo for lo, _hi in spans),
                        "last_ns": max(hi for _lo, hi in spans),
                        "gaps": gaps(spans),
                    })
                    for name, secs in self_seconds(events).items():
                        ops[name] = ops.get(name, 0.0) + secs
                elif is_device and line.name == MODULES_LINE:
                    for name, lo, hi in events:
                        entry = modules.setdefault(name, [0.0, 0])
                        entry[0] += (hi - lo) / 1e9
                        entry[1] += 1
    # the Python threads first (what the program was doing), then the
    # runtime's own, each group by how much it recorded
    host_lines.sort(key=lambda item: (
        not item[0].startswith(("python", "main")), -len(item[1])))
    return {
        "devices": devices,
        "busy_s": (sum(d["busy_s"] for d in devices) / len(devices)
                   if devices else None),
        "ops": ops,
        "modules": {name: {"seconds": s, "runs": n}
                    for name, (s, n) in modules.items()},
        "compilations": compilations,
        "_host_lines": host_lines,
    }


def launching_lines(reduced: dict) -> List[list]:
    """The host threads that launch programs, the one that launches most
    first: a jitted call is a `PjitFunction(<name>)` event of the thread
    that makes it, whatever the Python tracer's level."""
    launches = [(sum(1 for name, _lo, _hi in events
                     if name.startswith(LAUNCH_EVENT)), events)
                for _line, events in reduced["_host_lines"]]
    return [events for n, events in sorted(
        launches, key=lambda item: -item[0]) if n]


def host_at(reduced: dict, at_ns: float) -> str:
    """What the host was doing at `at_ns`: the innermost of the PROGRAM's
    spans that covers it on a thread that launches (the thread whose
    waiting is the device's idling), else the innermost traced host event
    that covers it on the thread with most events that has one;
    "untraced" where none does."""
    for events in launching_lines(reduced):
        covering = [(hi - lo, name) for name, lo, hi in events
                    if lo <= at_ns < hi and _spans.is_span(name)]
        if covering:
            return min(covering)[1]
    for _line, events in reduced["_host_lines"]:
        covering = [(hi - lo, name) for name, lo, hi in events
                    if lo <= at_ns < hi]
        if covering:
            return min(covering)[1]
    return "untraced"


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The `breakdown` of a result line: the operations that took most
    device time, and the longest idle gaps of the busiest-gapped chip by
    what the host was doing when each began."""
    device_ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top]
    idle = []
    if reduced["devices"]:
        worst = max(reduced["devices"],
                    key=lambda d: sum(g[1] for g in d["gaps"]))
        idle = [[host_at(reduced, at_ns)[:80], secs]
                for at_ns, secs in worst["gaps"][:top]]
    return {"device_ops": [[name[:80], secs] for name, secs in device_ops],
            "idle_gaps": idle}


def main(argv) -> int:
    reduced = reduce_trace(argv[1], rehearse="--rehearse" in argv)
    for d in reduced["devices"]:
        print(d["name"], "busy_s", d["busy_s"], "span_s",
              (d["last_ns"] - d["first_ns"]) / 1e9, "gaps", d["gaps"][:3])
    print("modules", reduced["modules"])
    print("compilations", reduced["compilations"])
    print(breakdown(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
