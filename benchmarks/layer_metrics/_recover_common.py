"""Shared by the recovery readers: the spans of the traced recovery as the
trace holds them (every span of `cadence_tpu/utils/tracing.py` is a
`jax.profiler.TraceAnnotation`: an event of the host plane on the thread
that ran it, by its bare name), and the device's busy time.

`recover.call` is the whole of `recover_stores`; its legs `recover.*`, the
rebuilder's `rebuild.*` and the verify's `verify.*` are spans on the same
thread, so containment on that thread's line is the call tree. The two
passes' packs (`rebuild.encode`, `verify.pack`) run on the executor's pack
threads: roots of other lines. `_spans.py` does not know these names and is
not asked: this file matches them itself. A program from before the spans
gives every span reader nothing to read.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from _spans import Node

PREFIXES = ("recover.", "rebuild.", "verify.")
CALL = "recover.call"
#: the legs that lie side by side under the call
TOP_LEGS = ("recover.log-replay", "recover.rebuild", "recover.verify",
            "recover.reconcile")


def passes(ctx: dict) -> list:
    return ctx["passes"] if ctx.get("kind") == "recover" else []


def forest(ctx: dict) -> List[Node]:
    """The containment forest of every host thread's recovery spans."""
    trace = ctx.get("trace") if passes(ctx) else None
    roots: List[Node] = []
    for _line, events in (trace or {}).get("_host_lines") or ():
        stack: List[Node] = []
        spans = [e for e in events if e[0].startswith(PREFIXES)]
        for name, lo, hi in sorted(spans, key=lambda e: (e[1], -e[2])):
            while stack and stack[-1].hi <= lo:
                stack.pop()
            node = Node(name, lo, hi)
            (stack[-1].children if stack else roots).append(node)
            stack.append(node)
    return roots


def seconds_of(ctx: dict, *names: str) -> float:
    """Summed seconds of the spans of these names, on whatever thread."""
    return sum(n.seconds for root in forest(ctx) for n in root.walk()
               if n.name in names)


def share_pct(ctx: dict, *names: str) -> Optional[float]:
    """100 x the spans of these names over `recover.call`; None where the
    trace holds no `recover.call` or none of the spans."""
    call_s, part_s = seconds_of(ctx, CALL), seconds_of(ctx, *names)
    if not call_s or not part_s:
        return None
    return 100.0 * part_s / call_s


def call_breakdown(ctx: dict) -> Optional[Dict[str, float]]:
    """Seconds of every recovery span of the traced pass by name, the
    top-level legs' sum over the call, and the call's seconds in no leg."""
    calls = [n for root in forest(ctx) for n in root.walk()
             if n.name == CALL]
    if not calls:
        return None
    out: Dict[str, float] = {}
    for root in forest(ctx):
        for n in root.walk():
            out[n.name] = out.get(n.name, 0.0) + n.seconds
    legs = sum(out.get(name, 0.0) for name in TOP_LEGS)
    out["top_legs_over_call"] = legs / out[CALL]
    out["call_in_no_leg_s"] = sum(c.self_seconds for c in calls)
    return out


def module_seconds(ctx: dict) -> Optional[Dict[str, list]]:
    """[device seconds, runs] of each compiled program of the traced pass,
    longest first; None in a rehearsal (no chip's plane)."""
    trace = ctx.get("trace")
    if not trace or ctx.get("rehearse") or not passes(ctx):
        return None
    ranked = sorted(trace["modules"].items(),
                    key=lambda kv: -kv[1]["seconds"])
    return {name[:60]: [entry["seconds"], entry["runs"]]
            for name, entry in ranked[:12]}


def device_busy_s(ctx: dict) -> Optional[float]:
    """Busy seconds of the chip in the traced recovery: the union of its
    `XLA Ops` intervals (`trace_reduce.reduce_trace`); None where the trace
    holds none, and in a rehearsal (no chip's plane)."""
    trace = ctx.get("trace")
    if not trace or ctx.get("rehearse") or not passes(ctx):
        return None
    return trace.get("busy_s") or None


def traced_events(ctx: dict) -> float:
    return float(sum(p["events"] for p in passes(ctx) if p.get("traced")))
