"""Shared by the span readers: the program's own spans as the trace holds
them. Every span of `cadence_tpu/utils/tracing.py` is a
`jax.profiler.TraceAnnotation`, so it is an event of the host plane on the
thread that ran it, by its bare operation name; `ctx["trace"]["_host_lines"]`
holds each host thread's events as (name, start_ns, end_ns).

Per thread the spans nest, so containment on one line is the call tree. On
a one-host cluster every hop of a served op but the store's own side runs
on the op's dispatch thread: the tree under an `rpc.frontend` span is the
op. Self time is a span's duration less what its child spans cover (what
`trace_reduce.self_seconds` does for a device line).
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional

from harness import percentile

#: what the program's spans are named after: layer, dot, operation; the
#: bulk path's legs keep their histograms' bare names
SPAN_PREFIXES = ("rpc.", "frontend.", "history.", "matching.", "store.",
                 "serving.", "resident.", "feed.", "pack.")
LEG_SPANS = ("pack", "pack-queue-wait", "h2d", "device-wait", "readback",
             "fallback")
#: the frontend spans of the kinds the serve traffic lists as measured_ops
#: (start, cron-start and retry-start are all one frontend call)
MEASURED_FRONTEND_SPANS = (
    "frontend.start-workflow-execution",
    "frontend.signal-workflow-execution",
    "frontend.signal-with-start-workflow-execution")


def is_span(name: str) -> bool:
    """One of the program's spans, not an event of the profiler's own (a
    Python call reads `file.py:12 name`, a runtime event `Name(...)`)."""
    if " " in name or ":" in name or "(" in name:
        return False
    return name.startswith(SPAN_PREFIXES) or name in LEG_SPANS


class Node:
    __slots__ = ("name", "lo", "hi", "children")

    def __init__(self, name: str, lo: float, hi: float) -> None:
        self.name, self.lo, self.hi, self.children = name, lo, hi, []

    @property
    def seconds(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def self_seconds(self) -> float:
        return max(0.0, self.seconds - sum(c.seconds for c in self.children))

    def walk(self) -> Iterator["Node"]:
        yield self
        for child in self.children:
            yield from child.walk()


def trees(events) -> List[Node]:
    """The containment forest of one thread's spans; `events` are
    (name, start_ns, end_ns), of which only the program's spans count."""
    roots: List[Node] = []
    stack: List[Node] = []
    spans = [e for e in events if is_span(e[0])]
    for name, lo, hi in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1].hi <= lo:
            stack.pop()
        node = Node(name, lo, hi)
        (stack[-1].children if stack else roots).append(node)
        stack.append(node)
    return roots


def forest(ctx: dict) -> List[Node]:
    """The roots of every host thread's span tree; empty where the run was
    not traced or the program put no span on the timeline."""
    trace = ctx.get("trace")
    if not trace:
        return []
    return [root for _line, events in trace.get("_host_lines") or ()
            for root in trees(events)]


def spans_named(ctx: dict, name: str) -> List[Node]:
    return [n for root in forest(ctx) for n in root.walk() if n.name == name]


def measured_ops(ctx: dict) -> List[Node]:
    """The `rpc.frontend` spans of the traced window whose frontend call
    is one of the measured kinds."""
    if ctx.get("kind") != "serve":
        return []
    return [op for op in spans_named(ctx, "rpc.frontend")
            if any(c.name in MEASURED_FRONTEND_SPANS for c in op.children)]


def self_of(op: Node, prefix: str, but: str = "") -> float:
    """Summed self seconds of the op's spans under `prefix`."""
    return sum(n.self_seconds for n in op.walk()
               if n.name.startswith(prefix) and n.name != but)


def total_of(node: Node, prefix: str) -> float:
    """Summed seconds of the outermost spans under `prefix` inside `node`."""
    def outermost(at: Node) -> Iterator[Node]:
        for child in at.children:
            if child.name.startswith(prefix):
                yield child
            else:
                yield from outermost(child)
    return sum(n.seconds for n in outermost(node))


def op_parts(op: Node) -> Dict[str, float]:
    """The parts of one op that the readers report, in seconds: they add
    up to the root span but for `unexplained`, the spans of other layers
    under it. A span the program lacks is NOT there: its time is self
    time of the span around it (`largest_self` is the check for that)."""
    parts = {
        "rpc": self_of(op, "rpc."),
        "frontend": self_of(op, "frontend."),
        "history": self_of(op, "history.", but="history.lock-wait"),
        "lock_wait": total_of(op, "history.lock-wait"),
        "store": total_of(op, "store."),
    }
    parts["unexplained"] = op.seconds - sum(parts.values())
    parts["store_trips"] = sum(1 for n in op.walk()
                               if n.name.startswith("store."))
    return parts


def largest_self(op: Node) -> Node:
    """The span of the op with the most self time, the round trips and the
    lock wait apart (waits on another process or thread by design). A
    boundary without a span shows here: what crosses it is self time of
    the span around it."""
    return max((n for n in op.walk() if not n.name.startswith("store.")
                and n.name != "history.lock-wait"),
               key=lambda n: n.self_seconds)


def part(ctx: dict, key: str) -> List[float]:
    """`op_parts(op)[key]` of every measured op of the traced window."""
    return [op_parts(op)[key] for op in measured_ops(ctx)]


def p50_ms(seconds: List[float]) -> Optional[float]:
    return percentile(seconds, 50) * 1e3 if seconds else None


def mean(values: List[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None
