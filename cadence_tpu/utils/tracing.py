"""Tracing: the program's one span recorder.

Reference: the Go server wires opentracing through every handler
(common/rpc sets up jaeger; service handlers carry per-request tagged
loggers). Here the same observable contract is reduced to its core: a
span records (trace_id, span_id, parent_id, operation, start, duration,
tags); the tracer keeps a thread-local active-span stack so nested calls
parent naturally; finished spans land in an in-process ring with an
export seam (CADENCE_TPU_TRACE_EXPORT=<dir>: `Tracer.dump()` and process
exit append the spans not yet written to spans-<pid>.jsonl, so
multi-process traces stitch by trace_id).

Two rings. The program's own sites are always on (every RPC, store call,
flush, leg), some 2,000 spans a second on a host serving 40 ops/s. A trace
one of them roots is BACKGROUND and lands in a ring of its own; a trace
rooted by `Tracer.start_span` (a client, a test, an operator's script)
is KEPT, across processes too: the carrier says which, so a request sent
under a caller's span keeps its whole subtree in the kept ring however
much untraced traffic the hosts serve meanwhile.

One clock with the device trace: in a process that has already imported
`jax` every span is also a `jax.profiler.TraceAnnotation` for its life,
so with a profiler session live it is an event of the xplane's
`/host:CPU` plane on the thread that ran it, next to the device's own
lines. This module never imports jax itself: the launcher and the store
server stay off the device. `start_time` is `time.time_ns()`, the clock
the profiler stamps its events with; the duration is `perf_counter_ns()`.

The replay profiler's legs (utils/profiler.py) are spans too: a span
opened with `observe=(fn, scope, name)` calls `fn(scope, name, seconds)`
when it closes, which is how a leg still lands in its histogram and how
`traced()` records its latency from the span's own clock.

Wire propagation: `inject(request)` wraps a wire-frame request as
("traced", carrier, request) when a span is active; the serving side
`extract(request)`s the carrier back into a SpanContext and parents its
server span on it — a frontend→history→matching chain therefore yields
ONE trace whether the hops are in-process calls or real sockets.

Spans stand where work changes hands (per RPC, store call, transaction,
flush, chunk, call): never per event or inside a jitted function.
"""
from __future__ import annotations

import atexit
import functools
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

#: ids are <8 hex of this process><8 hex of a counter>: unique across the
#: processes of a cluster without a system call per span
_PROCESS = os.urandom(4).hex()
_SEQ = itertools.count(1)
_time_ns, _perf_counter_ns = time.time_ns, time.perf_counter_ns


def _reseed() -> None:
    global _PROCESS
    _PROCESS = os.urandom(4).hex()


os.register_at_fork(after_in_child=_reseed)


def _hex_id(seq: int) -> str:
    return "%s%08x" % (_PROCESS, seq)


#: jax.profiler.TraceAnnotation, once this process has imported jax
_annotation: Optional[type] = None


_modules = sys.modules


def _find_annotation() -> Optional[type]:
    global _annotation
    _annotation = getattr(_modules["jax.profiler"], "TraceAnnotation", None)
    return _annotation


@dataclass(frozen=True)
class SpanContext:
    """The propagated identity of a span (what crosses process edges)."""

    trace_id: str
    span_id: str
    #: the trace was rooted by one of the program's always-on sites
    background: bool = False

    def to_carrier(self) -> Dict[str, Any]:
        carrier = {"trace_id": self.trace_id, "span_id": self.span_id}
        if self.background:
            carrier["bg"] = 1
        return carrier

    @staticmethod
    def from_carrier(carrier: Any) -> Optional["SpanContext"]:
        """Tolerant decode of a wire carrier (untrusted shape: the wire is
        an internal transport, but a malformed envelope must not take the
        handler down)."""
        if not isinstance(carrier, dict):
            return None
        trace_id, span_id = carrier.get("trace_id"), carrier.get("span_id")
        if not trace_id or not span_id:
            return None
        return SpanContext(str(trace_id)[:64], str(span_id)[:64],
                           bool(carrier.get("bg")))


class Span:
    """One span, and the context manager that times it. Ids stay counter
    values until something reads them; a remote parent's are strings.
    `background` says which ring a trace lands in if this span roots it;
    under a parent, local or remote, the parent's ring is the span's."""

    __slots__ = ("operation", "_tags", "start_ns", "duration_ns", "finished",
                 "dumped", "_tracer", "_child_of", "_observe", "_seq",
                 "_trace", "_parent", "_t0", "_annotated", "_background")

    def __init__(self, tracer: "Tracer", operation: str,
                 child_of: Optional[SpanContext] = None,
                 tags: Optional[Dict[str, Any]] = None,
                 observe: Optional[tuple] = None,
                 background: bool = True) -> None:
        self._tracer = tracer
        self.operation = operation
        self._child_of = child_of
        self._tags: Optional[Dict[str, Any]] = dict(tags) if tags else None
        self._observe = observe
        self._background = background
        self.start_ns = 0
        self.duration_ns = 0
        self.finished = self.dumped = False
        self._annotated = None

    # -- the span's life ---------------------------------------------------

    def __enter__(self) -> "Span":
        try:
            stack = self._tracer._local.stack
        except AttributeError:
            stack = self._tracer._local.stack = []
        self._seq = next(_SEQ)
        remote = self._child_of
        if remote is not None:
            self._trace, self._parent = remote.trace_id, remote.span_id
            self._background = remote.background
        elif stack:
            parent = stack[-1]
            self._trace, self._parent = parent._trace, parent._seq
            self._background = parent._background
        else:
            self._trace, self._parent = self._seq, None
        stack.append(self)
        annotation = _annotation
        if annotation is None and "jax.profiler" in _modules:
            annotation = _find_annotation()
        if annotation is not None:
            self._annotated = annotated = annotation(self.operation)
            annotated.__enter__()
        self.start_ns = _time_ns()
        self._t0 = _perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.duration_ns = _perf_counter_ns() - self._t0
        if self._annotated is not None:
            self._annotated.__exit__(exc_type, exc, tb)
            self._annotated = None
        tracer = self._tracer
        tracer._local.stack.pop()
        if exc_type is not None:
            self.tags["error"] = exc_type.__name__
        self.finished = True
        # atomic; a ring drops its oldest
        (tracer._background if self._background
         else tracer._finished).append(self)
        if self._observe is not None:
            fn, scope, name = self._observe
            fn(scope, name, self.duration_ns / 1e9)
        return False

    # -- reads -------------------------------------------------------------

    @property
    def trace_id(self) -> str:
        trace = self._trace
        return trace if isinstance(trace, str) else _hex_id(trace)

    @property
    def span_id(self) -> str:
        return _hex_id(self._seq)

    @property
    def parent_id(self) -> Optional[str]:
        parent = self._parent
        return _hex_id(parent) if isinstance(parent, int) else parent

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id, self._background)

    @property
    def start_time(self) -> float:
        """Wall clock, seconds since epoch."""
        return self.start_ns / 1e9

    @property
    def duration_s(self) -> float:
        return self.duration_ns / 1e9

    @property
    def tags(self) -> Dict[str, Any]:
        """Made when first asked for: most spans carry none."""
        if self._tags is None:
            self._tags = {}
        return self._tags

    def set_tag(self, key: str, value: Any) -> None:
        self.tags[key] = value

    def to_dict(self) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "operation": self.operation,
            "start_time": round(self.start_time, 6),
            "duration_s": round(self.duration_s, 6),
            "tags": self.tags,
            "pid": os.getpid(),
        }


class Tracer:
    """Span factory + in-process collector (thread-safe; the active-span
    stack is thread-local, so concurrent requests never cross-parent)."""

    def __init__(self, max_spans: int = 10_000) -> None:
        #: ring buffers: a long-running host keeps the NEWEST spans, so
        #: /traces stays useful after the cap fills (oldest evicted); the
        #: kept traces, and the ones the program's own sites root
        self._finished: deque = deque(maxlen=max_spans)
        self._background: deque = deque(maxlen=max_spans)
        self._dump_lock = threading.Lock()
        self.max_spans = max_spans
        self._local = threading.local()
        #: export seam: dump() appends the spans collected since the last
        #: dump to <dir>/spans-<pid>.jsonl; also run at process exit
        self.export_dir = os.environ.get("CADENCE_TPU_TRACE_EXPORT") or None
        if self.export_dir:
            atexit.register(self.dump)

    # -- active-span bookkeeping (per thread) ------------------------------

    def active_context(self) -> Optional[SpanContext]:
        stack = getattr(self._local, "stack", None)
        return stack[-1].context if stack else None

    # -- span lifecycle ----------------------------------------------------

    def start_span(self, operation: str,
                   child_of: Optional[SpanContext] = None,
                   tags: Optional[Dict[str, Any]] = None,
                   observe: Optional[tuple] = None,
                   background: bool = False) -> Span:
        """Open a span (use as `with tracer.start_span(...) as span`):
        explicit `child_of` (an extracted remote context) wins; otherwise
        the thread's active span is the parent; otherwise this span roots
        a new trace, a kept one unless `background`. `observe=(fn, scope,
        name)`: the span's seconds go to `fn(scope, name, seconds)` when
        it closes."""
        return Span(self, operation, child_of, tags, observe, background)

    # -- reads -------------------------------------------------------------

    def finished_spans(self) -> List[Span]:
        """Both rings' spans, in the order they ended."""
        while True:
            try:
                kept, own = list(self._finished), list(self._background)
                break
            except RuntimeError:   # appended to while it was copied
                continue
        if kept and own:
            return sorted(kept + own,
                          key=lambda s: s.start_ns + s.duration_ns)
        return kept or own

    def traces(self) -> Dict[str, List[Span]]:
        """Finished spans grouped by trace_id, each trace start-ordered."""
        out: Dict[str, List[Span]] = {}
        for span in self.finished_spans():
            out.setdefault(span.trace_id, []).append(span)
        for spans in out.values():
            spans.sort(key=lambda s: s.start_ns)
        return out

    def dump(self, directory: Optional[str] = None) -> Optional[str]:
        """Append the spans no dump has written yet to
        <directory>/spans-<pid>.jsonl (default: CADENCE_TPU_TRACE_EXPORT);
        returns the file, or None where no directory is configured. What
        a ring dropped between two dumps is lost."""
        directory = directory or self.export_dir
        if not directory:
            return None
        with self._dump_lock:
            fresh = [s for s in self.finished_spans() if not s.dumped]
            os.makedirs(directory, exist_ok=True)
            path = os.path.join(directory, f"spans-{os.getpid()}.jsonl")
            with open(path, "a", encoding="utf-8") as fh:
                for span in fresh:
                    fh.write(json.dumps(span.to_dict(), default=str) + "\n")
                    span.dumped = True
        return path

    def reset(self) -> None:
        self._finished.clear()
        self._background.clear()


# -- wire-envelope propagation ----------------------------------------------

def inject(request: Any, tracer: Optional["Tracer"] = None) -> Any:
    """Wrap a wire request with the calling thread's active trace context:
    ("traced", carrier, request). Pass-through when no span is active, so
    untraced traffic keeps the bare envelope."""
    ctx = (tracer or DEFAULT_TRACER).active_context()
    if ctx is None:
        return request
    return ("traced", ctx.to_carrier(), request)


def extract(request: Any) -> Tuple[Optional[SpanContext], Any]:
    """Unwrap a possibly-traced wire request → (context or None, inner)."""
    if (isinstance(request, tuple) and len(request) == 3
            and request[0] == "traced"):
        return SpanContext.from_carrier(request[1]), request[2]
    return None, request


def traced(operation: str):
    """Method decorator: span + latency histogram around a service method.

    The span parents on the thread's active span (or an extracted remote
    context activated by the RPC handler); when the instance carries a
    `metrics` registry, the span's own duration is recorded under
    scope=`operation` — one name shared by the trace and the metric, the
    reference's scope-per-API convention (metrics/defs.go)."""
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            tracer = getattr(self, "tracer", None) or DEFAULT_TRACER
            registry = getattr(self, "metrics", None)
            with Span(tracer, operation, observe=None if registry is None
                      else (registry.record, operation, "latency")):
                return fn(self, *args, **kwargs)
        return wrapper
    return decorate


def spanned(operation: str):
    """Function decorator: every call is a span on the default tracer."""
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with Span(DEFAULT_TRACER, operation):
                return fn(*args, **kwargs)
        return wrapper
    return decorate


def span(operation: str) -> Span:
    """A span on the default tracer: `with tracing.span("history.commit")`."""
    return Span(DEFAULT_TRACER, operation)


#: fallback tracer for components constructed without explicit wiring
#: (mirrors metrics.DEFAULT_REGISTRY; tests reset it per test)
DEFAULT_TRACER = Tracer()
