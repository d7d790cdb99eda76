"""Replay profiler: per-kernel-launch leg timing into metric histograms.

BENCH numbers report one end-to-end rate; regressions can't be localized
without decomposing a launch into its host legs. Every instrumented
replay path (engine/tpu_engine.py, engine/rebuild.py, native/feeder.py,
ops/replay.replay_corpus) wraps its phases in a ReplayProfiler:

  pack            — host encode/pack of the event corpus
  pack-queue-wait — device consumer stalled waiting on the pack producer
                    pipeline (engine/executor.py): this leg growing means
                    host packing is starving the device; near-zero means
                    the device side is the bottleneck
  h2d             — host→device transfer dispatch (+ bytes, M_H2D_BYTES)
  kernel          — host time blocked on the device, to block_until_ready
                    (on the timeline this leg's span is `device-wait`)
  readback        — device→host pull of payload rows / CRCs / errors
  fallback        — capacity-escalation ladder (engine/ladder.py): gather
                    + widened-K re-replay of overflow-flagged rows; the
                    batched replacement for the per-workflow oracle leg
  serving         — micro-batched transaction flush (engine/serving.py):
                    one drain cycle of the device-serving tier — suffix
                    from-state launches plus cold full-replay admits

Legs land as histograms under the component's scope (SCOPE_TPU_REPLAY by
default, SCOPE_REBUILD for the rebuilder), so `/metrics` scrapes, the
admin snapshot and the benchmark's traced runs can all read the legs.

A leg is a span of the program's one recorder (utils/tracing.py), which
keeps the time: the span's close observes the leg's histogram, and with a
profiler session live the leg is an event on the device trace's timeline.
"""
from __future__ import annotations

from typing import Dict, Optional

from . import metrics as m
from . import tracing

#: the leg metric names, in pipeline order
LEGS = (m.M_PROFILE_PACK, m.M_PROFILE_PACK_WAIT, m.M_PROFILE_H2D,
        m.M_PROFILE_KERNEL, m.M_PROFILE_READBACK, m.M_PROFILE_FALLBACK,
        m.M_PROFILE_SERVING)


#: a leg whose timeline name differs from its histogram's: `kernel` is host
#: time blocked on the device, not device time, and reads so on a timeline
SPAN_NAMES = {m.M_PROFILE_KERNEL: "device-wait"}


class ReplayProfiler:
    """Cheap handle over a registry: construct per launch site, record
    legs; summary() aggregates whatever the registry has accumulated."""

    def __init__(self, registry: Optional[m.MetricsRegistry] = None,
                 scope: str = m.SCOPE_TPU_REPLAY) -> None:
        self.registry = registry if registry is not None else m.DEFAULT_REGISTRY
        self.scope = scope

    def leg(self, name: str, span: Optional[str] = None) -> tracing.Span:
        """`with prof.leg(name) as leg:` — a span named `span` (default:
        the leg's name) whose seconds land in the histogram `name`;
        `leg.duration_s` holds them once the block has ended."""
        return tracing.Span(
            tracing.DEFAULT_TRACER, span or SPAN_NAMES.get(name, name),
            observe=(self.registry.observe, self.scope, name))

    def h2d(self, nbytes: int) -> None:
        """One host→device transfer of `nbytes`."""
        self.registry.inc(self.scope, m.M_H2D_BYTES, int(nbytes))

    def summary(self) -> Dict[str, object]:
        """Leg breakdown for reports (the bench JSON / `admin profile`)."""
        out: Dict[str, object] = {
            "scope": self.scope,
            "kernel_launches": self.registry.counter(
                self.scope, m.M_KERNEL_LAUNCHES),
            "h2d_bytes": self.registry.counter(self.scope, m.M_H2D_BYTES),
        }
        for leg in LEGS:
            hist = self.registry.histogram(self.scope, leg)
            if hist.count == 0:
                continue
            out[leg] = {
                "count": hist.count,
                "total_s": round(hist.total, 6),
                "p50_s": round(hist.percentile(0.5), 6),
                "p99_s": round(hist.percentile(0.99), 6),
            }
        return out
