"""What `run.py`, the drivers and the readers share."""
from __future__ import annotations

import json
import sys
from dataclasses import dataclass
from typing import List, Sequence


def say(**fields) -> None:
    """One JSON line of the run's record, on standard error: standard
    output carries the result line alone."""
    print(json.dumps(fields, default=str), file=sys.stderr, flush=True)


@dataclass
class Compared:
    """One number that decides `correct`, beside its limit: the run is
    correct while every `value <= limit`."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class CompileCounter:
    """Counts this process's XLA compilations as JAX itself reports them:
    every program that is compiled or fetched from the persistent cache is
    one `backend_compile_duration` event. A steady window has none."""

    def __init__(self) -> None:
        import jax.monitoring

        self.total = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def percentile(samples: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) of raw samples, by linear interpolation
    between closest ranks (numpy's default), on a sorted copy."""
    if not samples:
        raise ValueError("no samples")
    xs: List[float] = sorted(samples)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
