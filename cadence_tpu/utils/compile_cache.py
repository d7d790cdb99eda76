"""Persistent XLA compilation cache wiring.

The first compile of a replay kernel costs seconds to tens of seconds
per shape; without a persistent cache EVERY process (bench, CLI, service
hosts, dryruns, the chip smoke's phases) pays it again.

One rule, kept here and nowhere else. Where `JAX_COMPILATION_CACHE_DIR`
is set, that directory is the cache and this module names no other: the
installed JAX (0.9) reads the variable when it is imported, so there is
nothing to do for the directory after import. Where it is not set, the
cache is one fixed directory inside the checkout (`.jax_cache/`,
git-ignored) — the path is part of the cache's key, so it must not
move, and it must not be outside the tree this code runs from. That case, and
only that one, needs the post-import `jax.config.update`. Child
processes (rpc/cluster.launch) inherit the variable, or compute the
same in-checkout path, so a parent and its children always share one
directory. Call enable() early in every entry point.

Besides the directory the code sets one thing, the same in both cases:
`jax_persistent_cache_min_compile_time_secs` = 0, so every compile is
kept (JAX's default drops those under a second). It says WHAT is kept,
not where, and the rule needs it wherever the cache is placed: the
serving and visibility tiers compile many sub-second bucket shapes, a
restarted host should pay for none of them twice, and a second run can
be shown to compile nothing only if the first kept everything.
tests/test_compile_cache.py holds the code to exactly these settings.
"""
from __future__ import annotations

import os
import threading
from typing import Callable, Dict, Hashable

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout cache (repo root = three levels above this file)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The directory this process's compile cache lives in — answered
    from the environment alone, without importing JAX."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_DIR


def enable() -> str:
    """Switch JAX's persistent compilation cache on for this process and
    return the directory in use (see the module docstring for the rule).
    Idempotent."""
    import jax

    path = cache_dir()
    if not os.environ.get(CACHE_DIR_ENV):
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class KernelVariantCache:
    """Process-level registry of compiled kernel VARIANTS, keyed by the
    caller on (wire format, layout, rung/K, padded shape, shard count).

    The escalation ladder (engine/ladder.py) compiles one extra
    executable per widened-K rung variant; this cache makes that cost
    observable and amortized: get() returns the cached callable (a HIT —
    zero compile work) or builds it once (a MISS — exactly one XLA
    compile, itself served from the persistent disk cache above on warm
    processes). Hit/miss counters land on `tpu.fallback/*` so a warm
    re-run can PROVE it paid zero ladder recompiles (tests/test_ladder.py,
    tests/test_feeder_ladder.py).

    Shape keys should be pow2-bucketed by the caller: flagged-row counts
    wobble run to run, and bucketing keeps them landing on the same
    variant instead of minting a new executable per count.
    """

    def __init__(self, registry=None) -> None:
        self._lock = threading.Lock()
        self._fns: Dict[Hashable, Callable] = {}
        self.metrics = registry

    def _registry(self):
        if self.metrics is not None:
            return self.metrics
        from . import metrics as m
        return m.DEFAULT_REGISTRY

    def get(self, key: Hashable, build: Callable[[], Callable],
            registry=None, scope: str = "") -> Callable:
        """`registry` routes THIS call's hit/miss counters (a shared
        cache serves ladders bound to different per-cluster registries;
        each caller's counters must land on its own /metrics scrape);
        falls back to the cache-level registry, then the default.
        `scope` routes the counters' metric scope — the ladder's
        tpu.fallback by default; the mesh-aware serving executor passes
        its own so a warm serving run can prove zero recompiles without
        reading fallback series."""
        from . import metrics as m

        reg = registry if registry is not None else self._registry()
        scope = scope or m.SCOPE_TPU_FALLBACK
        with self._lock:
            fn = self._fns.get(key)
        if fn is not None:
            reg.inc(scope, m.M_LADDER_CACHE_HITS)
            return fn
        built = build()
        with self._lock:
            fn = self._fns.setdefault(key, built)
        if fn is built:
            # exactly one winner per key counts the miss/compile, even
            # when two ladder passes race on the same variant
            reg.inc(scope, m.M_LADDER_CACHE_MISSES)
            reg.inc(scope, m.M_LADDER_COMPILES)
        else:
            reg.inc(scope, m.M_LADDER_CACHE_HITS)
        return fn

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()


#: shared variant registry — all ladders in a process reuse one another's
#: compiled rungs (Onebox clusters, bench trials, tests)
DEFAULT_VARIANTS = KernelVariantCache()
