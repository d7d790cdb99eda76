"""Test configuration.

The tests run on XLA's CPU backend, whatever the machine holds: they
check results, control flow and counts, never device speed, and several
workers of one run could not share a chip anyway. Sharding tests use a
virtual 8-device CPU mesh. Both settings must be in the environment
before jax initialises its backends, so they are set here at conftest
import time (pytest imports conftest before test modules import jax).
`chip_smoke.py` is the program's run on the accelerator; it never goes
through pytest and inherits none of this.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_DEVICE_FLAG = "--xla_force_host_platform_device_count=8"
if _DEVICE_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _DEVICE_FLAG).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent compilation cache: the suite is dominated by XLA compiles;
# cache them across workers and runs (the one rule lives in
# utils/compile_cache.py — the test run follows it like any entry point)
from cadence_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_default_observability():
    """DEFAULT_REGISTRY / DEFAULT_TRACER are process-global fallbacks that
    components constructed without explicit wiring share; reset them IN
    PLACE (components hold them by reference) before every test so one
    test's counters and spans never leak into another's assertions."""
    from cadence_tpu.utils import circuitbreaker, metrics, tracing
    metrics.DEFAULT_REGISTRY.reset()
    tracing.DEFAULT_TRACER.reset()
    # per-target breaker state is process-global the same way: a breaker
    # opened by one test must not shed the next test's calls to a reused
    # ephemeral port; chaos is per-process too, never leak an injector
    circuitbreaker.DEFAULT_BREAKERS.reset()
    from cadence_tpu.rpc import chaos
    chaos.uninstall()
    # durability crashpoints are process-global the same way: one test's
    # armed kill site must never fire inside another test's WAL append
    from cadence_tpu.engine import crashpoints
    crashpoints.uninstall()
    # resident-state caches pin DEVICE buffers per entry; clear every
    # live cache so one test's HBM residents (and their hit/miss state)
    # never leak into another's assertions or memory budget
    from cadence_tpu.engine import resident
    resident.reset_all()
    # serving schedulers own daemon drain threads + pending tickets the
    # same way: stop them so a leaked drain never flushes into the next
    # test's registry (a stopped scheduler restarts on its next submit)
    from cadence_tpu.engine import serving
    serving.reset_all()
    # quota limiters are held by reference inside frontends the same
    # way: drain one test's consumed tokens so they never shed the next
    # test's first requests
    from cadence_tpu.utils import quotas
    quotas.reset_all()
    # device-visibility views own daemon appender threads the same way
    # as serving schedulers: stop them so a leaked drain never applies
    # into the next test's registry (a stopped view restarts its thread
    # on the next enqueue)
    from cadence_tpu.engine import visibility_device
    visibility_device.reset_all()
    # the telemetry plane is process-global three ways: the flight
    # recorder's ring (emit points hold DEFAULT_RECORDER by reference),
    # and any sampler/profiler threads a test started — stop + clear so
    # one test's events/windows never surface in another's dumps
    from cadence_tpu.utils import flightrecorder, hostprof, timeseries
    flightrecorder.reset_all()
    timeseries.reset_all()
    hostprof.reset_all()
    yield


@pytest.fixture(params=["jsonl", "sqlite"])
def wal(request, tmp_path):
    """One durable-WAL path per open_log backend: every crash/fault/
    recovery test requesting this fixture runs the full matrix over both
    JSONL and SqliteLog (backend selected by extension)."""
    return str(tmp_path /
               ("wal.db" if request.param == "sqlite" else "wal.jsonl"))
