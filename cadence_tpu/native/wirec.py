"""Native wirec pipeline: ctypes binding, reusable staging buffers, and
the native/Python dispatcher every wirec-packing hot path routes through.

The numpy wirec emit cannot keep pace with the device's replay, so
`wirec.cc` ports measure/emit to C++ (threaded, byte-identical, same
ProfileMisfit refit contract) and adds the STREAMED entry points, whose
unit is the row: a thread decodes one workflow's blob into a one-row
scratch that stays in its cache and measures or emits it there, so no
[W, E, L] int64 tensor exists between the wire blobs and the wirec
buffers. A pinned chunk is one such pass (`cadence_wirec_pack_fused`),
a chunk with no profile yet two (`cadence_wirec_measure_blobs`, then
the fused pass under the fresh plan), writing into preallocated
reusable host buffers sized to the feeder's ring slots so a streaming
chunk costs zero Python-side allocation or copies before the single H2D
transfer. The dense entry points (`measure_profile_native`,
`pack_wirec_native`) serve callers that already hold a lane tensor and
run the same per-row code.

Path selection: `CADENCE_TPU_NATIVE_WIREC` (default ON when the .so is
loadable, any of 0/false/off forces the pure-Python path; the fallback
is byte-identical, it is only slower). The `tpu.native/available` gauge
plus native-packs/python-packs counters say which encoder actually
served, so "which path ran" is a /metrics scrape, never a guess.
"""
from __future__ import annotations

import ctypes
import os
from typing import Optional, Sequence, Tuple

import numpy as np

from ..ops.encode import NUM_LANES
from ..ops.wirec import (
    KIND_DELTA,
    KIND_TSREL_NZ,
    LaneCode,
    ProfileMisfit,
    WirecCorpus,
    pack_wirec,
)
from ..utils import metrics as m
from ..utils import tracing
from ..utils.concurrency import pack_threads
from . import build as _build

#: the native-wirec knob: default on when the .so is available;
#: 0/false/off pins the byte-identical pure-Python encoder
NATIVE_WIREC_ENV = "CADENCE_TPU_NATIVE_WIREC"

#: host→device staging knob: default on — on the CPU backend reusable
#: staging buffers hand off through dlpack (see h2d_path);
#: 0/false/off pins plain jax.device_put
ZERO_COPY_ENV = "CADENCE_TPU_ZERO_COPY"

_I64P = ctypes.POINTER(ctypes.c_int64)
_U8P = ctypes.POINTER(ctypes.c_uint8)
_I32P = ctypes.POINTER(ctypes.c_int32)


def native_wirec_available() -> bool:
    return _build.load_wirec() is not None


def wirec_native_enabled(registry=None) -> bool:
    """True when wirec packs should take the native encoder. Publishes
    the `tpu.native/available` gauge as a side effect — the scrape-level
    answer to "did this process ever have the fast path at all"."""
    reg = registry if registry is not None else m.DEFAULT_REGISTRY
    avail = native_wirec_available()
    reg.scope(m.SCOPE_TPU_NATIVE).gauge(m.M_NATIVE_AVAILABLE,
                                        1.0 if avail else 0.0)
    env = os.environ.get(NATIVE_WIREC_ENV, "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return False
    return avail


def h2d_path() -> str:
    """Which staging path this process takes — "dlpack" or "device_put"
    — chosen from what the code can observe: the default backend's
    platform. A numpy buffer exports as a kDLCPU tensor, so a dlpack
    import can only ever land on the CPU device: on the CPU backend
    that IS the target and the import hands the buffer over without a
    copy; on an accelerator it would land on the wrong device (or, with
    only the accelerator's platform loaded, fail), so there the one path
    is jax.device_put, which copies host→HBM. CADENCE_TPU_ZERO_COPY=0
    pins device_put everywhere."""
    import jax

    env = os.environ.get(ZERO_COPY_ENV, "").strip().lower()
    if env in ("0", "false", "off", "no"):
        return "device_put"
    return "dlpack" if jax.default_backend() == "cpu" else "device_put"


def stage_h2d(arr):
    """ONE host→device staging hop for a reusable host buffer, by the
    path h2d_path() names. DLPack carries neither a read-only flag nor
    non-compact strides, so such an array takes device_put on any
    backend. Safe against ring-slot reuse either way: the executor's
    ring discipline frees a slot only after the chunk that last used it
    has fully replayed, so the device is never still reading a buffer
    being overwritten."""
    import jax

    if (h2d_path() == "dlpack" and arr.flags.c_contiguous
            and arr.flags.writeable):
        return jax.dlpack.from_dlpack(arr)
    return jax.device_put(arr)


def stage_corpus(corpus: WirecCorpus):
    """Stage a wirec triple for a single-device launch (the feeder's
    non-mesh hot path); returns (slab, bases, n_events) device arrays."""
    return (stage_h2d(corpus.slab), stage_h2d(corpus.bases),
            stage_h2d(corpus.n_events))


def _assemble_profile(plans) -> Tuple[LaneCode, ...]:
    """(kind, width, scale, const) per lane → LaneCode tuple — the EXACT
    offset/base-column assembly loop of ops.wirec.pack_wirec, so the
    profile structure cannot drift between the two encoders."""
    off = 0
    base_cols = 0
    entries = []
    for lane, (kind, width, scale, const) in enumerate(plans):
        bi = -1
        if kind in (KIND_DELTA, KIND_TSREL_NZ):
            bi = base_cols
            base_cols += 1
        entries.append(LaneCode(lane, kind, off if width else 0,
                                width, scale, const, bi))
        off += width
    return tuple(entries)


def _profile_columns(profile):
    cols = []
    for field in ("lane", "kind", "offset", "width", "scale", "const",
                  "base_index"):
        cols.append(np.fromiter((getattr(e, field) for e in profile),
                                dtype=np.int64, count=len(profile)))
    return cols


def _col_ptrs(cols):
    return [c.ctypes.data_as(_I64P) for c in cols]


def profile_widths(profile) -> Tuple[int, int]:
    """(B, K): slab bytes per event and bases columns under `profile`."""
    return (sum(e.width for e in profile),
            sum(1 for e in profile if e.base_index >= 0))


def _raise_misfit(code: int) -> None:
    lane, reason = divmod(code - 1000, 4)
    what = {0: "non-const under CONST", 1: "scale misfit",
            2: "width overflow"}.get(reason, f"code {reason}")
    raise ProfileMisfit(f"lane {lane}: {what} (native)")


class WirecBuffers:
    """Preallocated reusable host staging for ONE ring slot of the
    streaming pipeline: the wirec output triple (slab/bases/n_events),
    lazily (re)sized when the pinned profile's slab width changes (a
    refit event — rare by design).

    The native emit fully overwrites every byte it hands out, so slots
    are reused chunk after chunk with no zeroing; the executor's ring
    discipline guarantees the device consumed a slot's H2D copy before
    the slot is written again."""

    def __init__(self, chunk_workflows: int, max_events: int) -> None:
        self.W = chunk_workflows
        self.E = max_events
        self._key: Optional[Tuple[int, int]] = None
        self.slab = self.bases = self.n_events = None

    def for_profile(self, profile):
        B, K = profile_widths(profile)
        if self._key != (B, K):
            self.slab = np.empty((self.W, self.E, B), dtype=np.uint8)
            self.bases = np.empty((self.W, K), dtype=np.int64)
            self.n_events = np.empty((self.W,), dtype=np.int32)
            self._key = (B, K)
        return self.slab, self.bases, self.n_events


def _measure(entry, *frame, num_threads: int
             ) -> Tuple[Tuple[LaneCode, ...], int]:
    """Run one native measure entry point over its call frame (a lane
    tensor, or blob + offsets, then W, E, L) → (profile, return code)."""
    plan = [np.zeros(NUM_LANES, dtype=np.int64) for _ in range(4)]
    rc = entry(*frame, *_col_ptrs(plan), num_threads)
    return _assemble_profile(list(zip(*(c.tolist() for c in plan)))), rc


def measure_profile_native(events64: np.ndarray,
                           num_threads: Optional[int] = None
                           ) -> Tuple[LaneCode, ...]:
    """Per-lane plan of a [W, E, L] int64 tensor — the native twin of
    pack_wirec's profile measurement (identical decision procedure)."""
    lib = _build.load_wirec()
    if lib is None:
        raise RuntimeError("native wirec unavailable (no C++ toolchain)")
    ev = np.ascontiguousarray(events64, dtype=np.int64)
    W, E, L = ev.shape
    assert L == NUM_LANES, f"expected {NUM_LANES} lanes, got {L}"
    profile, rc = _measure(
        lib.cadence_wirec_measure, ev.ctypes.data_as(_I64P), W, E, L,
        num_threads=pack_threads(num_threads, cap=max(1, W)))
    assert rc == 0, rc
    return profile


def pack_wirec_native(events64: np.ndarray,
                      profile=None,
                      num_threads: Optional[int] = None,
                      out: Optional[WirecBuffers] = None) -> WirecCorpus:
    """Native [W, E, L] int64 → WirecCorpus, byte-identical to
    ops.wirec.pack_wirec (same profile measurement when `profile` is
    None; ProfileMisfit under a pinned profile whose widths/scales the
    chunk exceeds). `out` stages into a reusable WirecBuffers slot."""
    lib = _build.load_wirec()
    if lib is None:
        raise RuntimeError("native wirec unavailable (no C++ toolchain)")
    ev = np.ascontiguousarray(events64, dtype=np.int64)
    W, E, L = ev.shape
    assert L == NUM_LANES, f"expected {NUM_LANES} lanes, got {L}"
    threads = pack_threads(num_threads)
    if profile is None:
        profile = measure_profile_native(ev, num_threads=threads)
    B, K = profile_widths(profile)
    if out is not None:
        assert (out.W, out.E) == (W, E), ((out.W, out.E), (W, E))
        slab, bases, n_events = out.for_profile(profile)
    else:
        slab = np.empty((W, E, B), dtype=np.uint8)
        bases = np.empty((W, K), dtype=np.int64)
        n_events = np.empty((W,), dtype=np.int32)
    rc = lib.cadence_wirec_emit(
        ev.ctypes.data_as(_I64P), W, E, L,
        *_col_ptrs(_profile_columns(profile)), len(profile), B, K,
        slab.ctypes.data_as(_U8P), bases.ctypes.data_as(_I64P),
        n_events.ctypes.data_as(_I32P), threads)
    if rc != 0:
        _raise_misfit(rc)
    return WirecCorpus(slab, bases, n_events, profile)


def pack_serialized_wirec(blobs: Sequence[bytes], max_events: int,
                          profile=None,
                          num_threads: Optional[int] = None,
                          out: Optional[WirecBuffers] = None
                          ) -> Tuple[WirecCorpus, int]:
    """The streamed chunk: W serialized histories → wirec buffers, a row
    at a time inside the native library, with no lane tensor in between.
    Under a pinned profile that is ONE decode pass (each row decoded,
    counted and emitted while it is in its thread's cache); with
    `profile=None` (the first chunk, a refit) it is TWO over the same
    joined blobs: span `pack.measure` decodes and accumulates the lane
    statistics, then the fused pass packs under the fresh plan. Returns
    (corpus, total events); raises ProfileMisfit when the chunk falls
    outside a pinned profile (the caller refits, exactly like the numpy
    path) and ValueError naming the lowest workflow that fails to
    decode."""
    from .packing import blob_offsets, raise_pack_error

    lib = _build.load_wirec()
    if lib is None:
        raise RuntimeError("native wirec unavailable (no C++ toolchain)")
    W = len(blobs)
    blob, offsets = blob_offsets(blobs)
    frame = (blob, offsets.ctypes.data_as(_I64P), W, max_events, NUM_LANES)
    threads = pack_threads(num_threads, cap=max(1, W))
    if out is not None:
        assert (out.W, out.E) == (W, max_events)

    if profile is None:
        with tracing.span("pack.measure"):
            profile, rc = _measure(lib.cadence_wirec_measure_blobs, *frame,
                                   num_threads=threads)
        if rc < 0:
            raise_pack_error(rc)

    B, K = profile_widths(profile)
    if out is not None:
        slab, bases, n_events = out.for_profile(profile)
    else:
        slab = np.empty((W, max_events, B), dtype=np.uint8)
        bases = np.empty((W, K), dtype=np.int64)
        n_events = np.empty((W,), dtype=np.int32)
    misfit = np.zeros(1, dtype=np.int64)
    rc = lib.cadence_wirec_pack_fused(
        *frame,
        *_col_ptrs(_profile_columns(profile)), len(profile), B, K,
        slab.ctypes.data_as(_U8P), bases.ctypes.data_as(_I64P),
        n_events.ctypes.data_as(_I32P), misfit.ctypes.data_as(_I64P),
        threads)
    if rc < 0:
        raise_pack_error(rc)
    if int(misfit[0]) != 0:
        _raise_misfit(int(misfit[0]))
    return WirecCorpus(slab, bases, n_events, profile), int(rc)


def pack_wirec_auto(events64: np.ndarray, profile=None,
                    num_threads: Optional[int] = None,
                    registry=None) -> WirecCorpus:
    """The ONE wirec-pack dispatcher the hot paths call (feeder,
    executor streaming, resident appends, bench): native encoder when
    enabled+available, byte-identical pure-Python otherwise. Counts
    which encoder served under tpu.native/*. ProfileMisfit propagates
    from either side — the refit contract is path-independent."""
    reg = registry if registry is not None else m.DEFAULT_REGISTRY
    if wirec_native_enabled(reg):
        corpus = pack_wirec_native(events64, profile=profile,
                                   num_threads=num_threads)
        reg.inc(m.SCOPE_TPU_NATIVE, m.M_NATIVE_PACKS)
        return corpus
    corpus = pack_wirec(events64, profile=profile, num_threads=num_threads)
    reg.inc(m.SCOPE_TPU_NATIVE, m.M_NATIVE_PY_PACKS)
    return corpus
