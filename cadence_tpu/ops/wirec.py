"""wirec: the compressed host→device wire format (columnar, adaptive width).

The host link is the product bottleneck: every byte the device replays
has to cross it first, and the dense int64 lanes spend 144 B/event on
values whose information content is a handful of bits: event ids advance
by 1, timestamps by a fixed tick, half the lanes are constant per corpus.
wirec ships 10-20 B/event by exploiting that shape the way
the reference's serializers exploit thrift compactness
(common/persistence/serialization/, parquet-style columnar encoding) —
but decodes ON DEVICE with pure vectorized XLA ops, so the dense form
never crosses the link.

Format. A corpus [W, E, NUM_LANES] int64 becomes:
  - slab   [W, E, B] uint8 — per-lane byte-columns, little-endian two's
           complement at each lane's minimal width (1..8 bytes);
  - bases  [W, K] int64 — per-workflow first-row values for delta/ts-rel
           lanes (amortized over E events);
  - n_events [W] int32 — real-row counts (tail padding is reconstructed,
           never shipped);
  - profile — a static per-lane plan, chosen at pack time by measuring
           the corpus:
      * CONST  c        : every real value equals c; 0 bytes on the wire.
      * ABS    v = q*s  : values divided by their GCD s, stored at the
                          minimal width for the quotient.
      * DELTA  v = cumsum(q*s) + base : row-to-row differences (event
                          ids, timestamps, task ids), GCD-scaled — a 1ns
                          tick stream ships 1 byte/event regardless of
                          the 8-byte absolute magnitude.
      * TSREL_NZ        : sparse absolute-nanos lanes (expiration
                          timestamps): zero stays zero, nonzero values
                          are GCD-scaled offsets from the workflow's
                          first timestamp.

Decoding is exact: every transform is integer-reversible, so the decoded
tensor is bit-identical to the int64 lane tensor (tests assert equality
and CRC parity with the dense replay). Widths are chosen from the actual
data, so pathological corpora degrade gracefully toward raw width-8
columns instead of failing.

The profile is a hashable static jit argument: one compiled executable
per (shape, profile), shared by every chunk of a homogeneous stream (the
feeder refits and recompiles only when a chunk's values fall outside the
profile — measured, never silent).
"""
from __future__ import annotations

import threading
from typing import NamedTuple, Optional, Tuple

import numpy as np

from .encode import LANE_EVENT_ID, LANE_EVENT_TYPE, LANE_TIMESTAMP, NUM_LANES

KIND_CONST = 0
KIND_ABS = 1
KIND_DELTA = 2
KIND_TSREL_NZ = 3

#: reconstructed value of each lane in tail-padding rows
PAD_VALUES = tuple(-1 if lane == LANE_EVENT_TYPE else 0
                   for lane in range(NUM_LANES))


class LaneCode(NamedTuple):
    """One lane's static decode plan."""

    lane: int
    kind: int
    offset: int      # byte offset inside the slab row (unused for CONST)
    width: int       # bytes per event (0 for CONST)
    scale: int       # GCD the stored quotient multiplies back by
    const: int       # CONST value
    base_index: int  # column in `bases` (-1 when no base is needed)


class WirecCorpus(NamedTuple):
    slab: np.ndarray       # [W, E, B] uint8
    bases: np.ndarray      # [W, K] int64
    n_events: np.ndarray   # [W] int32
    profile: Tuple[LaneCode, ...]

    @property
    def wire_bytes(self) -> int:
        return self.slab.nbytes + self.bases.nbytes + self.n_events.nbytes

    def bytes_per_event(self) -> float:
        real = int(self.n_events.sum())
        return self.wire_bytes / real if real else float("inf")


class ProfileMisfit(Exception):
    """A chunk's values exceed the pinned profile's widths/scales; the
    caller refits (recompute + recompile) — measured, never silent."""


def _width_for(lo: int, hi: int) -> int:
    """Minimal little-endian two's-complement byte width holding [lo, hi]."""
    for w in range(1, 8):
        if -(1 << (8 * w - 1)) <= lo and hi < (1 << (8 * w - 1)):
            return w
    return 8


def _gcd_scale(vals: np.ndarray) -> int:
    """GCD of |vals| (1 when empty/all-zero): the exact common tick."""
    if vals.size == 0:
        return 1
    g = int(np.gcd.reduce(np.abs(vals)))
    return g if g > 0 else 1


def _delta_codes(v: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Row-to-row differences with the real→pad cliff zeroed (pad rows
    carry delta 0 — the decoder's pad mask reconstructs their values, so
    only the width matters and zero always fits). d[:, 0] is 0 by
    construction: the workflow base ships in `bases`."""
    d = v.copy()
    d[:, 1:] -= v[:, :-1]
    d[:, 0] = 0
    return np.where(mask, d, 0)


def _plan_lane(v: np.ndarray, mask: np.ndarray, n: np.ndarray,
               ts_base: np.ndarray) -> Tuple[int, int, int, int]:
    """Choose (kind, width, scale, const) for one lane's [W, E] values.
    Only real rows matter — padding is reconstructed from n_events."""
    real = v[mask]
    if real.size == 0 or (real == real.flat[0]).all():
        return KIND_CONST, 0, 1, (int(real.flat[0]) if real.size else 0)

    g_abs = _gcd_scale(real)
    w_abs = _width_for(int(real.min()) // g_abs, int(real.max()) // g_abs)

    d = _delta_codes(v, mask)
    g_d = _gcd_scale(d[mask])
    dq = d[mask] // g_d
    w_d = _width_for(int(dq.min()), int(dq.max())) if dq.size else 1

    best = (KIND_ABS, w_abs, g_abs, 0)
    if w_d < w_abs:
        best = (KIND_DELTA, w_d, g_d, 0)

    # sparse absolute-nanos lanes: zeros + huge values (expiration stamps)
    if (real == 0).any() and (np.abs(real) > 1 << 31).any():
        rel = (v - ts_base[:, None])[mask & (v != 0)]
        g_ts = _gcd_scale(rel)
        q = rel // g_ts
        code_lo = min(int(q.min()), 0)
        code_hi = max(int(q.max()) + 1, 0)
        w_ts = _width_for(code_lo, code_hi)
        if w_ts < best[1] or (best[0] == KIND_DELTA and w_ts == best[1]):
            best = (KIND_TSREL_NZ, w_ts, g_ts, 0)
    return best


def _emit(slab: np.ndarray, off: int, width: int, code: np.ndarray) -> None:
    """Write [W, E] int64 codes as `width` little-endian bytes."""
    u = code.astype(np.uint64)
    for k in range(width):
        slab[:, :, off + k] = ((u >> np.uint64(8 * k))
                               & np.uint64(0xFF)).astype(np.uint8)


def _lane_codes(v: np.ndarray, mask: np.ndarray, n: np.ndarray,
                ts_base: np.ndarray, kind: int, scale: int
                ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """The stored quotient grid for one lane, plus the per-workflow base
    column (or None). Pad-row codes are whatever falls out of the raw
    values (ABS) or zero (DELTA/TSREL) — the decoder's pad mask makes
    their decoded value irrelevant; only the byte width must hold them,
    and pad values are 0/-1."""
    if kind == KIND_ABS:
        return v // scale if scale != 1 else v, None
    if kind == KIND_DELTA:
        d = _delta_codes(v, mask)
        return d // scale if scale != 1 else d, v[:, 0].copy()
    if kind == KIND_TSREL_NZ:
        q = (v - ts_base[:, None]) // scale
        code = np.where(q >= 0, q + 1, q)
        return np.where(mask & (v != 0), code, 0), ts_base.copy()
    raise ValueError(f"kind {kind} emits no codes")


def _check_fit(code: np.ndarray, width: int) -> bool:
    lo, hi = -(1 << (8 * width - 1)), (1 << (8 * width - 1)) - 1
    return bool((code >= lo).all() and (code <= hi).all())


def _pack_rows(ev: np.ndarray, mask: np.ndarray, n: np.ndarray,
               ts_base: np.ndarray, profile: Tuple[LaneCode, ...],
               slab: np.ndarray, bases: np.ndarray) -> None:
    """Emit every lane of a [w, E, L] row block into its slab/bases slice
    (each transform is per-workflow-row, so blocks are independent)."""
    for e in profile:
        v = ev[:, :, e.lane]
        if e.kind == KIND_CONST:
            if mask.any() and not (v[mask] == e.const).all():
                raise ProfileMisfit(f"lane {e.lane}: non-const under CONST")
            continue
        code, base = _lane_codes(v, mask, n, ts_base, e.kind, e.scale)
        # exactness: the quotient must reproduce the value on REAL rows
        # (scale divides evenly) — pad rows are reconstructed by mask
        if e.scale != 1 or e.kind == KIND_TSREL_NZ:
            if e.kind == KIND_ABS:
                bad = (code * e.scale != v) & mask
            elif e.kind == KIND_DELTA:
                bad = (code * e.scale != _delta_codes(v, mask)) & mask
            else:  # KIND_TSREL_NZ: undo the zero-escape bias
                m = code - (code >= 1)
                bad = ((m * e.scale + ts_base[:, None] != v)
                       & mask & (v != 0))
            if bad.any():
                raise ProfileMisfit(f"lane {e.lane}: scale {e.scale} misfit")
        if not _check_fit(code, e.width):
            raise ProfileMisfit(f"lane {e.lane}: width {e.width} overflow")
        _emit(slab, e.offset, e.width, code)
        if base is not None:
            bases[:, e.base_index] = base


#: minimum rows per thread block: below this the pool overhead beats the
#: numpy-releases-the-GIL parallelism win
_MIN_BLOCK_ROWS = 256

#: process-lifetime pack pools by worker count — the wirec feeder calls
#: pack_wirec once per chunk, so per-call pool spawn/join would be pure
#: overhead on the exact path this parallelism is optimizing
_POOLS: dict = {}
_POOLS_LOCK = threading.Lock()


def _pack_pool(threads: int):
    with _POOLS_LOCK:
        pool = _POOLS.get(threads)
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor
            pool = _POOLS[threads] = ThreadPoolExecutor(
                max_workers=threads, thread_name_prefix="wirec-pack")
        return pool


def pack_wirec(events64: np.ndarray,
               profile: Optional[Tuple[LaneCode, ...]] = None,
               num_threads: Optional[int] = None) -> WirecCorpus:
    """[W, E, NUM_LANES] int64 → WirecCorpus.

    With `profile` pinned (streaming chunks sharing one executable), the
    chunk is packed under that plan; values that don't fit its
    widths/scales raise ProfileMisfit so the caller refits explicitly.

    `num_threads` > 1 enables the chunk-parallel path: lane PLANNING fans
    out per lane and EMIT fans out over workflow-row blocks (every
    transform — delta, GCD scaling, ts-rel — is per-workflow, so blocks
    are independent and the packed bytes are identical to the serial
    path). numpy releases the GIL inside the ufunc loops, so host packing
    scales with cores instead of pinning one. `None` resolves through the
    one CADENCE_TPU_PACK_THREADS knob (utils/concurrency.pack_threads);
    small corpora stay serial either way (_MIN_BLOCK_ROWS).
    """
    from ..utils.concurrency import pack_threads

    ev = np.asarray(events64, dtype=np.int64)
    W, E, L = ev.shape
    assert L == NUM_LANES, f"expected {NUM_LANES} lanes, got {L}"
    n = (ev[:, :, LANE_EVENT_ID] > 0).sum(axis=1).astype(np.int32)
    mask = np.arange(E)[None, :] < n[:, None]
    # row 0 is real whenever n > 0, so the first-row value IS the base
    ts_base = ev[:, 0, LANE_TIMESTAMP]

    threads = pack_threads(num_threads)
    if W < 2 * _MIN_BLOCK_ROWS:
        threads = 1
    pool = _pack_pool(threads) if threads > 1 else None

    if profile is None:
        if pool is not None:
            plans = list(pool.map(
                lambda lane: _plan_lane(ev[:, :, lane], mask, n, ts_base),
                range(NUM_LANES)))
        else:
            plans = [_plan_lane(ev[:, :, lane], mask, n, ts_base)
                     for lane in range(NUM_LANES)]
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
        profile = tuple(entries)

    B = sum(e.width for e in profile)
    K = sum(1 for e in profile if e.base_index >= 0)
    slab = np.zeros((W, E, B), dtype=np.uint8)
    bases = np.zeros((W, K), dtype=np.int64)
    if pool is not None:
        block = max(_MIN_BLOCK_ROWS, -(-W // threads))
        bounds = [(lo, min(lo + block, W)) for lo in range(0, W, block)]
        list(pool.map(
            lambda b: _pack_rows(ev[b[0]:b[1]], mask[b[0]:b[1]],
                                 n[b[0]:b[1]], ts_base[b[0]:b[1]],
                                 profile, slab[b[0]:b[1]],
                                 bases[b[0]:b[1]]),
            bounds))
    else:
        _pack_rows(ev, mask, n, ts_base, profile, slab, bases)
    return WirecCorpus(slab, bases, n, profile)


def gather_corpus(corpus: WirecCorpus, indices,
                  pad_workflows: int = 0,
                  pad_events: int = 0) -> WirecCorpus:
    """Gather flagged rows into a compact sub-corpus under the SAME
    profile (engine/ladder.py's wirec leg): the widened-K re-replay
    decodes the identical bytes, so gather+re-replay is byte-equivalent
    to the rows' original decode. The event axis trims to the flagged
    rows' longest real history; padding rows carry n_events = 0 (the
    decoder masks every event past n_events to no-op lanes), letting
    padded shapes pow2-bucket for executable reuse."""
    idx = np.asarray(indices, dtype=np.int64)
    n = corpus.n_events[idx]
    e_real = int(n.max()) if len(idx) else 1
    e_real = max(e_real, 1)
    E = max(e_real, pad_events)
    W = max(len(idx), pad_workflows)
    slab = np.zeros((W, E, corpus.slab.shape[2]), dtype=np.uint8)
    bases = np.zeros((W, corpus.bases.shape[1]), dtype=np.int64)
    n_events = np.zeros((W,), dtype=np.int32)
    slab[:len(idx), :e_real] = corpus.slab[idx][:, :e_real]
    bases[:len(idx)] = corpus.bases[idx]
    n_events[:len(idx)] = n
    return WirecCorpus(slab, bases, n_events, corpus.profile)


# ---------------------------------------------------------------------------
# Device decode (pure jnp; exact inverse of pack_wirec)
# ---------------------------------------------------------------------------


def _read_le(slab, off: int, width: int):
    """[..., B] uint8 → [...] int64: little-endian, top byte sign-extended
    (explicit arithmetic, identical on CPU and TPU backends)."""
    import jax.numpy as jnp

    v = (slab[..., off + width - 1].astype(jnp.int8).astype(jnp.int64)
         << (8 * (width - 1)))
    for k in range(width - 1):
        v = v | (slab[..., off + k].astype(jnp.int64) << (8 * k))
    return v


def decode_wirec(slab, bases, n_events,
                 profile: Tuple[LaneCode, ...]):
    """Full-tensor decode: [W, E, B] uint8 → [W, E, NUM_LANES] int64,
    bit-identical to the packed corpus (tests assert)."""
    import jax.numpy as jnp

    W, E, _ = slab.shape
    in_real = jnp.arange(E)[None, :] < n_events[:, None]
    lanes = []
    for e in profile:
        if e.kind == KIND_CONST:
            v = jnp.full((W, E), e.const, dtype=jnp.int64)
        else:
            code = _read_le(slab, e.offset, e.width)
            if e.kind == KIND_ABS:
                v = code * e.scale
            elif e.kind == KIND_DELTA:
                v = (jnp.cumsum(code * e.scale, axis=1)
                     + bases[:, e.base_index][:, None])
            else:  # KIND_TSREL_NZ
                m = jnp.where(code >= 1, code - 1, code)
                v = jnp.where(code == 0, 0,
                              m * e.scale + bases[:, e.base_index][:, None])
        lanes.append(jnp.where(in_real, v, PAD_VALUES[e.lane]))
    return jnp.stack(lanes, axis=-1)


def decode_step(sl, prev, bases, n_events, e_idx,
                profile: Tuple[LaneCode, ...]):
    """Scan-fused decode of ONE event column: sl [W, B] uint8 → (ev
    [W, NUM_LANES] int64, new prev [W, n_delta] int64). DELTA lanes carry
    their running value in `prev` instead of a materialized cumsum, so
    the dense tensor never exists in HBM."""
    import jax.numpy as jnp

    W = sl.shape[0]
    in_real = e_idx < n_events
    vals = []
    new_prev = prev
    di = 0
    for e in profile:
        if e.kind == KIND_CONST:
            v = jnp.full((W,), e.const, dtype=jnp.int64)
        else:
            code = _read_le(sl, e.offset, e.width)
            if e.kind == KIND_ABS:
                v = code * e.scale
            elif e.kind == KIND_DELTA:
                v = prev[:, di] + code * e.scale
                new_prev = new_prev.at[:, di].set(v)
                di += 1
            else:
                m = jnp.where(code >= 1, code - 1, code)
                v = jnp.where(code == 0, 0,
                              m * e.scale + bases[:, e.base_index])
        vals.append(jnp.where(in_real, v, PAD_VALUES[e.lane]))
    return jnp.stack(vals, axis=-1), new_prev


def delta_base_columns(profile: Tuple[LaneCode, ...]) -> Tuple[int, ...]:
    """`bases` columns of the DELTA lanes, in profile order (the scan
    carry's initial values)."""
    return tuple(e.base_index for e in profile if e.kind == KIND_DELTA)
