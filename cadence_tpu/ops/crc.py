"""Device-side CRC32: the checksum leg of the parity oracle, on chip.

The reference computes IEEE CRC32 over the canonical mutable-state payload
on the CPU (common/checksum/crc.go:35-57); core/checksum.py mirrors it with
zlib over little-endian int64 rows. Pulling [W, width] payload rows to the
host just to hash them is D2H-bandwidth-bound — so the hash itself runs
on device: a table-driven byte-at-a-time CRC over each row's 8·width
little-endian bytes, reduced to one uint32 per workflow. The host then pulls 4 bytes per workflow instead
of 8·width, and bitwise-identical values to `crc32_of_row` (asserted by
tests/test_device_crc.py).

The classic reflected-polynomial table algorithm maps cleanly onto the
VPU: per scanned word, 8 unrolled steps of (xor, mask, 256-entry gather,
shift) over the [W] lane — no host round-trip anywhere.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_POLY = np.uint32(0xEDB88320)  # reflected IEEE polynomial (crc.go IEEETable)


def _make_tables() -> np.ndarray:
    """Slice-by-8 table set T[0..7]: T[0] is the classic byte table;
    T[k][i] advances T[k-1][i] by one zero byte. Processing one int64 word
    per iteration with 8 independent gathers keeps the sequential
    dependency chain at `width` instead of `8*width` — the chain, not the
    gather count, is what a latency-bound [W]-lane loop pays for."""
    t = np.zeros((8, 256), dtype=np.uint32)
    for i in range(256):
        c = np.uint32(i)
        for _ in range(8):
            c = (c >> np.uint32(1)) ^ (_POLY if c & np.uint32(1) else np.uint32(0))
        t[0, i] = c
    for k in range(1, 8):
        prev = t[k - 1]
        t[k] = (prev >> np.uint32(8)) ^ t[0][prev & np.uint32(0xFF)]
    return t


_TABLES = _make_tables()


@jax.jit
@jax.named_scope("crc32")
def crc32_rows(rows: jnp.ndarray) -> jnp.ndarray:
    """Per-row IEEE CRC32 of a [W, width] int64 matrix's little-endian
    bytes; bit-identical to core.checksum.crc32_of_rows."""
    tables = jnp.asarray(_TABLES)
    # all-ones, but DERIVED from `rows` rather than built as a constant:
    # inside a shard_map (ops/genkernel's fused kernel) the rows vary
    # across the mesh axis, and the scan below requires its initial
    # carry to have the same varying type as the carry it produces —
    # a constant would be typed replicated and rejected
    init = (rows[:, 0] & 0).astype(jnp.uint32) ^ jnp.uint32(0xFFFFFFFF)

    def word_step(crc, word):
        # word [W] int64, consumed LSB-first (little-endian): xor the low
        # half into the running crc, then 8 parallel table gathers
        lo = word.astype(jnp.uint32)  # bits 0..31 (two's complement wrap)
        hi = jnp.right_shift(word, 32).astype(jnp.uint32)
        x = crc ^ lo
        out = jnp.zeros_like(crc)
        for k in range(4):
            out = out ^ tables[7 - k][(x >> (8 * k)) & 0xFF]
        for k in range(4):
            out = out ^ tables[3 - k][(hi >> (8 * k)) & 0xFF]
        return out, None

    crc, _ = jax.lax.scan(word_step, init, jnp.swapaxes(rows, 0, 1))
    return crc ^ jnp.uint32(0xFFFFFFFF)


@partial(jax.jit, static_argnames=("layout",))
def replay_to_crc(events: jnp.ndarray, layout):
    """Replay packed events and reduce all the way to (crc32 [W] uint32,
    error [W]) — the minimal-D2H form of the north-star pipeline."""
    from .payload import payload_rows
    from .replay import replay_events

    s = replay_events(events, layout)
    return crc32_rows(payload_rows(s, layout)), s.error
