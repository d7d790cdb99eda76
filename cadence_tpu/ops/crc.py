"""Device-side CRC32: the checksum leg of the parity oracle, on chip.

The reference computes IEEE CRC32 over the canonical mutable-state payload
on the CPU (common/checksum/crc.go:35-57); core/checksum.py mirrors it with
zlib over little-endian int64 rows. Pulling [W, width] payload rows to the
host just to hash them is D2H-bandwidth-bound — so the hash itself runs
on device and the host pulls 4 bytes per workflow instead of 8·width,
bit-identical to `crc32_of_row` (asserted by tests/test_device_crc.py).

The CRC of a message of FIXED length is affine over GF(2):
`crc(m) = M·bits(m) ⊕ crc(0ⁿ)`. Row `i` of `M` is the CRC of the message
whose only set bit is bit `i`, xor the CRC of the all-zero message; the
constant already holds the 0xFFFFFFFF init and the final xor. A payload
row's width is static under `jit`, so the whole hash is one 0/1 matrix
product on the MXU with an exact accumulator, then a parity.

It replaced a byte-table form (one `lax.scan` step a word, eight 256-entry
table gathers a step over the [W] lane) whose rationale was that a
latency-bound loop pays for the length of its chain and not for the number
of gathers. The v5e refuted that: it serialises a lane gather, 33.6 µs for
one gather of 4,096 lanes (0.0599 s over 1,780 runs of each of the eight
gather fusions, ledger PR 25), so the 712 gathers a chunk were 23.9 ms of
a 68.6 ms kernel. The product has no gather, no loop and no carried state.
"""
from __future__ import annotations

import zlib
from functools import lru_cache, partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HALF_BITS = 32  # an int64 word is hashed as its low and its high uint32


@lru_cache(maxsize=None)
def _affine(width: int) -> Tuple[np.ndarray, int]:
    """(M, crc(0ⁿ)) for rows of `width` int64 words, n = 8·width bytes.

    M is laid out for the product below: `M[k, h, o]` is output bit `o`
    of the CRC's linear part for bit `k` of half-word `h`, the half-words
    being all the rows' low uint32s and then all their high ones. With
    the init left out, zero bytes before a set bit keep the register at
    zero, the bit's own byte leaves it at that byte's table entry, and
    every byte after it advances it by one zero byte: so the last byte's
    eight registers, advanced once a byte towards the front, give every
    row.
    zlib does both steps: `crc32(data, v)` starts from register `~v` and
    returns the complement of the register it ends on.
    """
    ones = 0xFFFFFFFF
    n = 8 * width
    regs = [zlib.crc32(bytes([1 << bit]), ones) ^ ones for bit in range(8)]
    by_byte = np.empty((n, 8), dtype=np.uint32)
    for byte in range(n - 1, -1, -1):
        by_byte[byte] = regs
        regs = [zlib.crc32(b"\0", r ^ ones) ^ ones for r in regs]
    # [n, 8] -> [width, 64] (little-endian: bit i of a word is bit i % 8 of
    # its byte i // 8) -> [32, 2·width]: bit k of the low, then high, halves
    by_word = by_byte.reshape(width, 2, _HALF_BITS)
    by_half = by_word.transpose(2, 1, 0).reshape(_HALF_BITS, 2 * width)
    m = ((by_half[..., None] >> np.arange(32, dtype=np.uint32)) & 1
         ).astype(np.uint8)
    m.setflags(write=False)  # one array for every caller of the cache
    return m, zlib.crc32(bytes(n))


@jax.jit
@jax.named_scope("crc32")
def crc32_rows(rows: jnp.ndarray) -> jnp.ndarray:
    """Per-row IEEE CRC32 of a [W, width] int64 matrix's little-endian
    bytes; bit-identical to core.checksum.crc32_of_rows."""
    m, zero_crc = _affine(rows.shape[1])
    m = jnp.asarray(m, dtype=jnp.bfloat16)
    lo = rows.astype(jnp.uint32)  # bits 0..31 (two's complement wrap)
    hi = jnp.right_shift(rows, 32).astype(jnp.uint32)
    halves = jnp.concatenate([lo, hi], axis=1)  # [W, 2·width]
    # one product a bit plane, [W, 2·width] x [2·width, 32]: the 0/1
    # operand that lives at any time is 2·width bf16 a row, not 64·width.
    # 0/1 products summed in float32 are exact: every sum is at most
    # 64·width, far under 2**24
    sums = sum(jnp.dot(((halves >> k) & 1).astype(jnp.bfloat16), m[k],
                       preferred_element_type=jnp.float32)
               for k in range(_HALF_BITS))
    parity = sums.astype(jnp.uint32) & 1  # the sum over GF(2)
    crc = (parity << jnp.arange(32, dtype=jnp.uint32)).sum(
        axis=1, dtype=jnp.uint32)
    return crc ^ jnp.uint32(zero_crc)


@partial(jax.jit, static_argnames=("layout",))
def replay_to_crc(events: jnp.ndarray, layout):
    """Replay packed events and reduce all the way to (crc32 [W] uint32,
    error [W]) — the minimal-D2H form of the north-star pipeline."""
    from .payload import payload_rows
    from .replay import replay_events

    s = replay_events(events, layout)
    return crc32_rows(payload_rows(s, layout)), s.error
