"""Device-side CRC32 parity with the host checksum (ops/crc.py).

The device hash must be bit-identical to core.checksum.crc32_of_rows
(zlib IEEE CRC32 over little-endian int64 bytes) — it replaces the host
pull of full payload rows on the bench/verify paths.
"""
import collections
import re
import zlib

import numpy as np
import pytest

from cadence_tpu.core.checksum import DEFAULT_LAYOUT, PAD, crc32_of_rows
from cadence_tpu.ops.crc import _affine, crc32_rows, replay_to_crc
from cadence_tpu.ops.state import widen_layout

I64 = np.iinfo(np.int64)
#: 1 and 2 (the smallest), the base layout's 89, one ladder rung's 153
WIDTHS = (1, 2, DEFAULT_LAYOUT.width, widen_layout(DEFAULT_LAYOUT, 2).width)


def ops_under_scope(hlo_text, scope):
    """Opcode -> count over the compiled HLO's instructions whose
    `op_name` metadata lies under the named scope."""
    ops = collections.Counter()
    for line in hlo_text.splitlines():
        inst = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if inst and name and scope in name.group(1).split("/"):
            ops[inst.group(1)] += 1
    return ops


class TestDeviceCRC:
    def test_matches_zlib_on_random_rows(self):
        rng = np.random.default_rng(7)
        rows = rng.integers(np.iinfo(np.int64).min, np.iinfo(np.int64).max,
                            size=(64, 89), dtype=np.int64)
        assert (np.asarray(crc32_rows(rows)) == crc32_of_rows(rows)).all()

    def test_matches_zlib_on_payload_values(self):
        # realistic payload rows incl. the PAD sentinel (1<<62) and zeros
        rows = np.full((8, 89), PAD, dtype=np.int64)
        rows[:, :11] = np.arange(88).reshape(8, 11)
        rows[3] = 0
        assert (np.asarray(crc32_rows(rows)) == crc32_of_rows(rows)).all()

    def test_replay_to_crc_equals_host_pipeline(self):
        import jax.numpy as jnp

        from cadence_tpu.gen.corpus import generate_corpus
        from cadence_tpu.ops.encode import encode_corpus
        from cadence_tpu.ops.replay import replay_to_payload

        hist = generate_corpus("echo_signal", num_workflows=24, seed=3,
                               target_events=60)
        ev = jnp.asarray(encode_corpus(hist))
        rows, errors = replay_to_payload(ev, DEFAULT_LAYOUT)
        want = crc32_of_rows(np.asarray(rows))
        crc, errors2 = replay_to_crc(ev, DEFAULT_LAYOUT)
        assert (np.asarray(crc) == want).all()
        assert (np.asarray(errors2) == np.asarray(errors)).all()

    @pytest.mark.parametrize("width", WIDTHS)
    @pytest.mark.parametrize("w", [1, 7, 64, 1000])
    def test_matches_zlib_at_every_shape(self, w, width):
        rng = np.random.default_rng(1000 * width + w)
        rows = rng.integers(I64.min, I64.max, size=(w, width),
                            dtype=np.int64, endpoint=True)
        assert (np.asarray(crc32_rows(rows)) == crc32_of_rows(rows)).all()

    @pytest.mark.parametrize("width", WIDTHS)
    @pytest.mark.parametrize("value", [0, -1, I64.min, I64.max, PAD],
                             ids=["zeros", "ones", "min", "max", "pad"])
    def test_matches_zlib_on_constant_rows(self, value, width):
        rows = np.full((3, width), value, dtype=np.int64)
        assert (np.asarray(crc32_rows(rows)) == crc32_of_rows(rows)).all()

    @pytest.mark.parametrize("width", WIDTHS)
    def test_constant_is_the_crc_of_the_zero_message(self, width):
        m, zero_crc = _affine(width)
        assert zero_crc == zlib.crc32(bytes(8 * width))
        assert m.shape == (32, 2 * width, 32)
        # one row of M against its definition: bit 40 of the last word
        # is bit 8 of the high halves' last entry
        msg = np.zeros((1, width), dtype=np.int64)
        msg[0, -1] = 1 << 40
        want = int(crc32_of_rows(msg)[0]) ^ zero_crc
        got = sum(int(b) << o for o, b in enumerate(m[8, 2 * width - 1]))
        assert got == want

    def test_compiled_crc_is_a_product_not_a_gather_loop(self):
        """The guard against the byte-table scan coming back: under the
        `crc32` scope the compiled replay program holds matrix products
        and neither a gather nor a loop."""
        import jax.numpy as jnp

        from cadence_tpu.gen.corpus import generate_corpus
        from cadence_tpu.ops.encode import encode_corpus
        from cadence_tpu.ops.replay import replay_wirec_to_crc
        from cadence_tpu.ops.wirec import pack_wirec

        c = pack_wirec(encode_corpus(generate_corpus(
            "basic", num_workflows=8, seed=3, target_events=24)))
        hlo = replay_wirec_to_crc.lower(
            jnp.asarray(c.slab), jnp.asarray(c.bases),
            jnp.asarray(c.n_events), c.profile).compile().as_text()
        ops = ops_under_scope(hlo, "crc32")
        assert ops["dot"] + ops["convolution"] > 0, ops
        assert ops["gather"] == 0 and ops["while"] == 0, ops
        # the reader does see loops and scopes: the replay scan is one
        assert ops_under_scope(hlo, "transition")
        assert re.search(r" while\(", hlo)
