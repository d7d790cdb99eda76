"""wire32 int32 transfer format: exact round-trip + replay equivalence.

H2D bytes are the scarce resource; the wire format
ships 20 int32 lanes instead of 18 int64 with the two 64-bit values
(timestamp nanos, start-event expiration nanos) split lo/hi and
reconstructed exactly on device.
"""
import numpy as np
import pytest

from cadence_tpu.core.checksum import DEFAULT_LAYOUT, crc32_of_rows
from cadence_tpu.gen.corpus import SUITES, generate_corpus
from cadence_tpu.ops.encode import NUM_LANES, NUM_LANES32, encode_corpus, to_wire32


def _corpus(suite, n=16, seed=9):
    return encode_corpus(generate_corpus(suite, num_workflows=n, seed=seed,
                                         target_events=80))


class TestWire32:
    def test_round_trip_exact(self):
        import jax.numpy as jnp

        from cadence_tpu.ops.replay import widen_wire32

        ev = _corpus("timer_retry")
        w32 = to_wire32(ev)
        assert w32.dtype == np.int32 and w32.shape[-1] == NUM_LANES32
        back = np.asarray(widen_wire32(jnp.asarray(w32)))
        assert back.shape == ev.shape and (back == ev).all()

    @pytest.mark.parametrize("suite", SUITES)
    def test_replay32_matches_replay64(self, suite):
        import jax.numpy as jnp

        from cadence_tpu.ops.replay import replay_to_crc32, replay_to_payload

        ev = _corpus(suite)
        rows, errors = replay_to_payload(jnp.asarray(ev), DEFAULT_LAYOUT)
        want = crc32_of_rows(np.asarray(rows))
        crc, errors32 = replay_to_crc32(jnp.asarray(to_wire32(ev)),
                                        DEFAULT_LAYOUT)
        assert (np.asarray(crc) == want).all()
        assert (np.asarray(errors32) == np.asarray(errors)).all()

    def test_sharded_crc_matches(self):
        import jax

        from cadence_tpu.parallel.mesh import make_mesh, replay_sharded_crc

        ev = _corpus("concurrent_child", n=32)
        mesh = make_mesh()
        crc, errors, stats = replay_sharded_crc(to_wire32(ev), mesh,
                                                DEFAULT_LAYOUT)
        from cadence_tpu.ops.replay import replay_to_payload
        import jax.numpy as jnp
        rows, _ = replay_to_payload(jnp.asarray(ev), DEFAULT_LAYOUT)
        assert (np.asarray(crc) == crc32_of_rows(np.asarray(rows))).all()
        assert int(stats[0]) == 0

    def test_overflow_refuses(self):
        ev = _corpus("basic", n=2)
        ev[0, 0, 4] = 1 << 40  # task_id lane beyond int32
        with pytest.raises(OverflowError):
            to_wire32(ev)

    def test_fused_generator_crc_matches_rows(self):
        from cadence_tpu.ops.genkernel import (
            generate_and_replay,
            generate_and_replay_crc,
        )

        rows, errors = generate_and_replay(11, 0, 64, 120, DEFAULT_LAYOUT)
        crc, errors2 = generate_and_replay_crc(11, 0, 64, 120, DEFAULT_LAYOUT)
        assert (np.asarray(crc) == crc32_of_rows(np.asarray(rows))).all()
        assert (np.asarray(errors2) == np.asarray(errors)).all()
