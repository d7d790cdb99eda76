"""Device-side corpus generator (ops/genkernel.py).

The north-star bench's data source: distinct histories generated inside
the same scan that replays them. Contracts tested here:
- reproducible + distinct per (seed, workflow_index);
- the fused generate_and_replay path equals materialize-then-replay;
- generated histories are ORACLE-valid (decode → StateBuilder replay →
  payload parity with the device);
- chunking by first_index is seamless (chunked == one-shot).
"""
import numpy as np
import pytest

from cadence_tpu.core.checksum import STICKY_ROW_INDEX, payload_row
from cadence_tpu.core.enums import EventType, WorkflowState
from cadence_tpu.ops.encode import decode_lanes
from cadence_tpu.ops.genkernel import generate_and_replay, generate_lanes
from cadence_tpu.ops.replay import replay_to_payload
from cadence_tpu.oracle.state_builder import StateBuilder

W, E = 32, 120


@pytest.fixture(scope="module")
def lanes():
    return np.asarray(generate_lanes(42, 0, W, E))


class TestGenerator:
    def test_reproducible_and_distinct(self, lanes):
        again = np.asarray(generate_lanes(42, 0, W, E))
        assert (lanes == again).all()
        assert len({lanes[i].tobytes() for i in range(W)}) == W
        other_seed = np.asarray(generate_lanes(43, 0, W, E))
        assert not (lanes == other_seed).all()

    def test_every_slot_is_a_real_event(self, lanes):
        assert (lanes[:, :, 0] > 0).all()
        # ids are 1..E in order
        assert (lanes[:, :, 0] == np.arange(1, E + 1)[None, :]).all()

    def test_histories_start_and_close(self, lanes):
        assert (lanes[:, 0, 1] == int(EventType.WorkflowExecutionStarted)).all()
        assert (lanes[:, 1, 1] == int(EventType.DecisionTaskScheduled)).all()
        assert (lanes[:, -1, 1]
                == int(EventType.WorkflowExecutionCompleted)).all()

    def test_fused_equals_materialized(self, lanes):
        import jax.numpy as jnp

        rows_m, err_m = map(np.asarray,
                            replay_to_payload(jnp.asarray(lanes)))
        rows_f, err_f = map(np.asarray, generate_and_replay(42, 0, W, E))
        assert (err_m == 0).all() and (err_f == err_m).all()
        assert (rows_f == rows_m).all()

    def test_oracle_parity(self, lanes):
        rows, errors = map(np.asarray, generate_and_replay(42, 0, W, E))
        assert (errors == 0).all()
        for i in range(W):
            ms = StateBuilder().replay_history(decode_lanes(lanes[i]))
            expected = payload_row(ms)
            expected[STICKY_ROW_INDEX] = 0
            assert (rows[i] == expected).all(), f"workflow {i} diverged"
            assert ms.execution_info.state == WorkflowState.Completed
            # every pending entity resolved before the close
            assert not ms.pending_activity_info_ids
            assert not ms.pending_timer_info_ids
            assert not ms.pending_child_execution_info_ids

    def test_chunked_indices_are_seamless(self):
        """first_index chunking reproduces the one-shot stream: workflow w
        depends only on (seed, w), never on chunk boundaries."""
        whole, _ = map(np.asarray, generate_and_replay(7, 0, 16, E))
        lo, _ = map(np.asarray, generate_and_replay(7, 0, 8, E))
        hi, _ = map(np.asarray, generate_and_replay(7, 8, 8, E))
        assert (whole == np.concatenate([lo, hi])).all()

    def test_sharded_equals_single_device(self):
        """The bench's multi-chip path: shard_map over the 8-device mesh
        produces the identical rows/errors as the one-device kernel."""
        import jax

        from cadence_tpu.ops.genkernel import generate_and_replay_sharded
        from cadence_tpu.parallel.mesh import make_mesh

        devices = jax.devices()
        assert len(devices) >= 8  # conftest forces the CPU 8-device mesh
        mesh = make_mesh(devices[:8])
        rows_s, err_s = map(np.asarray,
                            generate_and_replay_sharded(11, 0, 64, E, mesh))
        rows_1, err_1 = map(np.asarray, generate_and_replay(11, 0, 64, E))
        assert (err_s == err_1).all()
        assert (rows_s == rows_1).all()

        with pytest.raises(ValueError):
            generate_and_replay_sharded(11, 0, 65, E, mesh)

    def test_fused_generator_crc_matches_rows(self):
        """The fused generate+replay kernel reduced to CRCs on device
        checksums exactly the rows its unreduced twin returns."""
        from cadence_tpu.core.checksum import crc32_of_rows
        from cadence_tpu.ops.genkernel import generate_and_replay_crc

        rows, errors = generate_and_replay(11, 0, 64, E)
        crc, errors2 = generate_and_replay_crc(11, 0, 64, E)
        assert (np.asarray(crc) == crc32_of_rows(np.asarray(rows))).all()
        assert (np.asarray(errors2) == np.asarray(errors)).all()

    @pytest.mark.parametrize("n", [1, 8])
    def test_sharded_crc_equals_single_device(self, n):
        """The north-star form (chip_smoke.py): the shard_map
        kernel reduced to CRCs on device. Inside shard_map the rows vary
        across the mesh axis and the CRC's matrix is replicated; their
        product is typed varying like the rows — on a mesh of 1 too."""
        import jax

        from cadence_tpu.core.checksum import crc32_of_rows
        from cadence_tpu.ops.genkernel import (
            generate_and_replay_sharded_crc,
        )
        from cadence_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(jax.devices()[:n])
        crc_s, err_s = map(np.asarray, generate_and_replay_sharded_crc(
            11, 0, 64, E, mesh))
        rows_1, err_1 = map(np.asarray, generate_and_replay(11, 0, 64, E))
        assert (err_s == err_1).all()
        assert (crc_s.astype(np.uint32) == crc32_of_rows(rows_1)).all()
