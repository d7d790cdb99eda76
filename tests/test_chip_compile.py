"""The main path's XLA programs, compiled for the chip that is not here.

The TPU's compiler is installed in the sandbox and compiles for a
DESCRIBED v5e (`jax.experimental.topologies`), so what it refuses —
a program that does not fit HBM, a typing rule `shard_map` enforces, an
operation it cannot lower — costs no chip time to find. Every program
`chip_smoke.py` runs is asked here, at the widths it runs them: the
wirec bulk kernels with a profile measured from a real suite, the dense
serving chunk kernel, the serving tier's from-state buckets and its
row-stacking program up to its `max_batch`, the fused generator kernel
on a mesh of 1 and of 4, the widened-K ladder rung, and the visibility
scans at a 2^20-row bucket.

There are no Pallas kernels in this repo; the risk is in programs that
had only ever met XLA's CPU backend with x64 on: int64 lanes are
emulated on the TPU, the scans carry wide per-workflow state, and the
payload CRC is a bf16 matrix product that must stay one (no gather).

A compile that passes is not a chip run and is never reported as one:
nothing executes, so this says nothing about results or times.

All in ONE file, topology described inside a module-scoped fixture (the
process that describes it holds libtpu's lock until it exits, so a
second file on another worker would skip in silence), compiled in the
test's own process, persistent cache off around them (an entry written
for a described device cannot be read back without one).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from cadence_tpu.core.checksum import DEFAULT_LAYOUT
from cadence_tpu.ops.encode import NUM_LANES

#: one v5e chip, and what the resident tier may pin of it
HBM_BYTES = 16 * 1024 ** 3
RESIDENT_BUDGET_BYTES = 256 * 1024 ** 2

#: chip_smoke.py's widths (its SIZES["full"]): suite width, the
#: executor's chunk, the fused kernel's event axis, the ladder's
#: flagged-row bucket (~2.7% of a suite, pow2), the visibility bucket
SUITE_W = 16384
CHUNK_W = 4096
FUSED_EVENTS = 1000
LADDER_W = 512
VIS_ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices), ("shard",))


@pytest.fixture(scope="module")
def wirec_shape():
    """(E, B, K, profile) of a real suite: the profile is a static
    argument of every wirec kernel, so a made-up one would compile a
    different program from the one the smoke runs."""
    from cadence_tpu.gen.corpus import generate_corpus
    from cadence_tpu.native.wirec import pack_wirec_auto
    from cadence_tpu.ops.encode import encode_corpus

    hist = generate_corpus("timer_retry", 256, seed=20260730,
                           target_events=120)
    corpus = pack_wirec_auto(encode_corpus(hist))
    _w, E, B = corpus.slab.shape
    return E, B, corpus.bases.shape[1], corpus.profile


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _wirec_args(W, shape, sharding_for):
    E, B, K, _profile = shape
    return (jax.ShapeDtypeStruct((W, E, B), jnp.uint8,
                                 sharding=sharding_for(3)),
            jax.ShapeDtypeStruct((W, K), jnp.int64,
                                 sharding=sharding_for(2)),
            jax.ShapeDtypeStruct((W,), jnp.int32,
                                 sharding=sharding_for(1)))


def _no_gather(compiled):
    """No `gather` instruction anywhere in the module. Counted over the
    whole text, not under the `transition` scope: the 64-bit split
    rewrites an int64 gather into u32 gathers whose metadata is the bare
    `op_name="gather"`, so a scoped count reads 0 with them there (the
    program before `ops/state.pick_branch` held 13: eight in the scan
    body, the branch picks, and five in `payload`)."""
    held = [line.strip()[:120] for line in compiled.as_text().splitlines()
            if " gather(" in line]
    assert not held, held


def _fits(compiled):
    """The program's own bytes on one device, against the chip's HBM
    less what the resident tier may hold there at the same time."""
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes)
    assert total < HBM_BYTES - RESIDENT_BUDGET_BYTES, total
    return total


@pytest.mark.parametrize("W", [CHUNK_W, SUITE_W])
def test_wirec_bulk_kernels(one_chip, wirec_shape, W):
    """replay_wirec_to_crc, and the variant with the cross-shard stats
    the serving executor streams (parallel/mesh), at chunk and at full
    suite width."""
    from cadence_tpu.ops.replay import replay_wirec_to_crc
    from cadence_tpu.parallel.mesh import _replay_wirec_crc_with_stats
    from tests.test_device_crc import ops_under_scope

    args = _wirec_args(W, wirec_shape, lambda nd: one_chip)
    profile = wirec_shape[3]
    for fn in (replay_wirec_to_crc, _replay_wirec_crc_with_stats):
        compiled = fn.lower(*args, profile, DEFAULT_LAYOUT).compile()
        _fits(compiled)
        # the chip's compiler keeps the CRC a product on the MXU: the
        # v5e serialises a lane gather (33.6 us for 4,096 lanes, PERF.md)
        ops = ops_under_scope(compiled.as_text(), "crc32")
        assert ops["convolution"] > 0, ops
        assert ops["gather"] == 0 and ops["while"] == 0, ops
        # and the transition picks its version-history branch by select
        _no_gather(compiled)


def test_wirec_stream_on_four_chips(mesh4, wirec_shape):
    """The same chunk with its workflow axis over a mesh of 4: each
    device holds a quarter, and the stats reduction is the one
    collective the compiler puts in."""
    from cadence_tpu.parallel.mesh import _replay_wirec_crc_with_stats

    def over(nd):
        return NamedSharding(mesh4, P("shard", *([None] * (nd - 1))))

    args = _wirec_args(CHUNK_W, wirec_shape, over)
    compiled = _replay_wirec_crc_with_stats.lower(
        *args, wirec_shape[3], DEFAULT_LAYOUT).compile()
    _fits(compiled)
    _no_gather(compiled)
    assert set(re.findall(
        r"all-reduce|all-gather|reduce-scatter|collective-permute|"
        r"all-to-all", compiled.as_text())) == {"all-reduce"}


def test_dense_serving_chunk(one_chip):
    """engine/executor.replay_corpus_mesh's chunk kernel (int64 lanes
    in, payload rows out)."""
    from cadence_tpu.ops.replay import replay_to_payload_branch

    events = jax.ShapeDtypeStruct((CHUNK_W, 128, NUM_LANES), jnp.int64,
                                  sharding=one_chip)
    _fits(replay_to_payload_branch.lower(events, DEFAULT_LAYOUT).compile())


@pytest.mark.parametrize("W,E", [(8, 16), (64, 16), (64, 128)])
def test_serving_from_state_buckets(one_chip, wirec_shape, W, E):
    """The serving tier's flush: from-state replay at its pow2 buckets,
    floor to max_batch (engine/serving.DEFAULT_BATCH = 64) and the
    widest warmed event axis — the payload form the tier launches, the
    CRC form, and the wirec suffix form the feeder appends with."""
    from cadence_tpu.ops.replay import (
        replay_from_state_to_crc,
        replay_from_state_to_payload,
        replay_wirec_from_state_to_crc,
    )
    from cadence_tpu.ops.state import init_state

    s0 = _shapes(jax.eval_shape(lambda: init_state(W, DEFAULT_LAYOUT)),
                 one_chip)
    events = jax.ShapeDtypeStruct((W, E, NUM_LANES), jnp.int64,
                                  sharding=one_chip)
    for fn in (replay_from_state_to_payload, replay_from_state_to_crc):
        _fits(fn.lower(events, s0, DEFAULT_LAYOUT).compile())
    _E, B, K, profile = wirec_shape
    slab, bases, n_events = _wirec_args(W, (E, B, K, profile),
                                        lambda nd: one_chip)
    _fits(replay_wirec_from_state_to_crc.lower(
        slab, bases, n_events, profile, s0, DEFAULT_LAYOUT).compile())


def test_serving_stack_at_max_batch(one_chip):
    """The flush's host plumbing: max_batch W=1 resident rows stacked
    into one launch state (engine/resident._stack_padded). Its operand
    count — rows x state leaves — is what the TPU's compiler is slow
    over, so the tier keeps it to one program per flush width; this is
    the widest of them."""
    from cadence_tpu.engine import resident
    from cadence_tpu.engine.serving import DEFAULT_BATCH
    from cadence_tpu.ops.state import init_state

    row = init_state(1, DEFAULT_LAYOUT)
    # whatever the row count, a flush stacks exactly `width` operands:
    # the program one row traced serves five and eight
    resident._stack_padded([row], 8)
    traced = resident._STACK_FN._cache_size()
    for k in (5, 8):
        stacked = resident._stack_padded([row] * k, 8)
        assert jax.tree_util.tree_leaves(stacked)[0].shape[0] == 8
    assert resident._STACK_FN._cache_size() == traced
    _fits(resident._STACK_FN.lower(
        [_shapes(row, one_chip)] * DEFAULT_BATCH).compile())


def test_warm_restart_stack_at_an_append_chunk(one_chip):
    """A warm restart's append launch: `chunk_workflows` W=1 rows, 2,048
    at the default, stacked STACK_BLOCK at a time by the serving flush's
    program (the test above compiles it) and the blocks joined by one more
    program of 32 operands a leaf. One program over all 2,048 rows x 66
    leaves takes this compiler hours (28 s at 64 rows, 308 s at 256,
    1,061 s at 512: PERF.md, PR 36)."""
    from cadence_tpu.engine import resident
    from cadence_tpu.ops.state import init_state

    blocks = resident.DEFAULT_CHUNK // resident.STACK_BLOCK
    assert resident.STACK_BLOCK == 64 and blocks == 32
    block = _shapes(init_state(resident.STACK_BLOCK, DEFAULT_LAYOUT),
                    one_chip)
    resident._stack_padded([init_state(1, DEFAULT_LAYOUT)], 8)  # builds it
    _fits(resident._STACK_FN.lower([block] * blocks).compile())


@pytest.mark.parametrize("n", [1, 4])
def test_fused_generator_kernel(topo, n):
    """ops/genkernel's shard_map kernel (generate + replay + CRC in one
    scan) on a mesh of 1 and of 4: a whole suite's width across the
    mesh, the smoke's event axis."""
    from cadence_tpu.ops.genkernel import _sharded_fn

    mesh = Mesh(np.asarray(topo.devices[:n]), ("shard",))
    fn = _sharded_fn(mesh, SUITE_W // n, FUSED_EVENTS, DEFAULT_LAYOUT,
                     to_crc=True)
    seed = jax.ShapeDtypeStruct((), jnp.int64,
                                sharding=NamedSharding(mesh, P()))
    offsets = jax.ShapeDtypeStruct((n,), jnp.int64,
                                   sharding=NamedSharding(mesh, P("shard")))
    _fits(fn.lower(seed, offsets).compile())


@pytest.mark.parametrize("rung", [1, 2])
def test_ladder_rung_at_widened_k(one_chip, wirec_shape, rung):
    """engine/ladder's rungs over a wirec sub-corpus: replay at 2x and 4x
    the pending-table capacities (B = 4 and 8 version-history branches),
    payload narrowed back to base width, CRC on device — and the branch
    pick still a chain of selects at those widths, no gather."""
    from cadence_tpu.ops.replay import replay_wirec_escalated_crc
    from cadence_tpu.ops.state import widen_layout

    args = _wirec_args(LADDER_W, wirec_shape, lambda nd: one_chip)
    wide = widen_layout(DEFAULT_LAYOUT, 2 ** rung)
    assert wide.max_branches == DEFAULT_LAYOUT.max_branches * 2 ** rung
    compiled = replay_wirec_escalated_crc.lower(
        *args, wirec_shape[3], wide, DEFAULT_LAYOUT).compile()
    _fits(compiled)
    _no_gather(compiled)


def test_visibility_scans_at_a_million_rows(one_chip):
    """ops/scan's Count, bitmap List and top-K page over one plan that
    touches every column kind (interned id, int64, float64 — the TPU has
    no native 64-bit float either), plus the delta scatter."""
    from cadence_tpu.ops import scan

    plan = scan.ScanPlan(
        ("and", ("and", 0, 1), 2),
        ((scan.COL_ID, scan.OP_EQ, 0), (scan.COL_I64, scan.OP_EQ, 1),
         (scan.COL_F64, scan.OP_GT, 2)),
        ("domain", "close_status", "attr:Priority"),
        np.zeros(3, np.int64), np.zeros(3, np.float64))

    def col(dtype, n=VIS_ROWS):
        return jax.ShapeDtypeStruct((n,), jnp.dtype(dtype),
                                    sharding=one_chip)

    cols = (col("int64"), col("int64"), col("float64"))
    params = (col("int64", 3), col("float64", 3))
    _fits(scan.build_count(plan).lower(cols, col("bool"),
                                       *params).compile())
    _fits(scan.build_bitmap(plan).lower(cols, col("bool"),
                                        *params).compile())
    _fits(scan.build_topk(plan, 128).lower(cols, col("bool"), col("int64"),
                                           *params).compile())
    dtypes = ("int64",) * 7 + ("float64", "bool")
    _fits(scan.build_apply(dtypes).lower(
        tuple(col(d) for d in dtypes), col("int64", 512),
        tuple(col(d, 512) for d in dtypes)).compile())
