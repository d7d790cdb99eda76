"""Device-side corpus generator: distinct histories born where they replay.

The north-star bench needs 1M x 1k-event DISTINCT histories. Generating
them on host and shipping 144GB of lanes through the host→device link
makes the link the benchmark; the TPU-first formulation generates each
event ON DEVICE inside the same `lax.scan` that replays it — a stochastic
workflow simulator (per-workflow counter-based splitmix64 stream, fully
reproducible from (seed, workflow_index, step)) emitting one event per
workflow per step, fused with the transition kernel so the corpus never
materializes anywhere.

The emitted sequences follow engine-shaped rules: start → decision cycles
(scheduled → started → completed) interleaved with activity
schedule/start/close chains, user timers, child workflows, and signals;
every pending entity resolves before the close, capacities stay below the
kernel's tables, and every history ends with WorkflowExecutionCompleted.
`generate_lanes` materializes the identical rows (same RNG stream) for
small samples so the ORACLE can replay and cross-check payloads
(ops/encode.decode_lanes) — the spot-parity contract.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..core.checksum import DEFAULT_LAYOUT, PayloadLayout
from ..core.enums import EventType
from .encode import (
    LANE_A0,
    LANE_BATCH_FIRST,
    LANE_BATCH_LAST,
    LANE_EVENT_ID,
    LANE_EVENT_TYPE,
    LANE_TASK_ID,
    LANE_TIMESTAMP,
    LANE_VERSION,
    NUM_LANES,
)

I64 = jnp.int64
NANOS_MS = 1_000_000


class GenState(NamedTuple):
    ts: jnp.ndarray           # [W] i64 nanos
    phase: jnp.ndarray        # [W] i32: 0 none, 1 scheduled, 2 started
    dsched: jnp.ndarray       # [W] i64
    dstart: jnp.ndarray       # [W] i64
    act_occ: jnp.ndarray      # [W, 4] bool
    act_sched: jnp.ndarray    # [W, 4] i64
    act_started: jnp.ndarray  # [W, 4] bool
    act_count: jnp.ndarray    # [W] i64 (interned-key counter)
    tmr_occ: jnp.ndarray      # [W, 3] bool
    tmr_key: jnp.ndarray      # [W, 3] i64
    tmr_count: jnp.ndarray    # [W] i64
    ch_occ: jnp.ndarray       # [W, 2] bool
    ch_init: jnp.ndarray      # [W, 2] i64
    ch_started: jnp.ndarray   # [W, 2] bool


# action codes
A_STARTED, A_DSCHED, A_DSTART, A_DCOMPLETE = 0, 1, 2, 3
A_ASCHED, A_ASTART, A_ACLOSE = 4, 5, 6
A_TSTART, A_TFIRE = 7, 8
A_CINIT, A_CSTART, A_CCLOSE = 9, 10, 11
A_SIGNAL, A_WFCLOSE = 12, 13

_CODE_TO_TYPE = jnp.array([
    int(EventType.WorkflowExecutionStarted),
    int(EventType.DecisionTaskScheduled),
    int(EventType.DecisionTaskStarted),
    int(EventType.DecisionTaskCompleted),
    int(EventType.ActivityTaskScheduled),
    int(EventType.ActivityTaskStarted),
    int(EventType.ActivityTaskCompleted),
    int(EventType.TimerStarted),
    int(EventType.TimerFired),
    int(EventType.StartChildWorkflowExecutionInitiated),
    int(EventType.ChildWorkflowExecutionStarted),
    int(EventType.ChildWorkflowExecutionCompleted),
    int(EventType.WorkflowExecutionSignaled),
    int(EventType.WorkflowExecutionCompleted),
], dtype=I64)


def _mix(seed: jnp.ndarray, w: jnp.ndarray, step, salt: int) -> jnp.ndarray:
    """splitmix64-style counter hash; int64 wraparound is the ring."""
    z = (seed + w * jnp.int64(-7046029254386353131)
         + jnp.int64(step) * jnp.int64(6364136223846793005)
         + jnp.int64(salt) * jnp.int64(1442695040888963407))
    z = (z ^ (z >> 30)) * jnp.int64(-4658895280553007687)
    z = (z ^ (z >> 27)) * jnp.int64(-7723592293110705685)
    return z ^ (z >> 31)


def _die(r: jnp.ndarray, n: int) -> jnp.ndarray:
    return jnp.abs(r) % n


def _first(mask: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(onehot of first True per row, any per row)."""
    K = mask.shape[1]
    idx = jnp.argmax(mask, axis=1)
    onehot = (jnp.arange(K)[None, :] == idx[:, None]) & mask.any(
        axis=1)[:, None]
    return onehot, mask.any(axis=1)


def init_gen_state(num_workflows: int, seed: int,
                   first_index: int) -> GenState:
    W = num_workflows
    w = jnp.arange(W, dtype=I64) + jnp.int64(first_index)
    jitter = jnp.abs(_mix(jnp.int64(seed), w, 0, 17)) % 1_000_000
    return GenState(
        ts=jnp.int64(1_700_000_000_000_000_000) + jitter * NANOS_MS,
        phase=jnp.zeros((W,), jnp.int32),
        dsched=jnp.zeros((W,), I64),
        dstart=jnp.zeros((W,), I64),
        act_occ=jnp.zeros((W, 4), bool),
        act_sched=jnp.zeros((W, 4), I64),
        act_started=jnp.zeros((W, 4), bool),
        act_count=jnp.zeros((W,), I64),
        tmr_occ=jnp.zeros((W, 3), bool),
        tmr_key=jnp.zeros((W, 3), I64),
        tmr_count=jnp.zeros((W,), I64),
        ch_occ=jnp.zeros((W, 2), bool),
        ch_init=jnp.zeros((W, 2), I64),
        ch_started=jnp.zeros((W, 2), bool),
    )


def gen_step(g: GenState, seed: int, first_index: int, step: int,
             total_events: int):
    """Emit event lanes [W, NUM_LANES] for scan step `step` and advance the
    generator state. Every workflow emits exactly one REAL event per step;
    ids are therefore step+1 for all workflows."""
    W = g.ts.shape[0]
    w = jnp.arange(W, dtype=I64) + jnp.int64(first_index)
    s = jnp.int64(seed)
    r0 = _mix(s, w, step, 1)
    r1 = _mix(s, w, step, 2)
    r2 = _mix(s, w, step, 3)
    r3 = _mix(s, w, step, 4)

    eid = jnp.full((W,), step + 1, I64)
    ts = g.ts + (_die(r3, 5000) + 1) * NANOS_MS

    pending = (g.act_occ.sum(axis=1) + g.tmr_occ.sum(axis=1)
               + g.ch_occ.sum(axis=1)).astype(I64)
    # unstarted activities/children need TWO drain events (start, close):
    # the engine never produces a Completed event for an unstarted item,
    # so the drain must not either (generator.cc mirrors this)
    n_unstarted = ((g.act_occ & ~g.act_started).sum(axis=1)
                   + (g.ch_occ & ~g.ch_started).sum(axis=1)).astype(I64)
    remaining = jnp.int64(total_events - step)
    # margin 4: one normal step can grow pending+n_unstarted by 2 (a
    # schedule/init event creates an occupied AND unstarted item) while
    # remaining drops 1, overshooting a tighter threshold by 2
    drain = remaining <= pending + n_unstarted + 4

    # -- choose the action code -------------------------------------------
    # normal mode by decision phase
    die = _die(r0, 16)
    die2 = _die(r1, 8)
    act_free = ~g.act_occ.all(axis=1)
    act_unstarted = (g.act_occ & ~g.act_started).any(axis=1)
    act_any = g.act_occ.any(axis=1)
    # closes only ever land on STARTED items: the engine cannot produce
    # ActivityTaskCompleted / ChildWorkflowExecutionCompleted without a
    # preceding Started event (state_builder.go replicate order)
    act_started_any = (g.act_occ & g.act_started).any(axis=1)
    tmr_free = ~g.tmr_occ.all(axis=1)
    tmr_any = g.tmr_occ.any(axis=1)
    ch_free = ~g.ch_occ.all(axis=1)
    ch_unstarted = (g.ch_occ & ~g.ch_started).any(axis=1)
    ch_any = g.ch_occ.any(axis=1)
    ch_started_any = (g.ch_occ & g.ch_started).any(axis=1)

    external = jnp.select(
        [die2 <= 1, die2 == 2, die2 == 3, die2 == 4, die2 == 5,
         die2 == 6, die2 == 7],
        [jnp.where(act_free, A_ASCHED, A_SIGNAL),
         jnp.where(act_unstarted, A_ASTART, A_SIGNAL),
         jnp.where(act_started_any, A_ACLOSE, A_SIGNAL),
         jnp.where(tmr_free, A_TSTART,
                   jnp.where(tmr_any, A_TFIRE, A_SIGNAL)),
         jnp.where(tmr_any, A_TFIRE, A_SIGNAL),
         jnp.where(ch_free, A_CINIT,
                   jnp.where(ch_started_any, A_CCLOSE, A_SIGNAL)),
         jnp.where(ch_unstarted, A_CSTART,
                   jnp.where(ch_started_any, A_CCLOSE, A_SIGNAL))],
        A_SIGNAL)
    normal = jnp.select(
        [g.phase == 1, g.phase == 2],
        [jnp.where(die < 13, A_DSTART, A_SIGNAL),
         jnp.where(die < 6, A_DCOMPLETE, external)],
        jnp.where(die < 8, A_DSCHED, external))

    # start-before-close within each family: closes pick the FIRST occupied
    # slot, and all starts precede all closes, so a close never lands on an
    # unstarted item — the history shape the real engine produces
    drained = jnp.select(
        [act_unstarted, act_any, ch_unstarted, tmr_any, ch_any,
         remaining > 1],
        [A_ASTART, A_ACLOSE, A_CSTART, A_TFIRE, A_CCLOSE, A_SIGNAL],
        A_WFCLOSE)

    code = jnp.where(drain, drained, normal)
    code = jnp.where(eid == 1, A_STARTED, code)
    code = jnp.where(eid == 2, A_DSCHED, code)

    def m(k):
        return code == k

    # -- per-action state updates + attr lanes ----------------------------
    a = [jnp.zeros((W,), I64) for _ in range(8)]

    # Started
    a[0] = jnp.where(m(A_STARTED), 600 + _die(r2, 6600), a[0])
    a[1] = jnp.where(m(A_STARTED), 10, a[1])
    a[7] = jnp.where(m(A_STARTED), -1, a[7])

    # decision machine
    a[0] = jnp.where(m(A_DSCHED), 10, a[0])
    phase = jnp.where(m(A_DSCHED), 1, g.phase)
    dsched = jnp.where(m(A_DSCHED), eid, g.dsched)
    a[0] = jnp.where(m(A_DSTART), dsched, a[0])
    phase = jnp.where(m(A_DSTART), 2, phase)
    dstart = jnp.where(m(A_DSTART), eid, g.dstart)
    a[0] = jnp.where(m(A_DCOMPLETE), dsched, a[0])
    a[1] = jnp.where(m(A_DCOMPLETE), dstart, a[1])
    phase = jnp.where(m(A_DCOMPLETE), 0, phase)

    # activities
    ins, _ = _first(~g.act_occ)
    ins = ins & m(A_ASCHED)[:, None]
    act_occ = g.act_occ | ins
    act_sched = jnp.where(ins, eid[:, None], g.act_sched)
    act_started = g.act_started & ~ins
    act_count = g.act_count + m(A_ASCHED)
    a[0] = jnp.where(m(A_ASCHED), act_count, a[0])       # interned key
    a[1] = jnp.where(m(A_ASCHED), 5 + _die(r2, 115), a[1])
    a[2] = jnp.where(m(A_ASCHED), 30 + _die(r2, 570), a[2])
    a[3] = jnp.where(m(A_ASCHED), 10 + _die(r3, 290), a[3])

    sel, _ = _first(act_occ & ~act_started)
    sel = sel & m(A_ASTART)[:, None]
    a[0] = jnp.where(m(A_ASTART),
                     jnp.where(sel, act_sched, 0).sum(axis=1), a[0])
    act_started = act_started | sel

    sel, _ = _first(act_occ & act_started)
    sel = sel & m(A_ACLOSE)[:, None]
    a[0] = jnp.where(m(A_ACLOSE),
                     jnp.where(sel, act_sched, 0).sum(axis=1), a[0])
    act_occ = act_occ & ~sel
    act_started = act_started & ~sel

    # timers
    ins, _ = _first(~g.tmr_occ)
    ins = ins & m(A_TSTART)[:, None]
    tmr_count = g.tmr_count + m(A_TSTART)
    tmr_occ = g.tmr_occ | ins
    tmr_key = jnp.where(ins, tmr_count[:, None], g.tmr_key)
    a[0] = jnp.where(m(A_TSTART), tmr_count, a[0])
    a[1] = jnp.where(m(A_TSTART), 1 + _die(r2, 600), a[1])

    sel, _ = _first(tmr_occ)
    sel = sel & m(A_TFIRE)[:, None]
    a[0] = jnp.where(m(A_TFIRE),
                     jnp.where(sel, tmr_key, 0).sum(axis=1), a[0])
    tmr_occ = tmr_occ & ~sel

    # children
    ins, _ = _first(~g.ch_occ)
    ins = ins & m(A_CINIT)[:, None]
    ch_occ = g.ch_occ | ins
    ch_init = jnp.where(ins, eid[:, None], g.ch_init)
    ch_started = g.ch_started & ~ins

    sel, _ = _first(ch_occ & ~ch_started)
    sel = sel & m(A_CSTART)[:, None]
    a[0] = jnp.where(m(A_CSTART),
                     jnp.where(sel, ch_init, 0).sum(axis=1), a[0])
    ch_started = ch_started | sel

    sel, _ = _first(ch_occ & ch_started)
    sel = sel & m(A_CCLOSE)[:, None]
    a[0] = jnp.where(m(A_CCLOSE),
                     jnp.where(sel, ch_init, 0).sum(axis=1), a[0])
    ch_occ = ch_occ & ~sel
    ch_started = ch_started & ~sel

    # -- assemble lanes ----------------------------------------------------
    lanes = jnp.zeros((W, NUM_LANES), I64)
    lanes = lanes.at[:, LANE_EVENT_ID].set(eid)
    lanes = lanes.at[:, LANE_EVENT_TYPE].set(_CODE_TO_TYPE[code])
    lanes = lanes.at[:, LANE_VERSION].set(0)
    lanes = lanes.at[:, LANE_TIMESTAMP].set(ts)
    lanes = lanes.at[:, LANE_TASK_ID].set(eid + 1000)
    lanes = lanes.at[:, LANE_BATCH_FIRST].set(eid)  # one event per batch
    lanes = lanes.at[:, LANE_BATCH_LAST].set(1)
    for i in range(8):
        lanes = lanes.at[:, LANE_A0 + i].set(a[i])

    return GenState(ts=ts, phase=phase, dsched=dsched, dstart=dstart,
                    act_occ=act_occ, act_sched=act_sched,
                    act_started=act_started, act_count=act_count,
                    tmr_occ=tmr_occ, tmr_key=tmr_key, tmr_count=tmr_count,
                    ch_occ=ch_occ, ch_init=ch_init,
                    ch_started=ch_started), lanes


@partial(jax.jit, static_argnames=("num_workflows", "total_events"))
def generate_lanes(seed: int, first_index: int, num_workflows: int,
                   total_events: int) -> jnp.ndarray:
    """Materialize [W, E, L] lanes (for samples, tests, and oracle
    cross-checks — identical to what the fused path replays)."""
    g0 = init_gen_state(num_workflows, seed, first_index)

    def body(g, step):
        g, lanes = gen_step(g, seed, first_index, step, total_events)
        return g, lanes

    _, lanes = jax.lax.scan(body, g0, jnp.arange(total_events), unroll=2)
    return jnp.swapaxes(lanes, 0, 1)  # [W, E, L]


def _fused_scan(g0, s0, seed, first_index, total_events: int,
                layout: PayloadLayout, to_crc: bool = False):
    from .payload import payload_rows
    from .transitions import step as replay_step

    def body(carry, step):
        g, s = carry
        g, lanes = gen_step(g, seed, first_index, step, total_events)
        # the generator never emits FLAG_RUN_RESET: compile the
        # run-boundary blend out (also keeps shard_map happy — see step())
        s = replay_step(s, lanes, enable_reset=False)
        return (g, s), None

    (_, s), _ = jax.lax.scan(body, (g0, s0), jnp.arange(total_events),
                             unroll=2)
    rows = payload_rows(s, layout)
    if to_crc:
        # checksum on chip: the host pulls 4 bytes/workflow, not the row —
        # bytes over the host link are the scarce resource
        from .crc import crc32_rows
        return crc32_rows(rows), s.error
    return rows, s.error


@partial(jax.jit, static_argnames=("num_workflows", "total_events", "layout"))
def generate_and_replay(seed: int, first_index: int, num_workflows: int,
                        total_events: int,
                        layout: PayloadLayout = DEFAULT_LAYOUT):
    """The fused north-star step: generate each event and apply it to the
    replay state in the SAME scan iteration — the corpus never exists as a
    tensor. Returns (payload rows [W, width], errors [W])."""
    from .state import init_state

    g0 = init_gen_state(num_workflows, seed, first_index)
    s0 = init_state(num_workflows, layout)
    return _fused_scan(g0, s0, seed, first_index, total_events, layout)


@partial(jax.jit, static_argnames=("num_workflows", "total_events", "layout"))
def generate_and_replay_crc(seed: int, first_index: int, num_workflows: int,
                            total_events: int,
                            layout: PayloadLayout = DEFAULT_LAYOUT):
    """Fused north-star step reduced to (crc32 [W] uint32, errors [W]):
    generation, replay, canonical payload, and checksum all on device —
    the host pulls 4 bytes per workflow."""
    from .state import init_state

    g0 = init_gen_state(num_workflows, seed, first_index)
    s0 = init_state(num_workflows, layout)
    return _fused_scan(g0, s0, seed, first_index, total_events, layout,
                       to_crc=True)


#: compiled sharded executables keyed by (mesh, local_W, E, layout) —
#: rebuilt closures would defeat the jit cache and recompile every call
_SHARDED_CACHE: dict = {}


def _sharded_fn(mesh, local: int, total_events: int,
                layout: PayloadLayout, to_crc: bool = False):
    from jax.sharding import PartitionSpec as P

    from .state import init_state

    key = (mesh, local, total_events, layout, to_crc)
    fn = _SHARDED_CACHE.get(key)
    if fn is not None:
        return fn

    def local_fn(seed, offset):
        first = offset[0]
        # mark the constant-built initial carries as varying across the
        # mesh (each shard's trajectory differs), or scan/cond typing
        # rejects the mix of replicated carries with shard-varying lanes
        def varying(tree):
            def pv(x):
                # only lift replicated leaves; some (built from the traced
                # offset) are already shard-varying
                if "shard" in jax.typeof(x).vma:
                    return x
                return jax.lax.pcast(x, ("shard",), to="varying")
            return jax.tree_util.tree_map(pv, tree)

        g0 = varying(init_gen_state(local, seed, first))
        s0 = varying(init_state(local, layout))
        return _fused_scan(g0, s0, seed, first, total_events, layout,
                           to_crc=to_crc)

    fn = jax.jit(jax.shard_map(local_fn, mesh=mesh,
                               in_specs=(None, P("shard")),
                               out_specs=(P("shard"), P("shard"))))
    _SHARDED_CACHE[key] = fn
    return fn


def generate_and_replay_sharded(seed: int, first_index: int,
                                num_workflows: int, total_events: int,
                                mesh,
                                layout: PayloadLayout = DEFAULT_LAYOUT):
    """SPMD north-star step over a device mesh: every device runs the fused
    generator+replay on its own workflow-index range (pure data
    parallelism — per-workflow RNG streams make shards independent), so a
    multi-chip host actually exercises all chips. Workflow count must
    divide by the mesh size. Identical outputs to the single-device path
    for the same (seed, index) range. The compiled executable is cached
    per (mesh, shape): seed and offsets are traced arguments, so repeated
    chunks reuse it."""
    n = mesh.devices.size
    if num_workflows % n:
        raise ValueError(f"workflows {num_workflows} not divisible by "
                         f"mesh size {n}")
    local = num_workflows // n
    offsets = jnp.asarray(first_index + jnp.arange(n) * local, I64)
    fn = _sharded_fn(mesh, local, total_events, layout)
    return fn(jnp.int64(seed), offsets)


def generate_and_replay_sharded_crc(seed: int, first_index: int,
                                    num_workflows: int, total_events: int,
                                    mesh,
                                    layout: PayloadLayout = DEFAULT_LAYOUT):
    """SPMD fused step reduced on device to (crc32 [W], errors [W])."""
    n = mesh.devices.size
    if num_workflows % n:
        raise ValueError(f"workflows {num_workflows} not divisible by "
                         f"mesh size {n}")
    local = num_workflows // n
    offsets = jnp.asarray(first_index + jnp.arange(n) * local, I64)
    fn = _sharded_fn(mesh, local, total_events, layout, to_crc=True)
    return fn(jnp.int64(seed), offsets)
