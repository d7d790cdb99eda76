"""Device mesh + shardings: the TPU analog of the reference's shard fabric.

The reference scales by hashing workflow IDs onto history shards owned by
hosts via a consistent hashring (common/config/config.go:170-173,
membership/resolver.go:169, shard/controller.go). Here the same axis —
"which workflows live where" — is a sharded array dimension: workflows are
partitioned over the mesh's 'shard' axis and the replay kernel runs SPMD
with XLA inserting collectives only where results are aggregated (global
error counts, corpus-level checksums) — those ride ICI within a slice and
DCN across slices, replacing the reference's gRPC fan-out.

There are no weight tensors in a state-machine engine, so tensor/expert
parallelism do not apply; the event axis is inherently sequential per
workflow (scan), handled by host-side event-chunk streaming (the P6/P7
pipeline analog, see SURVEY.md §2.6).
"""
from __future__ import annotations

import os
import zlib
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.checksum import DEFAULT_LAYOUT, PayloadLayout
from ..ops.payload import payload_rows
from ..ops.replay import replay_events

SHARD_AXIS = "shard"

#: serving-mesh width knob: how many devices the SERVING hot path
#: (engine/executor.py replay paths, verify/rebuild/feeder/bench) shards
#: across. Unset/1 = single-chip (byte-identical to the pre-mesh
#: executor); 0 or "all" = every visible device; n = the first n.
MESH_DEVICES_ENV = "CADENCE_TPU_MESH_DEVICES"


def make_mesh(devices: Optional[list] = None) -> Mesh:
    """1D mesh over all (or given) devices; axis 'shard' partitions the
    workflow axis, mirroring numHistoryShards→host assignment (P1)."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (SHARD_AXIS,))


def mesh_devices_requested() -> int:
    """Parse the CADENCE_TPU_MESH_DEVICES knob WITHOUT touching a JAX
    backend (callers like ServiceHost pre-register metrics before any
    device work): 0 means "all visible devices", otherwise a count with
    a floor of 1."""
    raw = os.environ.get(MESH_DEVICES_ENV, "1").strip().lower()
    if raw in ("all", "pod"):
        return 0
    try:
        n = int(raw)
    except ValueError:
        return 1
    return 0 if n == 0 else max(1, n)


def serving_mesh(devices: Optional[list] = None) -> Mesh:
    """The serving executor's mesh, resolved from the env knob: the one
    mesh verify/rebuild/feeder/bench fan their chunks across. Defaults
    to a mesh of 1 so unconfigured deployments stay byte-identical to
    the single-chip executor."""
    if devices is None:
        n = mesh_devices_requested()
        devices = jax.devices()
        if n:
            devices = devices[:min(n, len(devices))]
    return make_mesh(devices)


def workflow_shard(key: Tuple[str, str, str], n_shards: int) -> int:
    """Stable workflow→shard assignment over the mesh — the device-mesh
    analog of the reference's workflowID→historyShard hash
    (common/config numHistoryShards): the same key always lands on the
    same mesh position, so per-device state (the sharded resident pool)
    stays on its owning device across calls."""
    if n_shards <= 1:
        return 0
    return zlib.crc32("|".join(key).encode()) % n_shards


def place_corpus(array: np.ndarray, mesh: Mesh) -> jnp.ndarray:
    """Per-device H2D staging of any leading-workflow-axis array: the
    device_put against a NamedSharding splits the HOST array and copies
    each shard slice to its own device — N parallel transfers instead of
    one chip absorbing the whole corpus."""
    spec = P(SHARD_AXIS, *([None] * (np.ndim(array) - 1)))
    return jax.device_put(array, NamedSharding(mesh, spec))


def shard_events(events: jnp.ndarray, mesh: Mesh) -> jnp.ndarray:
    """Place [W, E, L] events with W partitioned over the 'shard' axis."""
    return jax.device_put(events, NamedSharding(mesh, P(SHARD_AXIS, None, None)))


@partial(jax.jit, static_argnames=("layout",))
def _replay_with_stats(ev: jnp.ndarray, layout: PayloadLayout):
    s = replay_events(ev, layout)
    rows = payload_rows(s, layout)
    # cross-shard aggregation — XLA lowers to all-reduce over the mesh
    stats = jnp.stack([
        (s.error != 0).sum().astype(jnp.int64),
        (s.close_status != 0).sum().astype(jnp.int64),
    ])
    return rows, s.error, stats


def replay_sharded(events: jnp.ndarray, mesh: Mesh,
                   layout: PayloadLayout = DEFAULT_LAYOUT
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """SPMD replay over the mesh.

    Returns (payload_rows [W, width] sharded, errors [W] sharded,
    global_stats [2] replicated = [total_errors, total_closed]); the stats
    reduction is the cross-shard collective (psum over ICI), standing in for
    the reference's shard-level ack aggregation.
    """
    events = shard_events(events, mesh)
    # input NamedShardings propagate through jit; no global mesh needed
    return _replay_with_stats(events, layout)


@partial(jax.jit, static_argnames=("profile", "layout"))
def _replay_wirec_crc_with_stats(slab, bases, n_events, profile,
                                 layout: PayloadLayout):
    from ..ops.crc import crc32_rows
    from ..ops.replay import replay_wirec

    s = replay_wirec(slab, bases, n_events, profile, layout)
    rows = payload_rows(s, layout)
    stats = jnp.stack([
        (s.error != 0).sum().astype(jnp.int64),
        (s.close_status != 0).sum().astype(jnp.int64),
    ])
    return crc32_rows(rows), s.error, stats


def shard_wirec(corpus, mesh: Mesh):
    """Place a WirecCorpus's arrays with W partitioned over 'shard'."""
    w_spec = lambda nd: NamedSharding(mesh, P(SHARD_AXIS, *([None] * (nd - 1))))
    return (jax.device_put(corpus.slab, w_spec(3)),
            jax.device_put(corpus.bases, w_spec(2)),
            jax.device_put(corpus.n_events, w_spec(1)))


def replay_wirec_sharded_crc(corpus, mesh: Mesh,
                             layout: PayloadLayout = DEFAULT_LAYOUT
                             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """SPMD wirec replay: the compressed slab (~10-18 B/event) is what
    crosses the host link; decode + replay + CRC all on device."""
    slab, bases, n_events = shard_wirec(corpus, mesh)
    return _replay_wirec_crc_with_stats(slab, bases, n_events,
                                        corpus.profile, layout)


# ---------------------------------------------------------------------------
# Capacity-escalation rungs under the shard axis (engine/ladder.py): the
# flagged-row sub-corpus re-replays at widened K partitioned over the SAME
# 'shard' axis as the primary replay — capacity pressure stays SPMD on
# device instead of funnelling flagged rows to a per-workflow host oracle.
# The sub-corpus is padded to a multiple of the mesh size (padding rows
# are no-op lanes), so every shard re-replays its slice of the flagged set.
# ---------------------------------------------------------------------------


def replay_sharded_escalated(events: jnp.ndarray, mesh: Mesh,
                             layout: PayloadLayout,
                             out_layout: PayloadLayout = DEFAULT_LAYOUT
                             ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                        jnp.ndarray, jnp.ndarray]:
    """SPMD widened-K re-replay of a flagged sub-corpus; returns (rows
    [F, out_width] at the BASE payload width, errors [F], narrow-overflow
    [F], current branch [F]), all sharded over 'shard'."""
    from ..ops.replay import replay_escalated

    events = shard_events(events, mesh)
    return replay_escalated(events, layout, out_layout)


def replay_wirec_sharded_escalated_crc(corpus, mesh: Mesh,
                                       layout: PayloadLayout,
                                       out_layout: PayloadLayout = DEFAULT_LAYOUT
                                       ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                                  jnp.ndarray]:
    """SPMD widened-K wirec re-replay reduced to (crc32 [F] uint32 at the
    base payload width, errors [F], narrow-overflow [F])."""
    from ..ops.replay import replay_wirec_escalated_crc

    slab, bases, n_events = shard_wirec(corpus, mesh)
    return replay_wirec_escalated_crc(slab, bases, n_events,
                                      corpus.profile, layout, out_layout)
