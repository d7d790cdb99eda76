"""Capacity-escalation ladder: overflow pressure stays on device.

The reference never degrades on capacity — its pending maps are unbounded
Go maps (mutable_state_builder.go) — but the kernel's tables are fixed at
PayloadLayout's K, so a workflow that transiently holds more than K
pending items flags TABLE_OVERFLOW and, before this module, exited the
batched kernel into a per-workflow Python oracle: a few percent of
flagged workflows set the pace of the whole corpus.

The ladder replaces that scalar leg with batched device work: rows
flagged with a CAPACITY error (ops/state.CAPACITY_ERRORS) are gathered
into a compact sub-corpus (ops/encode.gather_subcorpus /
ops/wirec.gather_corpus) and re-replayed ON DEVICE with every capacity
doubled — K→2K→4K up a bounded rung ladder — then projected back to the
BASE payload width (ops/payload.payload_rows_narrow), so resolved rows
hash byte-identically to what the oracle would have produced. Only rows
that still overflow at the top rung (or whose FINAL state exceeds the
canonical payload itself, or whose error no capacity can fix) remain for
oracle arbitration — measured, counted, never silent.

Costs are amortized and observable:
- each (rung, wire format, padded shape) kernel variant is one extra
  compile, registered in utils/compile_cache.KernelVariantCache — warm
  runs pay zero recompiles and the hit/miss counters prove it;
- sub-corpus shapes are pow2-bucketed (workflow AND event axes), so
  run-to-run wobble in the flagged count reuses the same executable;
- counters land under `tpu.fallback/*` (rows per rung, rung compiles,
  resolved/residual rows); the host time blocked on a rung's results is
  the profiler's `fallback` leg, a span of the one recorder
  (utils/tracing.py) like `kernel` and `readback`. On a timeline the
  ladder's host work reads `feed.ladder.gather` (flagged rows copied
  into a sub-corpus), `feed.ladder.submit` (pad + H2D + launch of a
  rung) and `feed.ladder.device-wait` (the `fallback` leg), under the
  bulk path's prefix wherever the ladder runs; the rungs' device work
  sits under `jax.named_scope("ladder-rung")`.

submit()/finish() split the work so the pipelined executor
(engine/executor.py) can dispatch rung-1 re-replays asynchronously per
chunk while later chunks still pack and replay; rungs ≥ 2 run once,
batched across every chunk's survivors. Dense int64 lanes (the verify
engine) and compressed wirec corpora (the serialized feeder) go through
the same two calls: a sub-corpus is either, and results stay on the
device until finish() reads them.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..core.checksum import DEFAULT_LAYOUT, PayloadLayout
from ..ops.encode import LANE_EVENT_ID, gather_subcorpus
from ..ops.state import CAPACITY_ERRORS, widen_layout
from ..ops.wirec import WirecCorpus, gather_corpus
from ..utils import compile_cache
from ..utils import metrics as m
from ..utils import tracing
from ..utils.profiler import ReplayProfiler

#: rungs above base capacity (K→2K→4K with the default 2); bounded — each
#: rung is one more compiled variant and 2x the per-row state footprint
RUNGS_ENV = "CADENCE_TPU_LADDER_RUNGS"
DEFAULT_RUNGS = 2

_CAPACITY = np.asarray(CAPACITY_ERRORS, dtype=np.int32)

#: the ladder's host work on a timeline (module docstring)
SPAN_GATHER = "feed.ladder.gather"
SPAN_SUBMIT = "feed.ladder.submit"
SPAN_WAIT = "feed.ladder.device-wait"


def _pow2(n: int, floor: int) -> int:
    return max(floor, 1 << (max(1, int(n)) - 1).bit_length())


# A flagged sub-corpus is [F, E, L] int64 lanes or a WirecCorpus; these
# four are all the ladder needs to know of either.

def _dims(sub) -> Tuple[int, int]:
    return (sub.slab if isinstance(sub, WirecCorpus) else sub).shape[:2]


def _take(sub, indices, pad_workflows: int = 0, pad_events: int = 0):
    gather = gather_corpus if isinstance(sub, WirecCorpus) \
        else gather_subcorpus
    return gather(sub, indices, pad_workflows, pad_events)


def _real_events(sub) -> int:
    if isinstance(sub, WirecCorpus):
        return int(sub.n_events.sum())
    return int((sub[:, :, LANE_EVENT_ID] > 0).sum())


def _concat(subs: list):
    """Sub-corpora of one profile (or none: dense) as one, on the longest
    event axis among them."""
    if len(subs) == 1:
        return subs[0]
    E = max(_dims(s)[1] for s in subs)
    subs = [_take(s, np.arange(_dims(s)[0]), 0, E) for s in subs]
    if isinstance(subs[0], WirecCorpus):
        return WirecCorpus(*(np.concatenate(part) for part in
                             zip(*(s[:3] for s in subs))), subs[0].profile)
    return np.concatenate(subs)


@dataclass
class RungLaunch:
    """One dispatched rung: its results stay on the device until
    finish() reads them."""

    outs: tuple              # device (rows | crc32, err, ovf[, branch])
    rows: int                # real rows
    lanes: int               # rows after the pow2 padding
    events: int              # real events of those rows
    wire_bytes: int          # bytes of the gathered, unpadded sub-corpus


@dataclass
class PendingEscalation:
    """One chunk's dispatched rung-1 re-replay (submit() → finish())."""

    sub: object              # trimmed flagged sub-corpus (host copy):
                             # [F, E, L] lanes or a WirecCorpus
    launch: RungLaunch       # rung 1, in flight
    count: int               # real rows (padding excluded)


@dataclass
class LadderOutcome:
    """Final arbitration-ready results for F flagged rows."""

    rows: np.ndarray         # [F, base_width] (valid where resolved); for
                             # a wirec sub-corpus their CRC32s, [F] uint32
    resolved: np.ndarray     # [F] bool — device-resolved at some rung
    errors: np.ndarray       # [F] i32 — last rung's error per row
    branch: np.ndarray       # [F] i32 — device-chosen current branch
    rungs: List[dict] = field(default_factory=list)  # per-rung accounting


class EscalationLadder:
    """Widened-K re-replay ladder over capacity-flagged rows."""

    def __init__(self, layout: PayloadLayout = DEFAULT_LAYOUT,
                 max_rungs: Optional[int] = None,
                 registry=None, mesh=None,
                 variants: Optional[compile_cache.KernelVariantCache] = None
                 ) -> None:
        self.layout = layout
        self.max_rungs = (max_rungs if max_rungs is not None
                          else int(os.environ.get(RUNGS_ENV,
                                                  str(DEFAULT_RUNGS))))
        self.max_rungs = max(1, self.max_rungs)
        self.metrics = registry if registry is not None else m.DEFAULT_REGISTRY
        #: when set, rungs re-replay SPMD under the mesh's 'shard' axis
        #: (parallel/mesh.py escalated paths) instead of single-device
        self.mesh = mesh
        self.variants = (variants if variants is not None
                         else compile_cache.DEFAULT_VARIANTS)
        #: per-rung accounting of the most recent escalate/finish call
        #: (the feeder's report reads this)
        self.last_run: List[dict] = []
        self._prof = ReplayProfiler(self.metrics, scope=m.SCOPE_TPU_FALLBACK)

    # -- shared mechanics ---------------------------------------------------

    def rung_layout(self, rung: int) -> PayloadLayout:
        return widen_layout(self.layout, 2 ** rung)

    def _shards(self) -> int:
        return int(self.mesh.devices.size) if self.mesh is not None else 0

    def _pad_dims(self, F: int, E: int) -> Tuple[int, int]:
        """Pow2-bucketed padded shape; the workflow axis also rounds up to
        a multiple of the mesh so every shard gets a whole slice."""
        Wp = _pow2(F, 8)
        n = self._shards()
        if n > 1 and Wp % n:
            Wp = -(-Wp // n) * n
        return Wp, _pow2(E, 16)

    @staticmethod
    def capacity_flagged(errors: np.ndarray) -> np.ndarray:
        """Local indices of rows whose error a wider K could clear."""
        return np.nonzero(np.isin(np.asarray(errors), _CAPACITY))[0]

    def _rung_leg(self, span: Optional[str] = None) -> tracing.Span:
        """The `fallback` leg: a span whose close observes the histogram
        (a synchronous rung whole; of a dispatched one the wait)."""
        return self._prof.leg(m.M_PROFILE_FALLBACK, span=span)

    def _record_rung(self, rung: int, rows: int, seconds: float,
                     **counts) -> None:
        self.metrics.inc(m.SCOPE_TPU_FALLBACK, m.ladder_rung_rows(rung), rows)
        self.last_run.append({"rung": rung, "rows": rows,
                              "seconds": round(seconds, 6), **counts})

    def _finalize(self, resolved: np.ndarray) -> None:
        n_res = int(resolved.sum())
        self.metrics.inc(m.SCOPE_TPU_FALLBACK, m.M_LADDER_RESOLVED, n_res)
        self.metrics.inc(m.SCOPE_TPU_FALLBACK, m.M_LADDER_RESIDUAL,
                         len(resolved) - n_res)

    def _dense_fn(self, rung: int, Wp: int, Ep: int, keep_state: bool):
        """The compiled dense-lane rung variant, via the variant cache
        (a miss is exactly one XLA compile; warm runs always hit)."""
        import jax.numpy as jnp

        layout_r = self.rung_layout(rung)
        key = ("dense", self.layout, rung, Wp, Ep, self._shards(), keep_state)

        def build():
            if self.mesh is not None and not keep_state:
                from ..parallel.mesh import replay_sharded_escalated
                return lambda ev: replay_sharded_escalated(
                    jnp.asarray(ev), self.mesh, layout_r, self.layout)
            if keep_state:
                from ..ops.replay import replay_escalated_state
                return lambda ev: replay_escalated_state(
                    jnp.asarray(ev), layout_r, self.layout)
            from ..ops.replay import replay_escalated
            return lambda ev: replay_escalated(jnp.asarray(ev), layout_r,
                                               self.layout)

        return self.variants.get(key, build, self.metrics)

    def _pad(self, sub):
        """A trimmed sub-corpus in its pow2 bucket."""
        F, E = _dims(sub)
        return _take(sub, np.arange(F), *self._pad_dims(F, E))

    # -- the rungs: submit() per chunk, finish() once -----------------------

    def _launch(self, rung: int, sub) -> RungLaunch:
        """Pad a trimmed sub-corpus to its pow2 bucket and dispatch its
        re-replay at `rung` ASYNCHRONOUSLY (JAX async dispatch returns
        device handles at once)."""
        with tracing.span(SPAN_SUBMIT):
            padded = self._pad(sub)
            Wp, Ep = _dims(padded)
            if isinstance(sub, WirecCorpus):
                fn = self._wirec_fn(rung, Wp, Ep, sub.profile)
                wire_bytes = sub.wire_bytes
            else:
                fn = self._dense_fn(rung, Wp, Ep, keep_state=False)
                wire_bytes = sub.nbytes
            return RungLaunch(fn(padded), _dims(sub)[0], Wp,
                              _real_events(sub), wire_bytes)

    def submit(self, sub) -> PendingEscalation:
        """Dispatch the rung-1 re-replay of a trimmed flagged sub-corpus
        ([F, E, L] lanes or a WirecCorpus): the pipelined executor calls
        this per chunk so rung-1 compute overlaps later chunks'
        pack/replay."""
        F = _dims(sub)[0]
        self.metrics.inc(m.SCOPE_TPU_FALLBACK, m.M_LADDER_FLAGGED, F)
        return PendingEscalation(sub=sub, launch=self._launch(1, sub),
                                 count=F)

    def submit_wirec(self, corpus: WirecCorpus, indices
                     ) -> PendingEscalation:
        """submit() of rows `indices` of a packed wirec corpus: they are
        COPIED out first, so `corpus` may be a ring slot's view."""
        with tracing.span(SPAN_GATHER):
            sub = gather_corpus(corpus, np.asarray(indices, dtype=np.int64))
        return self.submit(sub)

    def _collect(self, rung: int, launches: list,
                 outcomes: List[LadderOutcome]) -> Tuple[np.ndarray,
                                                         np.ndarray]:
        """Read one rung's launches back into the outcomes; returns the
        (pending index, local row) pairs a wider rung could still clear.
        A launch is (RungLaunch, pending index of each row, its local row
        there)."""
        import jax

        with self._rung_leg(SPAN_WAIT) as leg:
            host = jax.device_get([launch.outs for launch, _, _ in launches])
        still_pi, still_j = [], []
        for (launch, pis, js), (values, err, ovf, *branch) in zip(launches,
                                                                  host):
            n = launch.rows
            values, err, ovf = values[:n], err[:n], ovf[:n]
            ok = (err == 0) & ~ovf
            for pi in np.unique(pis):
                sel, o = pis == pi, outcomes[pi]
                o.errors[js[sel]] = err[sel]
                if branch:
                    o.branch[js[sel]] = branch[0][:n][sel]
                good = sel & ok
                o.rows[js[good]] = values[good]
                o.resolved[js[good]] = True
            cap = np.isin(err, _CAPACITY)
            still_pi.append(pis[cap])
            still_j.append(js[cap])
        self._record_rung(
            rung, sum(launch.rows for launch, _, _ in launches),
            leg.duration_s,
            **{key: sum(getattr(launch, key) for launch, _, _ in launches)
               for key in ("lanes", "events", "wire_bytes")})
        return np.concatenate(still_pi), np.concatenate(still_j)

    def _relaunch(self, rung: int, pending: Sequence[PendingEscalation],
                  pis: np.ndarray, js: np.ndarray) -> list:
        """One launch at `rung` over every pending's survivors (one for
        each wirec profile among them: a profile is a jit key)."""
        groups: dict = {}
        for pi in np.unique(pis):
            sub = pending[pi].sub
            groups.setdefault(sub.profile if isinstance(sub, WirecCorpus)
                              else None, []).append(int(pi))
        launches = []
        for members in groups.values():
            rows = [js[pis == pi] for pi in members]
            with tracing.span(SPAN_GATHER):
                cur = _concat([_take(pending[pi].sub, idx)
                               for pi, idx in zip(members, rows)])
            launches.append((
                self._launch(rung, cur),
                np.concatenate([np.full(len(idx), pi)
                                for pi, idx in zip(members, rows)]),
                np.concatenate(rows)))
        return launches

    def finish(self, pending: Sequence[PendingEscalation]
               ) -> List[LadderOutcome]:
        """Collect rung-1 results and run rungs ≥ 2 ONCE, batched across
        every pending chunk's survivors. Returns one outcome per pending,
        aligned with its submitted rows."""
        self.last_run = []
        outcomes = []
        for p in pending:
            rows = (np.zeros(p.count, np.uint32)
                    if isinstance(p.sub, WirecCorpus)
                    else np.zeros((p.count, self.layout.width), np.int64))
            outcomes.append(LadderOutcome(
                rows=rows, resolved=np.zeros(p.count, bool),
                errors=np.zeros(p.count, np.int32),
                branch=np.zeros(p.count, np.int32)))
        launches = [(p.launch, np.full(p.count, pi), np.arange(p.count))
                    for pi, p in enumerate(pending)]
        for rung in range(1, self.max_rungs + 1):
            if not launches:
                break
            pis, js = self._collect(rung, launches, outcomes)
            launches = (self._relaunch(rung + 1, pending, pis, js)
                        if len(pis) and rung < self.max_rungs else [])
        for o in outcomes:
            o.rungs = list(self.last_run)
            self._finalize(o.resolved)
        return outcomes

    def escalate(self, sub) -> LadderOutcome:
        """Synchronous full ladder over one trimmed sub-corpus."""
        return self.finish([self.submit(sub)])[0]

    def escalate_wirec(self, corpus: WirecCorpus, indices
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Synchronous full ladder over flagged rows of a wirec corpus,
        reduced on device to base-width CRC32s. Returns (crc32 [F]
        uint32, resolved [F] bool, errors [F] i32) aligned with
        `indices`."""
        o = self.finish([self.submit_wirec(corpus, indices)])[0]
        return o.rows, o.resolved, o.errors

    # -- full-state path (engine/rebuild.py hydration) ----------------------

    def escalate_states(self, sub: np.ndarray):
        """Ladder that keeps the WIDENED rung states for hydration.
        Returns (outcome, states) where states[k] is (state_arrays,
        row_in_arrays) of the rung that resolved row k, or None."""
        import jax

        F = sub.shape[0]
        self.metrics.inc(m.SCOPE_TPU_FALLBACK, m.M_LADDER_FLAGGED, F)
        self.last_run = []
        rows_out = np.zeros((F, self.layout.width), np.int64)
        resolved = np.zeros(F, bool)
        err_out = np.zeros(F, np.int32)
        branch_out = np.zeros(F, np.int32)
        states: List[Optional[tuple]] = [None] * F
        active = np.arange(F)
        cur = sub
        for rung in range(1, self.max_rungs + 1):
            with self._rung_leg() as leg:
                padded = self._pad(cur)
                fn = self._dense_fn(rung, padded.shape[0], padded.shape[1],
                                    keep_state=True)
                s_dev, rows_dev, err_dev, ovf_dev = fn(padded)
                arrs = jax.device_get(s_dev)
                rows = np.asarray(rows_dev)[:len(active)]
                err = np.asarray(err_dev)[:len(active)]
                ovf = np.asarray(ovf_dev)[:len(active)]
            self._record_rung(rung, len(active), leg.duration_s)
            ok = (err == 0) & ~ovf
            for k in np.nonzero(ok)[0]:
                gi = active[k]
                rows_out[gi] = rows[k]
                resolved[gi] = True
                states[gi] = (arrs, int(k))
                branch_out[gi] = int(arrs.current_branch[k])
            err_out[active] = err
            still = self.capacity_flagged(err)
            if not len(still):
                break
            cur = gather_subcorpus(cur, still)
            active = active[still]
        self._finalize(resolved)
        return (LadderOutcome(rows=rows_out, resolved=resolved,
                              errors=err_out, branch=branch_out,
                              rungs=list(self.last_run)), states)

    # -- resident (from-state) path (engine/resident.py appends) ------------

    def escalate_resident(self, sub: np.ndarray, states, base_rung: int = 0):
        """Widened re-replay of an APPEND suffix against carried states.

        `sub` is the trimmed [F, E, L] suffix sub-corpus of rows whose
        from-state append flagged a CAPACITY error; `states` the batched
        PRE-APPEND resident states those rows replayed from (all at rung
        `base_rung`'s layout). Each rung widens the pre-append state
        (ops/state.widen_state — occupied slots keep their indices, new
        slots are empty) and re-replays ONLY the suffix, so an escalated
        append stays O(new events): the full history never re-replays and
        the row never leaves HBM.

        Returns (outcome, states_out): outcome rows/resolved/errors/branch
        aligned with `sub`; states_out[k] = (batched final state, local
        row, rung) of the rung that resolved row k, or None — the caller
        re-admits resolved rows as widened resident states (and may
        re-narrow them via ops/state.narrow_ok once their load drains).
        """
        import jax
        import jax.numpy as jnp

        from ..ops.state import init_state, widen_state

        F = sub.shape[0]
        self.metrics.inc(m.SCOPE_TPU_FALLBACK, m.M_LADDER_FLAGGED, F)
        self.last_run = []
        rows_out = np.zeros((F, self.layout.width), np.int64)
        resolved = np.zeros(F, bool)
        err_out = np.zeros(F, np.int32)
        branch_out = np.zeros(F, np.int32)
        states_out: List[Optional[tuple]] = [None] * F
        active = np.arange(F)
        cur = sub
        cur_states = states
        for rung in range(base_rung + 1, self.max_rungs + 1):
            with self._rung_leg() as leg:
                layout_r = self.rung_layout(rung)
                padded = self._pad(cur)
                Wp, Ep = padded.shape[:2]
                s0 = widen_state(cur_states, layout_r)
                if Wp > len(active):
                    pad_rows = init_state(Wp - len(active), layout_r)
                    s0 = jax.tree_util.tree_map(
                        lambda a, b: jnp.concatenate([a, b], axis=0),
                        s0, pad_rows)
                key = ("resident", self.layout, rung, Wp, Ep)

                def build():
                    from ..ops.replay import replay_from_state_to_payload
                    return lambda ev, st: replay_from_state_to_payload(
                        jnp.asarray(ev), st, self.layout)

                fn = self.variants.get(key, build, self.metrics)
                s_fin, rows_dev, err_dev, ovf_dev = fn(padded, s0)
                rows = np.asarray(rows_dev)[:len(active)]
                err = np.asarray(err_dev)[:len(active)]
                ovf = np.asarray(ovf_dev)[:len(active)]
                branch = np.asarray(s_fin.current_branch)[:len(active)]
            self._record_rung(rung, len(active), leg.duration_s)
            ok = (err == 0) & ~ovf
            for k in np.nonzero(ok)[0]:
                gi = active[k]
                rows_out[gi] = rows[k]
                resolved[gi] = True
                branch_out[gi] = branch[k]
                states_out[gi] = (s_fin, int(k), rung)
            err_out[active] = err
            still = self.capacity_flagged(err)
            if not len(still):
                break
            cur = gather_subcorpus(cur, still)
            cur_states = jax.tree_util.tree_map(
                lambda a: a[np.asarray(still)], cur_states)
            active = active[still]
        self._finalize(resolved)
        return (LadderOutcome(rows=rows_out, resolved=resolved,
                              errors=err_out, branch=branch_out,
                              rungs=list(self.last_run)), states_out)

    def _wirec_fn(self, rung: int, Wp: int, Ep: int, profile):
        import jax.numpy as jnp

        layout_r = self.rung_layout(rung)
        key = ("wirec", self.layout, rung, Wp, Ep, profile, self._shards())

        def build():
            if self.mesh is not None:
                from ..parallel.mesh import (
                    replay_wirec_sharded_escalated_crc,
                )
                return lambda c: replay_wirec_sharded_escalated_crc(
                    c, self.mesh, layout_r, self.layout)
            from ..ops.replay import replay_wirec_escalated_crc
            return lambda c: replay_wirec_escalated_crc(
                jnp.asarray(c.slab), jnp.asarray(c.bases),
                jnp.asarray(c.n_events), c.profile, layout_r, self.layout)

        return self.variants.get(key, build, self.metrics)
