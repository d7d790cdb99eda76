"""Columnar visibility scan kernels: query AST → vectorized mask.

The ES tier's esql surface (PAPER §2.4 indexer), reframed the way this
repo reframes everything: visibility rows live as device-resident
COLUMNS (interned string ids, int64 times/status, float64 numeric
search attributes), and a parsed query AST (engine/visibility_query.py
Cmp/And/Or) compiles into one jitted boolean-mask kernel evaluated over
every row at HBM bandwidth. Readback is minimized by construction:

- count: the mask's scalar reduction — 8 bytes off device;
- bitmap: the mask packed to 1 bit/row (matching row ids, nothing else);
- topk: K rounds of selection over the start-time column return the
  first K matching row ids in StartTime-DESC order — the paginated
  List/Scan readback is K ids + a count, independent of table size.

Compilation is two-phase so warm queries recompile NOTHING:
- `compile_plan` walks the AST once per query, resolving each leaf
  through a store-provided binder into (column slot, op code) plus the
  leaf's VALUE, which rides in traced parameter vectors — so two
  queries with the same shape (fields + ops) share one executable and
  only the parameters change;
- the kernel builders below are keyed by that structural signature (+
  padded capacity) in a KernelVariantCache, making every compile an
  observable miss counter (the zero-warm-recompile acceptance bar).

Host parity is the contract: every op code reproduces the host
evaluator's semantics exactly — missing values never match, IEEE NaN
(the float column's null) never matches, and cross-type comparisons
reduce at PLAN time to constant TRUE/FALSE leaves mirroring Python's
`==`-is-False / `<`-is-TypeError split. Ordering comparisons on interned
string columns cannot be expressed on device (interning does not
preserve lexicographic order) — the binder refuses them and the store
falls back to the host path (counted, never silently divergent).
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from ..engine.visibility_query import And, Cmp, Node, Or

#: interned-id null (row has no value in this column)
NULL_ID = -1

#: leaf op codes (structural — part of the kernel variant signature)
OP_FALSE = 0    # never matches (cross-type ordering, unknown column)
OP_TRUE = 1     # always matches (e.g. int column != non-integral float)
OP_EQ = 2
OP_NE = 3       # guarded by presence on nullable columns
OP_LT = 4
OP_LE = 5
OP_GT = 6
OP_GE = 7
OP_PRESENT = 8  # matches iff the row has a value (id/f64 `!=` vs
                # cross-type constant: present values always differ)

#: column kinds (structural)
COL_ID = "id"    # int64 interned ids, NULL_ID = missing; EQ/NE/PRESENT
COL_I64 = "i64"  # int64, always present (times, status); all six ops
COL_F64 = "f64"  # float64 numeric search attrs, NaN = missing

_INT64_MAX = (1 << 63) - 1
_INT64_MIN = -(1 << 63)


class UnsupportedPredicate(Exception):
    """The query needs host evaluation (string ordering, a column past
    the intern budget, a type-poisoned column). Not an error: the store
    counts it (`reason` picks the fallback counter — "predicate" for an
    inexpressible op, "column" for a column the device cannot carry)
    and serves the host path."""

    def __init__(self, msg: str, reason: str = "predicate") -> None:
        super().__init__(msg)
        self.reason = reason


class ScanPlan:
    """One compiled query: the structural signature (hashable — the
    kernel variant key) plus this query's parameter vectors.

    `leaves` is a tuple of (kind, op_code, slot) triples; `tree` is the
    nested ("and"|"or"|int) structure over leaf indices. `slots` names
    the columns the kernel consumes, in the order the store must pass
    them. Parameters are NOT part of the signature: they ride the
    traced int64/float64 vectors, so same-shape queries share one
    executable. The plan never crosses the jit boundary — kernels close
    over the structure."""

    def __init__(self, tree, leaves: Tuple, slots: Tuple[str, ...],
                 iparams, fparams) -> None:
        self.tree = tree
        self.leaves = leaves
        self.slots = slots
        self.iparams = iparams
        self.fparams = fparams

    @property
    def signature(self):
        return (self.tree, self.leaves, self.slots)

    def __hash__(self):
        return hash(self.signature)

    def __eq__(self, other):
        return (isinstance(other, ScanPlan)
                and self.signature == other.signature)


def plan_leaf_int(op: str, value: object):
    """Normalize a numeric comparison against an int64 column into an
    exact int64 (op_code, param) — or a constant leaf when Python-exact
    semantics say so. Python compares int/float EXACTLY (5 < 5.3 and
    5 == 5.0 are value comparisons, not casts); float64 cannot represent
    every int64, so the float is folded into the integer lattice here at
    plan time instead of casting the column on device."""
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        # bool is int in Python but never produced by the parser; any
        # non-numeric value vs an always-present int column: == False,
        # != True, ordering TypeError→False
        return {"!=": (OP_TRUE, 0)}.get(op, (OP_FALSE, 0))
    if isinstance(value, float):
        if value != value or value in (float("inf"), float("-inf")):
            if value == float("inf"):
                return ((OP_TRUE, 0) if op in ("<", "<=", "!=")
                        else (OP_FALSE, 0))
            if value == float("-inf"):
                return ((OP_TRUE, 0) if op in (">", ">=", "!=")
                        else (OP_FALSE, 0))
            return (OP_TRUE, 0) if op == "!=" else (OP_FALSE, 0)  # NaN
        if float(value).is_integer() and _INT64_MIN <= value <= _INT64_MAX:
            value = int(value)
        else:
            # non-integral: no int equals it; order against the floor
            import math
            f = math.floor(value)
            if f >= _INT64_MAX:
                lo_ops = ("<", "<=")
                return ((OP_TRUE, 0) if op in lo_ops or op == "!="
                        else (OP_FALSE, 0))
            if f < _INT64_MIN:
                hi_ops = (">", ">=")
                return ((OP_TRUE, 0) if op in hi_ops or op == "!="
                        else (OP_FALSE, 0))
            return {
                "=": (OP_FALSE, 0), "!=": (OP_TRUE, 0),
                "<": (OP_LE, f), "<=": (OP_LE, f),
                ">": (OP_GE, f + 1), ">=": (OP_GE, f + 1),
            }[op]
    if not _INT64_MIN <= value <= _INT64_MAX:
        # beyond int64: every stored value is on one known side
        if value > _INT64_MAX:
            return ((OP_TRUE, 0) if op in ("<", "<=", "!=")
                    else (OP_FALSE, 0))
        return ((OP_TRUE, 0) if op in (">", ">=", "!=")
                else (OP_FALSE, 0))
    return {"=": (OP_EQ, value), "!=": (OP_NE, value),
            "<": (OP_LT, value), "<=": (OP_LE, value),
            ">": (OP_GT, value), ">=": (OP_GE, value)}[op]


def compile_plan(node: Node, binder) -> ScanPlan:
    """Walk the AST into a ScanPlan. `binder.leaf(field, op, value)`
    resolves one comparison into (kind, op_code, slot_name, iparam,
    fparam) — the store owns column naming, interning and budget — and
    raises UnsupportedPredicate to route the whole query to the host."""
    import numpy as np

    leaves = []
    slots: list = []
    iparams: list = []
    fparams: list = []

    def walk(n):
        if isinstance(n, And):
            return ("and", walk(n.left), walk(n.right))
        if isinstance(n, Or):
            return ("or", walk(n.left), walk(n.right))
        assert isinstance(n, Cmp)
        kind, op_code, slot_name, ip, fp = binder.leaf(n.field, n.op,
                                                       n.value)
        if slot_name is None:
            slot = -1
        else:
            if slot_name not in slots:
                slots.append(slot_name)
            slot = slots.index(slot_name)
        leaves.append((kind, op_code, slot))
        iparams.append(int(ip))
        fparams.append(float(fp))
        return len(leaves) - 1

    tree = walk(node)
    return ScanPlan(tree, tuple(leaves), tuple(slots),
                    np.asarray(iparams, dtype=np.int64),
                    np.asarray(fparams, dtype=np.float64))


def _leaf_mask(spec, col, ip, fp):
    kind, op_code, _slot = spec
    if op_code == OP_FALSE:
        return None  # caller broadcasts False
    if op_code == OP_TRUE:
        return True  # caller broadcasts True
    if kind == COL_ID:
        if op_code == OP_EQ:
            return col == ip
        if op_code == OP_NE:
            return (col != NULL_ID) & (col != ip)
        return col != NULL_ID  # OP_PRESENT
    if kind == COL_I64:
        return {OP_EQ: col == ip, OP_NE: col != ip, OP_LT: col < ip,
                OP_LE: col <= ip, OP_GT: col > ip,
                OP_GE: col >= ip}[op_code]
    present = ~jnp.isnan(col)
    if op_code == OP_NE:
        return present & (col != fp)
    if op_code == OP_PRESENT:
        return present
    # IEEE: every comparison against NaN is already False — presence is
    # free for EQ/LT/LE/GT/GE
    return {OP_EQ: col == fp, OP_LT: col < fp, OP_LE: col <= fp,
            OP_GT: col > fp, OP_GE: col >= fp}[op_code]


def _tree_mask(tree, leaves, cols, valid, iparams, fparams):
    def eval_node(n):
        if isinstance(n, tuple):
            op, l, r = n
            lm, rm = eval_node(l), eval_node(r)
            if op == "and":
                if lm is None or rm is None:
                    return None
                if lm is True:
                    return rm
                if rm is True:
                    return lm
                return lm & rm
            if lm is True or rm is True:
                return True
            if lm is None:
                return rm
            if rm is None:
                return lm
            return lm | rm
        spec = leaves[n]
        col = cols[spec[2]] if spec[2] >= 0 else None
        return _leaf_mask(spec, col, iparams[n], fparams[n])

    m = eval_node(tree)
    if m is None:
        return jnp.zeros_like(valid)
    if m is True:
        return valid
    return m & valid


def build_count(plan: ScanPlan) -> Callable:
    """count(cols, valid, iparams, fparams) → int64 scalar: match count.
    One 8-byte readback regardless of table size."""
    tree, leaves = plan.tree, plan.leaves

    @jax.jit
    def count(cols, valid, iparams, fparams):
        mask = _tree_mask(tree, leaves, cols, valid, iparams, fparams)
        return jnp.sum(mask, dtype=jnp.int64)

    return count


def build_bitmap(plan: ScanPlan) -> Callable:
    """bitmap(cols, valid, iparams, fparams) → (uint8[ceil(N/8)],
    int64): the mask packed 1 bit/row (numpy-default big bitorder; host
    unpacks with np.unpackbits) plus the match count — matching row ids
    at 1/64th the readback of the id column itself."""
    tree, leaves = plan.tree, plan.leaves

    @jax.jit
    def bitmap(cols, valid, iparams, fparams):
        mask = _tree_mask(tree, leaves, cols, valid, iparams, fparams)
        return jnp.packbits(mask), jnp.sum(mask, dtype=jnp.int64)

    return bitmap


#: widest page the selection kernel below serves: it makes one pass
#: over the table per returned id, so past this the bitmap path (one
#: pass, host-side ordering of the matches) is the cheaper exact answer
TOPK_MAX_K = 1024


def build_topk(plan: ScanPlan, k: int) -> Callable:
    """topk(cols, valid, start, iparams, fparams) → (int64[k], int64):
    the first k MATCHING row ids in (start_time DESC, row ASC) order,
    plus the total match count; ids past the count are meaningless and
    the caller drops them. The paged List/Scan readback: k ids + a
    count, independent of table size. Row-ASC tie order inside one
    start_time is the DEVICE order; the store re-resolves ties against
    its host (workflow_id, run_id) order and escalates to the bitmap
    path when a tie straddles the k boundary.

    A SELECTION, not a sort: k rounds of "largest start_time still
    unpicked, lowest row id among its ties". A three-key int64 argsort
    gives the same ids, but int64 is emulated on the TPU and the chip's
    compiler takes minutes over a multi-operand 64-bit sort (measured
    for a described v5e: 265 s at 2^17 rows, 340 s at 2^20) — per plan
    shape, under the store lock. The rounds below are two reductions
    and an elementwise pass each and compile in under a second at any
    table size; k is bounded by TOPK_MAX_K."""
    tree, leaves = plan.tree, plan.leaves

    @jax.jit
    def topk(cols, valid, start, iparams, fparams):
        mask = _tree_mask(tree, leaves, cols, valid, iparams, fparams)

        def pick(j, carry):
            alive, ids = carry
            best = jnp.max(jnp.where(alive, start, _INT64_MIN))
            # argmax of a bool vector = its first True = lowest row id;
            # with nothing left alive it yields row 0, past the count
            i = jnp.argmax(alive & (start == best))
            return alive.at[i].set(False), ids.at[j].set(i)

        _, ids = jax.lax.fori_loop(
            0, k, pick, (mask, jnp.zeros((k,), dtype=jnp.int64)))
        return ids, jnp.sum(mask, dtype=jnp.int64)

    return topk


def build_apply(dtypes: Tuple[str, ...]) -> Callable:
    """apply(cols, idx, vals) → cols: scatter one drained delta batch
    (full replacement rows at `idx`) into every column in a single
    device launch. `idx` is padded to its pow2 bucket with
    out-of-range indices, dropped by scatter mode='drop' — padding
    never touches row state. dtypes is structural (one executable per
    column-set shape)."""

    @jax.jit
    def apply(cols, idx, vals):
        return tuple(c.at[idx].set(v, mode="drop")
                     for c, v in zip(cols, vals))

    return apply


def pow2_bucket(n: int, floor: int = 64) -> int:
    """Smallest pow2 ≥ max(n, floor) — delta batches and capacities land
    on shared kernel variants instead of minting one per exact size."""
    b = floor
    while b < n:
        b <<= 1
    return b
