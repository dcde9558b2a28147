"""The batched wide-query executor: Boolean expression trees over stacked slabs.

The paper's headline wins are *horizontal*: Algorithm 4 unions many bitmaps
at once, and the library-grade Roaring implementations (CRoaring's
aggregation layer) earn their keep on exactly these wide AND/OR/ANDNOT
queries. This module evaluates an expression tree whose leaves are members
of a key-aligned stacked ``repro.roaring.RoaringSlab`` (``ndim == 2``) — or
``RoaringSlab`` objects attached to the tree directly via ``leaf(slab)``:

  * every binary combine is one *row-state* step from the kind-dispatch
    engine (``jax_roaring._and_rows`` / ``_or_rows`` / ``_andnot_rows``),
    classifying each aligned container pair against the declarative registry
    in ``kernels.roaring.dispatch`` — so run rows gallop/range-mask and
    sparse array pairs merge packed at *every* tree level, not just the
    leaves;
  * n-ary AND/OR nodes reduce in log depth (``_tree_reduce_rows`` over the
    stacked leaf axis when all children are stack members, balanced pairing
    otherwise);
  * canonicalization (best-of-three runOptimize) is deferred to a single
    ``_finalize_rows`` at the root — an N-way query pays one pass, not N-1;
  * cardinality-only evaluation (``execute_card``) skips materialization
    entirely: per-level fused popcounts are the whole answer;
  * ``batched_and_card`` / ``topk_by_card`` score *all* N stacked slabs
    against one query in a single batched-meta dispatch launch
    (``kernels.roaring.ops.intersect_dispatch_stacked``), and the
    ``*_sharded`` variants ``shard_map`` the slab axis across a device mesh
    (``launch/mesh.py``) with the query replicated;
  * ``plan_scan`` / ``topk_by_scan`` score an
    *immutable* stack without lifting it: a plan built once from the
    stack's cards and kinds groups its rows into width classes, and each
    class is read in place over the slots its containers fill
    (``kernels.roaring.ops.card_scan``).

``execute`` returns a canonical ``repro.roaring.RoaringSlab``. Everything is
jit-/vmap-compatible; expression shapes are static Python.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core import jax_roaring as jr
from repro.kernels.roaring import fused as _fused
from repro.roaring.slab import RoaringSlab, SlabLike, _to_internal, _wrap

__all__ = [
    "Expr", "Leaf", "SlabLeaf", "And", "Or", "AndNot",
    "leaf", "and_", "or_", "andnot",
    "execute", "execute_card", "wide_union", "wide_intersect",
    "batched_and_card", "batched_and_card_sharded",
    "topk_by_card", "topk_by_card_sharded",
    "ScanPlan", "plan_scan", "topk_by_scan",
    "union_many_batched", "launch_model",
    "DegradationStats", "degradation_stats", "reset_degradation",
]


# =============================================================================
# expression trees
# =============================================================================

@dataclasses.dataclass(frozen=True)
class Expr:
    """Base class for wide Boolean query expressions (static structure)."""


@dataclasses.dataclass(frozen=True)
class Leaf(Expr):
    """Member ``i`` of the stacked slab."""

    i: int


@dataclasses.dataclass(frozen=True, eq=False)
class SlabLeaf(Expr):
    """A ``RoaringSlab`` operand attached to the tree directly (no stack
    membership, no manual tuple unpack) — its rows are gathered key-aligned
    to the query's shared key row at evaluation time."""

    slab: SlabLike


@dataclasses.dataclass(frozen=True)
class And(Expr):
    """N-ary intersection of child expressions (log-depth reduction)."""

    children: Tuple[Expr, ...]


@dataclasses.dataclass(frozen=True)
class Or(Expr):
    """N-ary union of child expressions (log-depth reduction)."""

    children: Tuple[Expr, ...]


@dataclasses.dataclass(frozen=True)
class AndNot(Expr):
    """Difference ``a \\ b``."""

    a: Expr
    b: Expr


def leaf(x: Union[int, SlabLike]) -> Expr:
    """Leaf node: an ``int`` selects member ``x`` of the stacked slab
    (bounds-checked at evaluation time — jnp's silent index clamping must
    never turn a bad leaf into a plausible wrong answer); a ``RoaringSlab``
    becomes its own operand (``SlabLeaf``)."""
    if isinstance(x, (RoaringSlab, jr.RoaringSlab)):
        if isinstance(x, RoaringSlab) and x.ndim != 1:
            raise ValueError("leaf(slab) needs a single slab (ndim == 1)")
        return SlabLeaf(x)
    if int(x) < 0:
        raise ValueError(f"leaf index must be >= 0, got {x}")
    return Leaf(int(x))


def and_(*children: Expr) -> Expr:
    """N-ary AND node (``and_(x)`` collapses to ``x``; >= 1 child
    required — fail at construction, not mid-evaluation)."""
    if not children:
        raise ValueError("and_() needs at least one child expression")
    return children[0] if len(children) == 1 else And(tuple(children))


def or_(*children: Expr) -> Expr:
    """N-ary OR node (``or_(x)`` collapses to ``x``; >= 1 child
    required — fail at construction, not mid-evaluation)."""
    if not children:
        raise ValueError("or_() needs at least one child expression")
    return children[0] if len(children) == 1 else Or(tuple(children))


def andnot(a: Expr, b: Expr) -> AndNot:
    """Difference node ``a \\ b``."""
    return AndNot(a, b)


# =============================================================================
# evaluation (row states: (data u16[C, 4096], card i32[C], kind i32[C]))
# =============================================================================

def _slab_leaves(expr: Expr) -> list:
    if isinstance(expr, SlabLeaf):
        return [expr.slab]
    if isinstance(expr, (And, Or)):
        return [s for c in expr.children for s in _slab_leaves(c)]
    if isinstance(expr, AndNot):
        return _slab_leaves(expr.a) + _slab_leaves(expr.b)
    return []


def _shared_keys(stack: Optional[RoaringSlab], expr: Expr,
                 capacity: Optional[int]) -> jax.Array:
    """The shared key row every leaf aligns to: the stack's aligned key row
    when a stack is given, else the merged key set of all slab leaves.
    Slab leaves with keys outside the stack's row contribute nothing there —
    pass ``stack=None`` (or restack) when leaf keys may exceed the stack's.
    """
    if stack is not None:
        return stack.keys[0]
    slabs = [_to_internal(s) for s in _slab_leaves(expr)]
    if not slabs:
        raise ValueError("execute(stack=None, ...) needs slab leaves")
    if capacity is None:
        capacity = sum(s.keys.shape[-1] for s in slabs)
    return jr._merge_keys_many([s.keys for s in slabs], capacity)


def _leaf_state(stack: Optional[RoaringSlab], i: int):
    if stack is None:
        raise ValueError(f"leaf({i}) needs a stacked slab; this expression "
                         "was executed without one")
    if not 0 <= i < stack.n_slabs:
        raise IndexError(
            f"leaf({i}) out of range for a stack of {stack.n_slabs} slabs")
    return stack.payload[i], stack.cards[i], stack.kinds[i]


def _fold_states(states, combine):
    """Balanced pairwise fold (log depth) over already-evaluated states."""
    states = list(states)
    while len(states) > 1:
        nxt = []
        for i in range(0, len(states) - 1, 2):
            a, b = states[i], states[i + 1]
            nxt.append(combine(a[0], a[1], a[2], b[0], b[1], b[2]))
        if len(states) % 2:
            nxt.append(states[-1])
        states = nxt
    return states[0]


def _nary(stack, keys, children, combine):
    if stack is not None and all(isinstance(c, Leaf) for c in children):
        # vectorized: slice the stacked leaf axis and tree-reduce flat —
        # every level is ONE combine over (n/2)*C rows, not n/2 traced calls
        for c in children:
            if not 0 <= c.i < stack.n_slabs:
                raise IndexError(f"leaf({c.i}) out of range for a stack of "
                                 f"{stack.n_slabs} slabs")
        idx = jnp.asarray([c.i for c in children], jnp.int32)
        return jr._tree_reduce_rows(stack.payload[idx], stack.cards[idx],
                                    stack.kinds[idx], combine)
    return _fold_states([_eval(stack, keys, c) for c in children], combine)


def _eval(stack, keys, expr: Expr):
    if isinstance(expr, Leaf):
        return _leaf_state(stack, expr.i)
    if isinstance(expr, SlabLeaf):
        return jr._gather_raw(_to_internal(expr.slab), keys)
    if isinstance(expr, And):
        return _nary(stack, keys, expr.children, jr._and_rows)
    if isinstance(expr, Or):
        return _nary(stack, keys, expr.children, jr._or_rows)
    if isinstance(expr, AndNot):
        a = _eval(stack, keys, expr.a)
        b = _eval(stack, keys, expr.b)
        return jr._andnot_rows(a[0], a[1], a[2], b[0], b[1], b[2])
    raise TypeError(f"not an Expr: {expr!r}")


def _normalize(stack, expr):
    """Allow ``execute(expr)`` when every leaf carries its own slab."""
    if isinstance(stack, Expr) and expr is None:
        return None, stack
    if expr is None:
        raise TypeError("execute needs an expression")
    return stack, expr


# =============================================================================
# fused evaluation: the whole tree in ONE launch (kernels.roaring.fused)
# =============================================================================

def _lower_tree(expr: Expr) -> tuple:
    """Structural lowering shared by the fused compiler and the launch
    model: ``(tree, order)`` where ``tree`` is the hash-consable structure
    with distinct leaves replaced by dense operand indices and ``order`` the
    deduplicated leaf list — a leaf referenced twice streams from HBM
    once."""
    order: list = []
    index_of: dict = {}

    def visit(e):
        if isinstance(e, Leaf):
            key = ("leaf", e.i)
        elif isinstance(e, SlabLeaf):
            key = ("slab", id(e.slab))
        elif isinstance(e, And):
            return ("and",) + tuple(visit(c) for c in e.children)
        elif isinstance(e, Or):
            return ("or",) + tuple(visit(c) for c in e.children)
        elif isinstance(e, AndNot):
            return ("andnot", visit(e.a), visit(e.b))
        else:
            raise TypeError(f"not an Expr: {e!r}")
        if key not in index_of:
            index_of[key] = len(order)
            order.append(e)
        return index_of[key]

    return visit(expr), order


def launch_model(expr: Expr, *, stacked: bool = True) -> dict:
    """Analytic kernel-launch accounting for one expression — the model the
    ``repro.obs`` telemetry plane cross-checks measured launch counters
    against (``obs.launch_crosscheck``).

    Two granularities matter. ``per_op_combines`` is the roofline model's
    logical combine count (``fused.plan_stats``'s ``launches_per_op``:
    N-1 for an N-leaf tree). ``per_op_dispatches`` is what the per-op
    engine *actually* launches through ``ops.intersect_dispatch``: AND
    combines over all-``Leaf`` children batch into a log-depth tree reduce
    (``ceil(log2 n)`` dispatches when ``stacked``, the ``execute``
    default), mixed-children ANDs fold pairwise (n-1 dispatches), and
    OR/ANDNOT combines run as jnp-level row algebra — zero kernel
    dispatches. ``fused_launches`` is always 1: the whole tree is one
    ``ops.fused_tree`` launch.
    """
    tree, order = _lower_tree(expr)
    plan = _fused.plan_tape(tree)

    def dispatches(e) -> int:
        if isinstance(e, (Leaf, SlabLeaf)):
            return 0
        if isinstance(e, And):
            n = len(e.children)
            if stacked and all(isinstance(c, Leaf) for c in e.children):
                return (n - 1).bit_length()
            return (n - 1) + sum(dispatches(c) for c in e.children)
        if isinstance(e, Or):
            return sum(dispatches(c) for c in e.children)
        if isinstance(e, AndNot):
            return dispatches(e.a) + dispatches(e.b)
        raise TypeError(f"not an Expr: {e!r}")

    return {
        "n_operands": len(order),
        "per_op_combines": int(plan.n_ops),
        "per_op_dispatches": dispatches(expr),
        "fused_launches": 1,
    }


def _fused_compile(stack, keys, expr: Expr):
    """Lower an ``Expr`` to the fused evaluator's inputs: the structural
    tree (via ``_lower_tree``), the stacked operand rows u16[N, C, 4096],
    and the packed lift meta."""
    tree, order = _lower_tree(expr)
    states = []
    for e in order:
        if isinstance(e, Leaf):
            d, c, k = _leaf_state(stack, e.i)
            r = stack.nruns[e.i]
        else:
            d, c, k = jr._gather_raw(_to_internal(e.slab), keys)
            r = jr._rows_nruns(d, k)
        states.append((d, c, k, r))
    data = jnp.stack([s[0] for s in states])
    meta = _fused.pack_lift_meta(jnp.stack([s[2] for s in states]),
                                 jnp.stack([s[1] for s in states]),
                                 jnp.stack([s[3] for s in states]))
    return _fused.plan_tape(tree), data, meta


def _fused_eval(stack, keys, expr: Expr):
    """Row-state result of the fused path: one ``ops.fused_tree`` launch,
    root rows in bitmap domain (kind from the fused per-column card)."""
    from repro.kernels.roaring import ops as _kops

    plan, data, meta = _fused_compile(stack, keys, expr)
    bits, card = _kops.fused_tree(data, meta, plan)
    kind = jnp.where(card > 0, jr.KIND_BITMAP, jr.KIND_EMPTY).astype(
        jnp.int32)
    # empty rows carry the packed-array padding fill, matching the per-op
    # pipeline's convention for dead payloads
    bits = jnp.where((card > 0)[:, None], bits, jnp.uint16(0xFFFF))
    return bits, card, kind


# =============================================================================
# graceful degradation: the Pallas -> XLA-ref fallback ladder
# =============================================================================

@dataclasses.dataclass
class DegradationStats:
    """Snapshot view of the engine's failure-ladder counters: how many
    dispatch attempts failed, how many retries the preferred backend got,
    and how many queries completed degraded on a lower rung.

    PR 9: the live counters moved to the ``repro.obs`` metrics registry
    (``index.dispatch_failures`` / ``index.retries`` / ``index.fallbacks``,
    plus per-rung ``index.rung_taken{kind,backend}``); this class survives
    as the deprecated ``degradation_stats()`` return type."""

    dispatch_failures: int = 0
    retries: int = 0
    fallbacks: int = 0

    def snapshot(self) -> "DegradationStats":
        return DegradationStats(self.dispatch_failures, self.retries,
                                self.fallbacks)


# failure classes the ladder absorbs: injected faults and device/runtime
# errors (preemption, OOM, ICI failures surface as XlaRuntimeError, a
# JaxRuntimeError subclass; RuntimeError covers interpret-mode lowering
# failures). Shape/type/user errors (ValueError, IndexError, ...) propagate
# untouched — degrading cannot fix a malformed query.
_FALLBACK_ERRORS = (RuntimeError, jax.errors.JaxRuntimeError)

_LADDER_COUNTERS = ("index.dispatch_failures", "index.retries",
                    "index.fallbacks", "index.rung_taken")


def degradation_stats() -> DegradationStats:
    """Deprecated: read the ``repro.obs`` registry instead —
    ``obs.registry().value("index.dispatch_failures")`` etc. This shim
    snapshots those counters into the legacy ``DegradationStats`` shape."""
    import warnings

    warnings.warn(
        "repro.index.degradation_stats() is deprecated; read the "
        "repro.obs metrics registry ('index.dispatch_failures', "
        "'index.retries', 'index.fallbacks') instead",
        DeprecationWarning, stacklevel=2)
    reg = obs.registry()
    return DegradationStats(
        int(reg.value("index.dispatch_failures")),
        int(reg.value("index.retries")),
        int(reg.value("index.fallbacks")))


def reset_degradation() -> None:
    """Zero the engine-wide degradation counters (test isolation)."""
    reg = obs.registry()
    for name in _LADDER_COUNTERS:
        reg.remove(name)


def _run_ladder(rungs, max_retries: int, backoff_s: float):
    """Run the first workable rung of ``rungs``: ordered ``(backend, kind,
    fn)`` triples, most-preferred first (``kind`` is the evaluator rung:
    ``"fused"`` / ``"per_op"``).

    The first rung gets ``max_retries`` retries with exponential backoff
    (transient device faults deserve a second chance before giving up on
    the fast path); later rungs get one attempt each. Every failed attempt
    counts in ``index.dispatch_failures``; every rung drop counts in
    ``index.fallbacks``; the winning rung counts in
    ``index.rung_taken{kind,backend}``. Each attempt runs under an
    ``index.rung`` span, so injected faults show up as errored child spans
    in the trace. A failure on the last rung propagates — there is nothing
    left to degrade to.
    """
    from repro.kernels.roaring import ops as _kops

    reg = obs.registry()
    for r, (rung_backend, rung_kind, fn) in enumerate(rungs):
        tries = (max_retries + 1) if r == 0 else 1
        for attempt in range(tries):
            try:
                with obs.span("index.rung", kind=rung_kind,
                              backend=rung_backend, attempt=attempt):
                    with _kops.backend_scope(rung_backend):
                        out = fn()
                reg.counter("index.rung_taken", kind=rung_kind,
                            backend=rung_backend).inc()
                return out
            except _FALLBACK_ERRORS:
                if r == len(rungs) - 1 and attempt == tries - 1:
                    raise
                reg.counter("index.dispatch_failures").inc()
                if attempt < tries - 1:
                    reg.counter("index.retries").inc()
                    if backoff_s > 0:
                        time.sleep(backoff_s * (2 ** attempt))
        reg.counter("index.fallbacks").inc()


def _run_degradable(fn, backend: Optional[str], max_retries: int,
                    backoff_s: float):
    """Run ``fn`` with the per-op Pallas->XLA-ref fallback ladder.

    ``backend=None``/"auto" resolves to the hardware default. A preferred
    non-"xla" backend gets ``max_retries`` retries with exponential backoff;
    when they are exhausted the query degrades to the XLA reference backend
    (bit-identical math, counted in the registry's ``index.fallbacks``).
    """
    from repro.kernels.roaring import ops as _kops

    preferred = backend or _kops.current_backend()
    if preferred == "xla":
        with obs.span("index.rung", kind="per_op", backend="xla"):
            with _kops.backend_scope("xla"):
                out = fn()
        obs.registry().counter("index.rung_taken", kind="per_op",
                               backend="xla").inc()
        return out
    return _run_ladder([(preferred, "per_op", fn), ("xla", "per_op", fn)],
                       max_retries, backoff_s)


def _run_query(fused_fn, per_op_fn, fused: bool, backend: Optional[str],
               max_retries: int, backoff_s: float):
    """Ladder selection for one query: ``fused=False`` runs the classic
    two-rung per-op ladder; ``fused=True`` prepends the fused evaluator —
    preferred-backend-fused -> preferred-backend-per-op -> XLA-ref-per-op
    (the per-op tree-reduce stays the bit-identity reference and the rung
    of last resort)."""
    from repro.kernels.roaring import ops as _kops

    if not fused:
        return _run_degradable(per_op_fn, backend, max_retries, backoff_s)
    preferred = backend or _kops.current_backend()
    rungs = [(preferred, "fused", fused_fn), (preferred, "per_op", per_op_fn)]
    if preferred != "xla":
        rungs.append(("xla", "per_op", per_op_fn))
    return _run_ladder(rungs, max_retries, backoff_s)


def execute(stack: Optional[RoaringSlab], expr: Optional[Expr] = None,
            capacity: Optional[int] = None, *, fused: bool = False,
            backend: Optional[str] = None, max_retries: int = 1,
            backoff_s: float = 0.0) -> RoaringSlab:
    """Evaluate ``expr`` over the stacked slab -> canonical ``RoaringSlab``.

    One deferred best-of-three canonicalization at the root; output is
    bit-identical (values, card, kind, packed payload) to evaluating the
    same expression with ``py_roaring`` set algebra. ``stack`` may be
    ``None`` (or omitted) when every leaf is a ``leaf(slab)`` — the shared
    key row is then the merged key set of the slab leaves (``capacity``
    bounds it, defaulting to the sum of leaf capacities).

    ``fused=True`` evaluates the whole tree in ONE kernel launch
    (``kernels.roaring.fused``): leaves stream from HBM once, every
    intermediate stays in VMEM, and the per-op tree-reduce becomes
    the fallback rung — same bytes out either way.

    ``backend`` picks the dispatch backend ("pallas" / "xla" / None=auto).
    Dispatch failures on a non-"xla" backend (real device faults or a
    ``runtime.fault_tolerance.FaultPlan``) retry ``max_retries`` times with
    exponential backoff, then degrade rung by rung — fused to per-op,
    preferred backend to the XLA reference — incrementing the ladder
    counters on the ``repro.obs`` registry while results stay
    bit-identical.
    """
    stack, expr = _normalize(stack, expr)
    keys = _shared_keys(stack, expr, capacity)

    def per_op() -> RoaringSlab:
        data, card, kind = _eval(stack, keys, expr)
        return _wrap(jr._finalize_rows(keys, data, card, kind))

    def fused_attempt() -> RoaringSlab:
        data, card, kind = _fused_eval(stack, keys, expr)
        return _wrap(jr._finalize_rows(keys, data, card, kind))

    with obs.span("index.execute", fused=fused, backend=backend or "auto"):
        if obs.enabled() and stack is not None:
            obs.record_kinds("index.input_kinds", stack.kinds)
        out = _run_query(fused_attempt, per_op, fused, backend, max_retries,
                         backoff_s)
        if obs.enabled():
            obs.record_kinds("index.output_kinds", out.kinds)
        return out


def execute_card(stack: Optional[RoaringSlab],
                 expr: Optional[Expr] = None,
                 capacity: Optional[int] = None, *, fused: bool = False,
                 backend: Optional[str] = None, max_retries: int = 1,
                 backoff_s: float = 0.0) -> jax.Array:
    """|expr| without materializing a result slab — every combine level
    already maintains exact per-row cardinalities (fused popcounts on the
    bitmap-domain paths), so the root's counter sum is the answer.
    ``fused=True`` gets it from the mega-kernel's per-column root popcount
    (one launch, no canonicalization at all). Runs the same degradation
    ladder as ``execute``."""
    stack, expr = _normalize(stack, expr)
    keys = _shared_keys(stack, expr, capacity)

    def per_op() -> jax.Array:
        _, card, _ = _eval(stack, keys, expr)
        return jnp.sum(card)

    def fused_attempt() -> jax.Array:
        _, card, _ = _fused_eval(stack, keys, expr)
        return jnp.sum(card)

    with obs.span("index.execute_card", fused=fused,
                  backend=backend or "auto"):
        if obs.enabled() and stack is not None:
            obs.record_kinds("index.input_kinds", stack.kinds)
        return _run_query(fused_attempt, per_op, fused, backend, max_retries,
                          backoff_s)


def wide_union(stack: RoaringSlab) -> RoaringSlab:
    """Union of all N stacked slabs (Algorithm 4): log-depth tree reduction,
    kind-dispatching at every level, deferred cardinality (one recount at
    the root), single deferred canonicalization."""
    data, card, kind = jr._tree_reduce_rows(stack.payload, stack.cards,
                                            stack.kinds, jr._or_rows_deferred)
    card = jr._recount_bitmap_rows(data, card, kind)
    return _wrap(jr._finalize_rows(stack.keys[0], data, card, kind))


def wide_intersect(stack: RoaringSlab) -> RoaringSlab:
    """Intersection of all N stacked slabs: log-depth tree of registry
    dispatch steps (arrays gallop, runs range-mask, bitmaps word-AND with
    fused popcount), single deferred canonicalization."""
    data, card, kind = jr._tree_reduce_rows(stack.payload, stack.cards,
                                            stack.kinds, jr._and_rows)
    return _wrap(jr._finalize_rows(stack.keys[0], data, card, kind))


# =============================================================================
# batched scoring: all N slabs against one query in one dispatch launch
# =============================================================================

def _align_query(stack: RoaringSlab, query: SlabLike):
    """Gather the query's rows aligned to the stack's key row."""
    qd, qc, qk = jr._gather_raw(_to_internal(query), stack.keys[0])
    return qd, qc, qk, jr._rows_nruns(qd, qk)


@jax.jit
def index_score(data, card, kind, nruns, qd, qc, qk, qr):
    """Per-slab |slab_n ∩ query| via the stacked batched-meta dispatch: the
    query rows are shared by all N slabs, never broadcast in HBM, and the
    dispatch's hit rows are dead code under this jit — only the fused
    cardinalities are computed. Its program is ``jit_index_score`` in a
    device trace."""
    from repro.kernels.roaring import ops as _kops
    N, C = kind.shape
    meta = jnp.stack([
        kind, jnp.broadcast_to(qk, (N, C)),
        card, jnp.broadcast_to(qc, (N, C)),
        nruns, jnp.broadcast_to(qr, (N, C)),
    ], axis=2).reshape(N, 6 * C).astype(jnp.int32)
    _, rc = _kops.intersect_dispatch_stacked(data, qd, meta)
    return jnp.sum(rc, axis=1)


def batched_and_card(stack: RoaringSlab, query: SlabLike) -> jax.Array:
    """i32[N] of |slab_n ∩ query| — the wide-query scoring primitive.

    One ``intersect_dispatch_stacked`` launch covers all N*C container
    pairs (run x run pairs score via the in-kernel coverage AND); nothing is
    materialized or canonicalized.
    """
    qd, qc, qk, qr = _align_query(stack, query)
    return index_score(stack.payload, stack.cards, stack.kinds, stack.nruns,
                         qd, qc, qk, qr)


def topk_by_card(stack: RoaringSlab, query: SlabLike, k: int):
    """Top-k stacked slabs by intersection cardinality with ``query``.

    Returns ``(scores i32[k], indices i32[k])`` — ``jax.lax.top_k`` over the
    batched scores (the "which posting lists match this query best"
    primitive).
    """
    return jax.lax.top_k(batched_and_card(stack, query), k)


# =============================================================================
# planned scoring: an immutable stack read where its containers lie
# =============================================================================

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["keys", "groups", "places", "codes",
                                "residual", "order"],
                   meta_fields=["widths", "bitmaps", "rows_lifted",
                                "slots_scanned", "slots_total"])
@dataclasses.dataclass(frozen=True)
class ScanPlan:
    """How one immutable stack is scored against a query (``plan_scan``).

    Class ``i`` holds the stack rows (terms) of width ``widths[i]``: every
    array row of theirs holds at most that many values, so the scan reads
    only their first ``widths[i]`` slots. The scan reads rows in groups of
    ``scan.GROUP`` consecutive ones (a tile of the stack): ``groups[i]``
    (i32[S_i]) are the groups holding the class's rows, ``places[i]``
    (i32[S_i·GROUP]) flags the class's rows holding an array row (the rows
    the kernel places), ``codes[i]`` (i32[S_i, GROUP, C]) their rows' scan
    codes, 0 for rows of other classes; ``bitmaps[i]`` says the class
    holds bitmap rows.
    ``residual`` (i32[R]) are the rows holding a run row, scored by the
    lift. Rows with no container are in no class and score 0. ``order``
    (i32[N]) puts each row's score back in stack order from the classes'
    scores (group by group), then the residual's, then one zero. ``keys``
    is the stack's shared key row. The last three fields count one
    scoring: rows handed to ``dispatch.lift_rows`` (the query's C and the
    residual's), container slots read (every row of each group a class
    visits at the class's width, its other classes' rows masked, and a
    residual row's 4096), and slots in the stack.
    """

    keys: jax.Array
    groups: Tuple[jax.Array, ...]
    places: Tuple[jax.Array, ...]
    codes: Tuple[jax.Array, ...]
    residual: jax.Array
    order: jax.Array
    widths: Tuple[int, ...]
    bitmaps: Tuple[bool, ...]
    rows_lifted: int
    slots_scanned: int
    slots_total: int


def plan_scan(stack: RoaringSlab) -> ScanPlan:
    """The scan plan of a stack, from its cards and kinds (read to the host
    once). A row's width is the next power of two at or above its largest
    array card, from the lane width (128) up to the row width (4096); a
    row holding a bitmap takes 4096 and one holding a run goes to the
    residual. The class set is those widths that some row takes."""
    from repro.kernels.roaring import scan as _scan

    kinds, cards = np.asarray(stack.kinds), np.asarray(stack.cards)
    N, C = kinds.shape
    G = _scan.GROUP
    largest = np.where(kinds == jr.KIND_ARRAY, cards, 0).max(axis=1)
    width = np.full(N, _scan.LANES)
    while (width < largest).any():
        width = np.where(width < largest, 2 * width, width)
    holds = [(kinds == k).any(axis=1) for k in (jr.KIND_BITMAP, jr.KIND_RUN)]
    width[holds[0]] = jr.ROW_WORDS
    scanned = (kinds != jr.KIND_EMPTY).any(axis=1) & ~holds[1]
    widths = sorted({int(w) for w in width[scanned]})
    # rows padded to whole groups; padding rows are in no class
    codes = np.zeros((-(-N // G) * G, C), np.int32)
    codes[:N] = _scan.scan_codes(kinds, cards)
    residual = np.flatnonzero(holds[1])
    order = np.full(N, -1, np.int64)
    groups, places, class_codes, at = [], [], [], 0
    for w in widths:
        member = np.zeros(codes.shape[0], bool)
        member[:N] = scanned & (width == w)
        g = np.flatnonzero(member.reshape(-1, G).any(axis=1))
        rows = (g[:, None] * G + np.arange(G)).reshape(-1)
        hit = member[rows]
        order[rows[hit]] = at + np.flatnonzero(hit)
        groups.append(g)
        places.append(hit & (codes[rows] > 0).any(axis=1))
        class_codes.append(np.where(hit[:, None], codes[rows], 0).reshape(
            g.size, G, C))
        at += rows.size
    order[residual] = at + np.arange(residual.size)
    order[order < 0] = at + residual.size      # the trailing zero
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    return ScanPlan(
        keys=i32(np.asarray(stack.keys[0])),
        groups=tuple(map(i32, groups)), places=tuple(map(i32, places)),
        codes=tuple(map(i32, class_codes)),
        residual=i32(residual), order=i32(order), widths=tuple(widths),
        bitmaps=tuple(bool((c == _scan.BITMAP_CODE).any())
                      for c in class_codes),
        rows_lifted=C * (1 + residual.size),
        slots_scanned=C * (sum(w * g.size * G for w, g in zip(widths, groups))
                           + jr.ROW_WORDS * residual.size),
        slots_total=N * C * jr.ROW_WORDS)


def _scan_scores(payload, kinds, cards, nruns, plan: ScanPlan, query):
    """i32[N] scores of ``plan``'s stack against ``query`` (traced)."""
    from repro.kernels.roaring import dispatch as _D
    from repro.kernels.roaring import ops as _kops

    qd, qc, qk = jr._gather_raw(_to_internal(query), plan.keys)
    lift = _D.device_array_lift()
    q = _D.lift_rows(qd, qk, qc, jr._rows_nruns(qd, qk), lift)
    parts = [_kops.card_scan(payload, g, p, c, q, width=w,
                             bitmaps=b).reshape(-1)
             for w, b, g, p, c in zip(plan.widths, plan.bitmaps, plan.groups,
                                      plan.places, plan.codes)]
    r = plan.residual
    if r.shape[0]:
        words = _D.lift_rows(payload[r], kinds[r], cards[r], nruns[r], lift)
        parts.append(jnp.sum(jax.lax.population_count(words & q).astype(
            jnp.int32), axis=(1, 2, 3)))
    parts.append(jnp.zeros((1,), jnp.int32))
    return jnp.concatenate(parts)[plan.order]


def _planned_score(payload, kinds, cards, nruns, plan, query, k):
    return jax.lax.top_k(_scan_scores(payload, kinds, cards, nruns, plan,
                                      query), k)


# whichever path scores, a device trace names the scoring program
# ``jit_index_score``
_planned_score.__name__ = "index_score"
_planned_score = jax.jit(_planned_score, static_argnames="k")


def topk_by_scan(stack: RoaringSlab, plan: ScanPlan, query: SlabLike,
                 k: int):
    """``topk_by_card`` for a stack with a scan plan: ``(scores i32[k],
    indices i32[k])``, the same scores with the stack read in place over
    the slots its containers fill (one ``card_scan`` launch per width
    class) and only the query's rows (and any residual run-holding rows)
    lifted. The scores are put back in stack order before ``top_k``, so
    ties break as ``topk_by_card``'s."""
    return _planned_score(stack.payload, stack.kinds, stack.cards,
                          stack.nruns, plan, query, k=k)


# =============================================================================
# sharding: slab axis across the device mesh, query replicated
# =============================================================================

def batched_and_card_sharded(stack: RoaringSlab, query: SlabLike,
                             mesh, axis: str = "data") -> jax.Array:
    """``batched_and_card`` with the slab axis sharded over ``mesh[axis]``.

    Each device scores its N/axis_size shard of the stack against the
    replicated query locally (one stacked dispatch launch per device, no
    cross-device traffic until the caller reduces the i32[N] scores).
    ``stack.n_slabs`` must divide evenly by the mesh axis size.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    # the query may sit on one device (a service's cache); replicate it
    query = jax.device_put(query, NamedSharding(mesh, P()))
    qd, qc, qk, qr = _align_query(stack, query)
    f = jax.shard_map(
        index_score, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(), P(), P(), P()),
        out_specs=P(axis))
    return f(stack.payload, stack.cards, stack.kinds, stack.nruns,
             qd, qc, qk, qr)


def topk_by_card_sharded(stack: RoaringSlab, query: SlabLike, k: int,
                         mesh, axis: str = "data"):
    """Sharded ``topk_by_card``: local scoring per device shard, global
    ``top_k`` over the gathered i32[N] scores (k*axis_size candidate traffic,
    never slab payloads)."""
    return jax.lax.top_k(
        batched_and_card_sharded(stack, query, mesh, axis=axis), k)


# =============================================================================
# batched (vmapped) wide union — deprecated shim over repro.roaring.union_all
# =============================================================================

def union_many_batched(slabs: Sequence[SlabLike],
                       capacity: int) -> RoaringSlab:
    """Deprecated: use ``repro.roaring.union_all`` (same vmapped tree)."""
    import warnings

    from repro.roaring.slab import union_all
    warnings.warn(
        "repro.index.union_many_batched is deprecated; use "
        "repro.roaring.union_all(slabs, capacity=...)",
        DeprecationWarning, stacklevel=2)
    return union_all(slabs, capacity=capacity)
