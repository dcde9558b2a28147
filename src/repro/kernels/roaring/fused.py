"""Fused expression-tree evaluation: ONE kernel launch per whole Boolean tree.

The per-op executor (``index.engine._eval``) pays a separate dispatch launch
— and a full HBM round trip of intermediate row state — per combine step of
an AND/OR/ANDNOT tree. This module collapses the whole tree into a single
Pallas launch whose grid body evaluates every node for one container column:

  * **plan** (``plan_tape``): the static expression shape is topo-ordered
    into a *tape* — a left-fold post-order sequence of ``("load", operand,
    slot)`` leaf lifts and ``(op, a_slot, b_slot, dst_slot)`` word ops — with
    stack-machine scratch-slot assignment (an n-ary node folds in place, so
    slot pressure is the tree's operand depth, not its width). Plans are
    hash-consed per structural tree (``functools.lru_cache``), so the jitted
    evaluator retraces once per expression *shape*, never per query.
  * **load**: each distinct leaf's rows are lifted to their membership
    bitmaps in XLA (``dispatch.lift_rows``: bitmap rows pass through,
    array rows by one-hot matrix products, run rows by scatter), then
    streamed into the kernel once.
  * **ops**: the kernel (``word_tape_pallas``) replays the tape over the
    rows' u32 words, a block of container rows per grid step — every
    interior node is a word op and intermediates never leave VMEM.
  * **root**: the root's popcount is fused into the same pass; the single
    best-of-three canonicalization happens once, outside, in
    ``jax_roaring._finalize_rows`` (same final pass as the per-op path, so
    results stay byte-identical to ``py_roaring``).

``fused_eval_ref`` is the XLA mirror (the same tape, word ops over whole
slot arrays; array rows lifted by scatter, so the two paths share no array
lift) — the reference of the bit-identity contract. The same
kernel serves the pairwise entries in ``kernel.py`` with one-op tapes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch as D

ROW_WORDS = D.ROW_WORDS
ROW_SHAPE = D.ROW_SHAPE

__all__ = [
    "FusedPlan", "plan_tape", "plan_cache_size", "plan_stats",
    "word_tape_pallas", "fused_eval_pallas", "fused_eval_ref",
    "LIFT_META_FIELDS", "pack_lift_meta",
    "group_card_pallas", "group_card_ref",
]

# A tree is an operand index (leaf) or an (op, *subtrees) tuple.
Tree = Union[int, Tuple]

_WORD_OPS = {
    "and": jnp.bitwise_and,
    "or": jnp.bitwise_or,
    "andnot": lambda a, b: jnp.bitwise_and(a, ~b),
    "xor": jnp.bitwise_xor,
}


@dataclasses.dataclass(frozen=True)
class FusedPlan:
    """A compiled expression shape: the static op tape one fused launch
    replays per container column.

    ``tape`` steps are ``("load", operand_idx, dst_slot)`` — lift operand
    row into scratch — or ``(op, a_slot, b_slot, dst_slot)`` with ``op`` in
    ``{"and", "or", "andnot"}``. The result lands in slot 0. ``n_slots`` is
    the peak scratch height; ``n_operands`` the number of distinct leaf
    rows the kernel streams in.
    """

    tape: Tuple[Tuple, ...]
    n_slots: int
    n_operands: int

    @property
    def n_loads(self) -> int:
        return sum(1 for s in self.tape if s[0] == "load")

    @property
    def n_ops(self) -> int:
        return len(self.tape) - self.n_loads


def _emit(node: Tree, tape: list, height: int) -> int:
    """Post-order tape emission with stack-machine slot allocation: a node
    evaluates into slot ``height``; an n-ary node left-folds in place so
    only one extra slot is live per nesting level. Returns the peak slot
    count."""
    if isinstance(node, int):
        tape.append(("load", node, height))
        return height + 1
    op = node[0]
    if op not in _WORD_OPS:
        raise ValueError(f"unknown fused op {op!r}")
    children = node[1:]
    if op == "andnot" and len(children) != 2:
        raise ValueError("andnot is binary")
    if not children:
        raise ValueError(f"{op} node needs children")
    peak = _emit(children[0], tape, height)
    for ch in children[1:]:
        peak = max(peak, _emit(ch, tape, height + 1))
        tape.append((op, height, height + 1, height))
    return peak


@functools.lru_cache(maxsize=None)
def plan_tape(tree: Tree) -> FusedPlan:
    """Compile a structural expression tree (operand indices at the leaves,
    ``(op, *subtrees)`` tuples inside) into a ``FusedPlan``. Hash-consed:
    equal trees return the *same* plan object, so plans are free to use as
    jit static arguments without retraces."""
    tape: list = []
    n_slots = _emit(tree, tape, 0)
    operands = {s[1] for s in tape if s[0] == "load"}
    n_operands = (max(operands) + 1) if operands else 0
    return FusedPlan(tuple(tape), n_slots, n_operands)


def plan_cache_size() -> int:
    """Number of distinct expression shapes compiled so far (retrace-guard
    instrumentation)."""
    return plan_tape.cache_info().currsize


def plan_stats(plan: FusedPlan, n_containers: int) -> dict:
    """Launch-count / HBM-traffic model for one plan over ``n_containers``
    key-aligned columns — fused vs the per-op tree-reduce path.

    The fused launch reads each distinct operand row once and writes the
    root bits + card; the per-op path launches one dispatch per interior
    combine, each reading two row states from HBM and writing one back.
    (8 kB payload per container row; the i32 card adds 4 B.)
    """
    row = 2 * ROW_WORDS                       # u16[4096] payload bytes
    per_col_fused = plan.n_loads * row + row + 4
    per_col_per_op = plan.n_ops * (2 * row + row + 4) + plan.n_loads * 0
    return {
        "n_operands": plan.n_operands,
        "n_combines": plan.n_ops,
        "launches_fused": 1,
        "launches_per_op": max(plan.n_ops, 1),
        "hbm_bytes_fused": per_col_fused * n_containers,
        "hbm_bytes_per_op": per_col_per_op * n_containers,
    }


# =============================================================================
# meta packing (shared by both backends and the engine)
# =============================================================================

LIFT_META_FIELDS = 3  # (kind, card, n_runs) per (operand, column)


def pack_lift_meta(kind, card, nruns):
    """Pack per-operand row tags into the fused evaluator's meta block.

    kind/card/nruns: i32[N, C]. Layout: interleaved (kind, card, n_runs) at
    flat index ``3 * (n * C + i)`` (i32[3*N*C]).
    """
    return jnp.stack([kind, card, nruns], axis=2).reshape(-1).astype(
        jnp.int32)


def _lift_leaves(ops_data, meta, array_lift):
    """Every operand's rows as bitmap-domain words, u32[N, C, 16, 128]
    (``dispatch.lift_rows``; array / run lifts skipped for leaves that hold
    none)."""
    N, C = ops_data.shape[0], ops_data.shape[1]
    fields = meta.reshape(N, C, LIFT_META_FIELDS)
    return D.lift_rows(ops_data, fields[..., 0], fields[..., 1],
                       fields[..., 2], array_lift)


# =============================================================================
# the word-op tape kernel (Pallas)
# =============================================================================

WORD_SHAPE = D.WORD_SHAPE       # u32[16,128] == one 8 kB container row
_STEP_BLOCK_BYTES = 8 << 20     # operand + output blocks of one grid step,
                                # double-buffered, inside the scoped VMEM


def _rows_per_step(C: int, n_blocks: int) -> int:
    """Container rows per grid step: the largest divisor of ``C`` (at most
    8) whose ``n_blocks`` double-buffered row blocks fit the step budget."""
    row = 2 * ROW_WORDS
    for tb in range(min(8, C), 1, -1):
        if C % tb == 0 and 2 * n_blocks * tb * row <= _STEP_BLOCK_BYTES:
            return tb
    return 1


def _tape_kernel(*refs, plan: FusedPlan, leaves: Tuple[Tuple[int, int], ...],
                 n_in: int):
    """One block of container rows per grid step: replay the tape over the
    operands' u32 words, write the root words and per-lane popcounts.
    Intermediates are values of the step and never return to HBM.
    ``leaves[n]`` is the (input, leaf) a tape load of operand ``n`` reads."""
    ins, bits_ref, card_ref = refs[:n_in], refs[n_in], refs[n_in + 1]
    res = _replay(plan, lambda n: ins[leaves[n][0]][0, leaves[n][1]])
    bits_ref[0] = res
    # per-lane partial cards (a lane-dense block); the caller sums lanes
    card_ref[0] = jnp.sum(jax.lax.population_count(res).astype(jnp.int32),
                          axis=1, keepdims=True)


def word_tape_pallas(operands, plan: FusedPlan, *, interpret: bool = True):
    """Replay ``plan``'s tape over bitmap-domain words in ONE launch.

    operands: a sequence of u32[M_k, L_k, C, 16, 128] words
    (``dispatch.to_words``) — the tape's operand ``n`` is the ``n``-th leaf
    counting through every input's ``L_k`` leaves in order. ``M_k`` is
    either ``M`` or 1 (one set of leaves shared by all M, read per column
    block without being broadcast in HBM). Returns (words u32[M, C, 16,
    128], card i32[M, C]). Grid ``(M, C/tb)`` with ``tb`` rows per step; the
    kernel holds no scalar state. Its stable name in a device trace is
    ``roaring_word_tape``.
    """
    M = max(o.shape[0] for o in operands)
    C = operands[0].shape[2]
    tb = _rows_per_step(C, sum(o.shape[1] for o in operands) + 1)
    leaves = tuple((k, l) for k, o in enumerate(operands)
                   for l in range(o.shape[1]))

    def spec(L, shared):
        block = (1, L, tb, *WORD_SHAPE)
        if shared:
            return pl.BlockSpec(block, lambda m, j: (0, 0, j, 0, 0))
        return pl.BlockSpec(block, lambda m, j: (m, 0, j, 0, 0))

    # under shard_map the outputs vary over the mesh axes the inputs do
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    out = functools.partial(jax.ShapeDtypeStruct, vma=vma) if vma else \
        jax.ShapeDtypeStruct
    words, lanes = pl.pallas_call(
        functools.partial(_tape_kernel, plan=plan, leaves=leaves,
                          n_in=len(operands)),
        grid=(M, C // tb),
        in_specs=[spec(o.shape[1], o.shape[0] != M) for o in operands],
        out_specs=[
            pl.BlockSpec((1, tb, *WORD_SHAPE), lambda m, j: (m, j, 0, 0)),
            pl.BlockSpec((1, tb, 1, 128), lambda m, j: (m, j, 0, 0)),
        ],
        out_shape=[out((M, C, *WORD_SHAPE), jnp.uint32),
                   out((M, C, 1, 128), jnp.int32)],
        interpret=interpret,
        name="roaring_word_tape",
    )(*operands)
    return words, jnp.sum(lanes[:, :, 0, :], axis=-1)


def _replay(plan: FusedPlan, load):
    """The root of ``plan``'s tape, with ``load(n)`` the words of operand
    ``n``."""
    slots = {}
    for step in plan.tape:
        if step[0] == "load":
            slots[step[2]] = load(step[1])
        else:
            op, sa, sb, dst = step
            slots[dst] = _WORD_OPS[op](slots[sa], slots[sb])
    return slots[0]


def _lane_card(words):
    """Per-lane popcount of rows of words ``(tb, 16, 128)``: ``(1, 128)``."""
    per_row = jnp.sum(jax.lax.population_count(words).astype(jnp.int32),
                      axis=1, keepdims=True)
    return jnp.sum(per_row, axis=0)


def _group_card_kernel(*refs, plan, has_groups: bool):
    """One block of chunk rows per grid step: the filter (``plan``'s tape
    over the leaf block), then, for each group and slice, the popcount of
    ``filter & group & slice``, added into the lane partials that stay in
    VMEM across the grid."""
    out_ref, ins = refs[-1], list(refs[:-1])
    leaves = ins.pop(0) if plan is not None else None
    groups = ins.pop(0) if has_groups else None
    slices = ins.pop(0)

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    filt = None if plan is None else _replay(plan, lambda n: leaves[n])
    for g in range(out_ref.shape[0]):
        sel = filt
        if groups is not None:
            sel = groups[g] if sel is None else sel & groups[g]
        for b in range(out_ref.shape[1]):
            words = slices[b] if sel is None else slices[b] & sel
            out_ref[g, b] += _lane_card(words)


def group_card_pallas(leaves, groups, slices, plan, *,
                      interpret: bool = True):
    """``|filter ∩ group_g ∩ slice_b|`` for every (group, slice) pair in ONE
    launch: i32[max(G, 1), B].

    leaves: u32[L, C, 16, 128] the bitmap-domain words of the filter's
    leaves, the operands of ``plan``'s tape (both None: no filter, every
    row). groups: u32[G, C, 16, 128] (None: one group, every row). slices:
    u32[B, C, 16, 128]. Grid ``(C/tb,)``: each step reads its ``tb`` chunk
    rows of every operand once, replays the tape on the leaves, and adds
    the ``G·B`` popcounts into lane partials held in VMEM across the grid,
    so no operand is read twice and nothing but the partials is written.
    Its stable name in a device trace is ``roaring_group_card``.
    """
    operands = [o for o in (leaves, groups, slices) if o is not None]
    C = slices.shape[1]
    G = 1 if groups is None else groups.shape[0]
    B = slices.shape[0]
    tb = _rows_per_step(C, sum(o.shape[0] for o in operands))
    lanes = pl.pallas_call(
        functools.partial(_group_card_kernel, plan=plan,
                          has_groups=groups is not None),
        grid=(C // tb,),
        in_specs=[pl.BlockSpec((o.shape[0], tb, *WORD_SHAPE),
                               lambda j: (0, j, 0, 0)) for o in operands],
        out_specs=pl.BlockSpec((G, B, 1, 128), lambda j: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, B, 1, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="roaring_group_card",
    )(*operands)
    return jnp.sum(lanes[:, :, 0, :], axis=-1)


def group_card_ref(leaves, groups, slices, plan):
    """XLA mirror of ``group_card_pallas``: the same tape over whole slot
    arrays, then one popcount per (group, slice) pair."""
    filt = None if plan is None else _replay(plan, lambda n: leaves[n])
    cards = []
    for g in range(1 if groups is None else groups.shape[0]):
        sel = filt
        if groups is not None:
            sel = groups[g] if sel is None else sel & groups[g]
        words = slices if sel is None else slices & sel[None]
        cards.append(jnp.sum(jax.lax.population_count(words).astype(
            jnp.int32), axis=(1, 2, 3)))
    return jnp.stack(cards)


def fused_eval_pallas(ops_data: jax.Array, meta: jax.Array, *,
                      plan: FusedPlan, interpret: bool = True):
    """Evaluate a whole Boolean tree in ONE Pallas launch.

    ops_data: u16[N, C, 4096] raw container rows (N distinct operands, key
    aligned). meta: the ``pack_lift_meta`` block (i32[3*N*C]). plan: the
    compiled tape (static). Leaves are lifted to bitmap domain in XLA
    (``dispatch.lift_rows``, array rows by one-hot products on the MXU),
    then the tape runs in the kernel. Returns
    (bits u16[C, 4096] bitmap-domain root rows, card i32[C]).
    """
    words, card = word_tape_pallas(
        [_lift_leaves(ops_data, meta, "matmul")[None]], plan,
        interpret=interpret)
    return D.from_words(words[0]), card[0]


# =============================================================================
# XLA mirror (same lifts, same tape)
# =============================================================================

def fused_eval_ref(ops_data: jax.Array, meta: jax.Array, *,
                   plan: FusedPlan):
    """XLA mirror of ``fused_eval_pallas``: leaves lifted with the scatter
    array lift, and the same word ops over whole [C, 16, 128] slot arrays.
    Bit-identical output."""
    leaves = _lift_leaves(ops_data, meta, "scatter")
    res = _replay(plan, lambda n: leaves[n])
    card = jnp.sum(jax.lax.population_count(res).astype(jnp.int32),
                   axis=(1, 2))
    return D.from_words(res), card
