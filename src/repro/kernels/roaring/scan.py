"""Card-bounded scoring of one query against a posting stack, in place.

A top-k terms aggregation needs ``|row ∩ query|`` summed over each term's C
rows, for every term of an immutable stack. The stack's rows stay where
they lie (``u16[N, C, 4096]`` in HBM); nothing is lifted, relaid out or
written back. Each term (stack row) has a width class from a plan built
once from the stack's cards and kinds (``index.engine.plan_scan``): a
class of width ``W`` holds terms whose array rows all fit in ``W`` slots,
so only the first ``W`` slots of their rows are ever read.

The kernel reads the stack through its transposed view ``u16[C, N, 4096]``,
whose (8, 128) tiles of u16 pairs hold ``GROUP`` consecutive terms' slots of
one chunk row, so it reads a *group* of ``GROUP`` consecutive terms at a
time. Where a TPU lays the stack out chunk-major of its own accord (when C
is not a multiple of 8, as for 8.8 M documents' 135 chunk rows) that view
is free; where it lays it out row-major, XLA transposes the stack once per
scoring (correct, and the copy's bytes are temporaries). The stack stays in
the layout the TPU gives it, the layout every other program over it was
compiled for. A class's grid visits each group holding one of its terms;
the group's other terms are read there too, but masked (scan code 0), and
are scored in their own classes. The kernel places a group's terms a few
at a time (16 rows of 128 slots, one of 4096) and skips the placements of
a few that hold none of the class's array rows, so a term order that
mixes the classes within groups adds reads, and few placements.

``card_scan_pallas`` (kernel ``roaring_card_scan``) scores one class in one
launch. The groups arrive by scalar prefetch; a grid step DMAs a block of
chunk rows of one group, over the class's ``W`` slots. Array rows are
placed into bitmap words in VMEM by exact one-hot products over their
``W`` slots (the products of ``dispatch.array_coverage_by_matmul``, over
``W`` slots and not 4096), then ANDed with the query's words and
popcounted; bitmap rows (only in the class of width 4096) AND with the
query in the stack's own u16 order; empty rows add nothing. Only ``i32``
scores leave the kernel.

``card_scan_ref`` is the XLA mirror: array rows probe the query's bits at
their values (as ``dispatch``'s ``k_probe`` does, over ``W`` slots), bitmap
rows AND and popcount. The two share no placement code and return the
same scores.

A row's scan code says what its slots hold: the card of an array row,
``-1`` for a bitmap row, 0 for an empty row. Run rows are never scanned
here: the plan scores their terms by the lift.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch as D

ROW_WORDS = D.ROW_WORDS
LANES = D.ROW_SHAPE[1]              # 128: the narrowest slot window
GROUP = 16                          # terms per tile of the u16 stack
BITMAP_CODE = -1                    # scan code of a bitmap row
_SLOT_CHUNK = 256                   # slots placed per one-hot product
_BLOCK_BYTES = 4 << 20              # the stack block of one grid step
_VMEM_LIMIT = 48 << 20              # the class of width 4096 holds two such
                                    # blocks, and the query's rows twice

__all__ = ["LANES", "GROUP", "BITMAP_CODE", "scan_codes",
           "card_scan_pallas", "card_scan_ref"]


def scan_codes(kind, card):
    """Per-row scan codes from kinds and cards (any array module)."""
    return (card * (kind == D.KIND_ARRAY)
            + BITMAP_CODE * (kind == D.KIND_BITMAP))


def _terms_per_body(width: int) -> int:
    """Terms placed per loop body: 16 narrow rows' products, or one wide
    row's, fill the MXU between branches."""
    return max(1, GROUP * LANES // width)


def _rows_per_step(C: int, width: int) -> int:
    """Chunk rows per grid step: the largest divisor of ``C`` whose block
    of one group fits the step budget."""
    fit = max(1, _BLOCK_BYTES // (GROUP * width * 2))
    return max(d for d in range(1, min(C, fit) + 1) if C % d == 0)


def _scan_kernel(groups_ref, bodies_ref, x_ref, code_ref, qw_ref, *rest,
                 width: int, bitmaps: bool):
    """One group's block of chunk rows per grid step; its terms' partial
    scores accumulate over the chunk axis of the grid. A loop body places
    its terms only where ``bodies_ref`` flags one of them."""
    del groups_ref                    # read by the index maps alone
    if bitmaps:
        qb_ref, out_ref, acc_ref, lanes_ref, v_scr = rest
    else:
        out_ref, acc_ref, lanes_ref, v_scr = rest
    j = pl.program_id(1)
    rows = x_ref.shape[0]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lanes_ref[...] = jnp.zeros_like(lanes_ref)

    code = code_ref[0]                                        # (GROUP, C)
    chunk_of = jax.lax.broadcasted_iota(jnp.int32, code.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (GROUP, width), 1)
    chunk = min(width, _SLOT_CHUNK)
    unrolled = _terms_per_body(width)
    first = pl.program_id(0) * (GROUP // unrolled)    # this group's flags
    half = D.ROW_SHAPE[0] // 2

    def place(t, q):
        placed = jnp.zeros(D.ROW_SHAPE, jnp.float32)
        for s in range(0, width, chunk):
            u = v_scr[pl.ds(t, 1), pl.ds(s, chunk)]           # (1, chunk)
            # value u lands in u16 word (u >> 11, (u >> 4) & 127) at bit
            # u & 15: a (32 x chunk) by (chunk x 128) one-hot product
            r = jax.lax.broadcasted_iota(jnp.int32,
                                         (D.ROW_SHAPE[0], chunk), 0)
            l = jax.lax.broadcasted_iota(jnp.int32, (LANES, chunk), 0)
            a = jnp.where((u >> 11) == r,
                          (1 << (u & 15)).astype(jnp.float32), 0.0)
            b = jnp.where(((u >> 4) & (LANES - 1)) == l, 1.0, 0.0)
            placed = placed + jax.lax.dot_general(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        w = placed.astype(jnp.int32)
        words = w[:half] | (w[half:] << 16)                   # to_words
        acc_ref[t] += jax.lax.population_count(words & q)

    def chunk_row(c, carry):
        # this chunk row's scan code of each of the group's terms
        col = jnp.sum(jnp.where(chunk_of == j * rows + c, code, 0), axis=1,
                      keepdims=True)                          # (GROUP, 1)
        v = x_ref[c].astype(jnp.int32)                        # (GROUP, W)
        # slots past an array row's card (and every slot of a bitmap or
        # empty row) become -1, which no one-hot row matches
        v_scr[...] = jnp.where(slot < col, v, -1)
        q = qw_ref[c]

        def some_terms(i, carry):
            # one branch a body: a branch per term would keep the MXU
            # from overlapping the body's products
            @pl.when(bodies_ref[first + i] != 0)
            def _():
                for t in range(unrolled):
                    place(i * unrolled + t, q)
            return carry

        jax.lax.fori_loop(0, GROUP // unrolled, some_terms, 0)
        if bitmaps:
            hit = jax.lax.population_count(
                jnp.where(col == BITMAP_CODE, v & qb_ref[c], 0))
            folded = lanes_ref[...]
            for s in range(0, width, LANES):
                folded = folded + hit[:, s:s + LANES]
            lanes_ref[...] = folded
        return carry

    jax.lax.fori_loop(0, rows, chunk_row, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        for t in range(GROUP):
            out_ref[0, t:t + 1, :] = (
                lanes_ref[t:t + 1, :]
                + jnp.sum(acc_ref[t], axis=0, keepdims=True))


def card_scan_pallas(payload, groups, places, codes, q_words, *, width: int,
                     bitmaps: bool, interpret: bool = True):
    """Scores of one width class: ``i32[S, GROUP]``, ``|term ∩ query|`` for
    each term of each visited group (0 for the terms masked out).

    payload: u16[N, C, 4096] the resident stack, read in place through its
    transposed view. groups: i32[S] the groups the class visits (group
    ``g`` is stack rows ``GROUP·g`` on). places: i32[S·GROUP] 1 for the
    terms holding the class's array rows, 0 for the others (terms of other
    classes, bitmap-only terms, padding): the kernel skips the placements
    of a loop body's terms when none is flagged. codes: i32[S, GROUP, C]
    their terms' scan codes (``scan_codes``), 0 for terms of other classes
    and rows past the stack. q_words: u32[C, 16, 128] the query's rows in
    bitmap domain (``dispatch.to_words``). width: the class's slot window,
    a power of two from 128 to 4096. bitmaps: the class holds bitmap rows
    (width 4096 only). Its stable name in a device trace is
    ``roaring_card_scan``.
    """
    C = payload.shape[1]
    S = groups.shape[0]
    rows = _rows_per_step(C, width)
    unrolled = _terms_per_body(width)
    bodies = jnp.max(places.reshape(S, GROUP // unrolled, unrolled), axis=-1)
    view = jnp.transpose(payload, (1, 0, 2))                  # (C, N, 4096)
    operands = [view, codes,
                jax.lax.bitcast_convert_type(q_words, jnp.int32)]
    in_specs = [
        pl.BlockSpec((rows, GROUP, width), lambda s, j, g, p: (j, g[s], 0)),
        pl.BlockSpec((1, GROUP, C), lambda s, j, g, p: (s, 0, 0)),
        pl.BlockSpec((rows, *D.WORD_SHAPE), lambda s, j, g, p: (j, 0, 0)),
    ]
    if bitmaps:
        # the query in the stack's own u16 order, for bitmap rows
        operands.append(D.from_words(q_words).astype(jnp.int32)[:, None])
        in_specs.append(pl.BlockSpec((rows, 1, ROW_WORDS),
                                     lambda s, j, g, p: (j, 0, 0)))
    lanes = pl.pallas_call(
        functools.partial(_scan_kernel, width=width, bitmaps=bitmaps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, C // rows), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, GROUP, LANES),
                                   lambda s, j, g, p: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((GROUP, *D.WORD_SHAPE), jnp.int32),
                pltpu.VMEM((GROUP, LANES), jnp.int32),
                pltpu.VMEM((GROUP, width), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, GROUP, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="roaring_card_scan",
    )(groups, bodies.reshape(-1), *operands)
    return jnp.sum(lanes, axis=-1)


def card_scan_ref(payload, groups, places, codes, q_words, *, width: int,
                  bitmaps: bool):
    """XLA mirror of ``card_scan_pallas``: the same scores, by probing the
    query's bits at each array value and popcounting bitmap rows. It masks
    by the scan codes alone (``places`` is not read), so it checks the
    kernel's flags."""
    del places
    N, C, _ = payload.shape
    rows = groups[:, None] * GROUP + jnp.arange(GROUP)        # (S, GROUP)
    x = payload[jnp.minimum(rows, N - 1), :, :width].astype(jnp.int32)
    code = codes[..., None]                                   # (S, G, C, 1)
    q = D.from_words(q_words).astype(jnp.int32)               # (C, 4096)
    word = q.reshape(-1)[jnp.arange(C)[:, None] * ROW_WORDS + (x >> 4)]
    hit = (jnp.arange(width) < code) & (((word >> (x & 15)) & 1) == 1)
    score = jnp.sum(hit.astype(jnp.int32), axis=(2, 3))
    if bitmaps:
        bits = jnp.where(code == BITMAP_CODE, x & q, 0)
        score = score + jnp.sum(jax.lax.population_count(bits), axis=(2, 3))
    return score
