"""The Pallas path of the pairwise container entries.

Every entry here has the same shape on the chip: the operands' rows are
lifted to bitmap domain in XLA (``dispatch.lift_rows``: bitmap rows pass
through, array rows by one-hot products on the MXU, run rows by scatter),
ONE launch of the word-op kernel (``fused.word_tape_pallas``) runs the op
and the fused popcount over the rows' u32 words (``dispatch.to_words``), and the output semantics of the
dispatch registry are applied on the result:

1. ``container_op``: the fused word-op + popcount of Algorithms 1/3 over
   bitmap-domain rows; pairs with both sides empty come out empty.

2. ``intersect_dispatch`` (and its stacked form): the hybrid per-type AND
   (paper S4, extended to the 2016 follow-up's run containers). The
   cardinality is the popcount of the lifted AND, exact for every pair
   class; ``hits`` follows ``dispatch.AND_TABLE`` — the AND words for the
   ``bits`` classes, and for the ``mask_a`` / ``mask_b`` classes a 0/1 mask
   over the array side's packed slots, probed from the AND words. The XLA
   reference (``ref.intersect_dispatch_ref``) runs the registry's row
   kernels instead and returns the same bytes.

3. ``array_intersect``: the array x array cell of the same path.

A container row is 8 kB: u16[4096] in the slab layout, u32[16, 128] inside
the kernel (two vector registers of 8 x 128 lanes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import dispatch as D
from . import fused as F

ROW_WORDS = D.ROW_WORDS
ROW_SHAPE = D.ROW_SHAPE

_PROBE = D.make_and_kernels()["probe"]


def container_op_pallas(a_bits: jax.Array, b_bits: jax.Array,
                        kinds: jax.Array, op: str,
                        interpret: bool = True):
    """Batched container op.

    a_bits, b_bits: u16[C, 4096] bitmap-domain rows (key-aligned).
    kinds: i32[2C] interleaved (kind_a0, kind_b0, kind_a1, ...) tags.
    Returns (out_bits u16[C, 4096], card i32[C]).
    """
    live = (kinds[0::2] != D.KIND_EMPTY) | (kinds[1::2] != D.KIND_EMPTY)
    words, card = F.word_tape_pallas(
        [D.to_words(a_bits)[None, None], D.to_words(b_bits)[None, None]],
        F.plan_tape((op, 0, 1)), interpret=interpret)
    return (jnp.where(live[:, None], D.from_words(words[0]), jnp.uint16(0)),
            jnp.where(live, card[0], 0))


def _mask_classes(bits, a_rows, b_rows, ka, kb, ca, cb):
    """Apply the registry's output semantic per row: ``mask_a`` / ``mask_b``
    rows become the 0/1 slot mask of the array side, probed from the AND
    words; ``bits`` rows keep the words."""
    ma = D.out_mask("mask_a", ka, kb)
    mb = D.out_mask("mask_b", ka, kb)

    def one(w, a, b, ma_i, mb_i, ca_i, cb_i):
        arr = jnp.where(ma_i, a, b).reshape(ROW_SHAPE)
        mask, _ = _PROBE(arr, w.reshape(ROW_SHAPE),
                         jnp.where(ma_i, ca_i, cb_i), 0, 0, 0)
        return jnp.where(ma_i | mb_i, mask.reshape(ROW_WORDS), w)

    def run(w):
        return jax.vmap(jax.vmap(one))(w, a_rows, b_rows, ma, mb, ca, cb)

    return jax.lax.cond(jnp.any(ma | mb), run, lambda w: w, bits)


def intersect_dispatch_stacked_pallas(a_data: jax.Array, b_data: jax.Array,
                                      meta: jax.Array, interpret: bool = True):
    """Kind-dispatch AND of N key-aligned slab pairs in one launch.

    a_data: u16[N, C, 4096] raw container rows (packed arrays, bitmap words
    or run pairs, per their kind tag). b_data: the same shape, or u16[C,
    4096] — one slab shared by all N (a query scored against a stack),
    which is lifted once and never broadcast in HBM. meta: i32[N, 6C]
    per-slab interleaved (kind_a, kind_b, card_a, card_b, nruns_a,
    nruns_b). Returns (hits u16[N, C, 4096], card i32[N, C]).
    """
    N, C = a_data.shape[0], a_data.shape[1]
    ka, kb, ca, cb, ra, rb = (f.reshape(N, C)
                              for f in D.unpack_meta(meta.reshape(-1)))
    la = D.lift_rows(a_data, ka, ca, ra, "matmul")
    if b_data.ndim == 2:
        lb = D.lift_rows(b_data[None], kb[:1], cb[:1], rb[:1], "matmul")
        b_data = jnp.broadcast_to(b_data, a_data.shape)
    else:
        lb = D.lift_rows(b_data, kb, cb, rb, "matmul")
    words, card = F.word_tape_pallas([la[:, None], lb[:, None]],
                                     F.plan_tape(("and", 0, 1)),
                                     interpret=interpret)
    return _mask_classes(D.from_words(words), a_data, b_data, ka, kb, ca,
                         cb), card


def intersect_dispatch_pallas(a_data: jax.Array, b_data: jax.Array,
                              meta: jax.Array, interpret: bool = True):
    """Kind-dispatch AND over key-aligned container rows.

    a_data, b_data: u16[C, 4096] raw container rows. meta: i32[6C]
    interleaved (kind_a, kind_b, card_a, card_b, nruns_a, nruns_b) per row.
    Returns (hits u16[C, 4096], card i32[C]); see the module docstring for
    the per-pair-class meaning of ``hits``.
    """
    hits, card = intersect_dispatch_stacked_pallas(
        a_data[None], b_data[None], meta[None], interpret=interpret)
    return hits[0], card[0]


def array_intersect_pallas(a_arr: jax.Array, b_arr: jax.Array,
                           cards: jax.Array, interpret: bool = True):
    """Intersect packed sorted array containers (0xFFFF-padded).

    a_arr, b_arr: u16[C, 4096]; cards: i32[2C] interleaved (card_a, card_b).
    Returns (hits u16[C, 4096] — 1 where a value of A is also in B — and
    count i32[C]).
    """
    C = a_arr.shape[0]
    arrays = jnp.full((C,), D.KIND_ARRAY, jnp.int32)
    zeros = jnp.zeros((C,), jnp.int32)
    meta = jnp.stack([arrays, arrays, cards[0::2], cards[1::2], zeros, zeros],
                     axis=1).reshape(-1)
    return intersect_dispatch_pallas(a_arr, b_arr, meta, interpret=interpret)
