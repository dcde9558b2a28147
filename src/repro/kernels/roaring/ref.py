"""Pure-jnp oracles for the roaring container kernels.

``intersect_dispatch_ref`` runs the declarative pair-class registry
(``dispatch.AND_TABLE``): one cond-guarded vmapped pass per grid cell,
selected by the cell's ``(kind_a, kind_b)`` predicate. XLA has no per-row
skip, so within a pass every row computes masked — but a class with no
matching rows is skipped wholesale at runtime by ``lax.cond``, and none of
the cheap paths touches the 2^16-element domain. The Pallas path lifts every
row to bitmap domain instead and returns the same bytes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import dispatch as D

ROW_WORDS = D.ROW_WORDS
ROW_SHAPE = D.ROW_SHAPE
KIND_EMPTY = D.KIND_EMPTY
KIND_ARRAY = D.KIND_ARRAY
KIND_BITMAP = D.KIND_BITMAP
KIND_RUN = D.KIND_RUN

_OPS = {
    "and": jnp.bitwise_and,
    "or": jnp.bitwise_or,
    "xor": jnp.bitwise_xor,
    "andnot": lambda a, b: jnp.bitwise_and(a, ~b),
}


def container_op_ref(a_bits: jax.Array, b_bits: jax.Array,
                     kinds: jax.Array, op: str):
    """Word op + popcount, unfused XLA formulation."""
    res = _OPS[op](a_bits, b_bits)
    ka, kb = kinds[0::2], kinds[1::2]
    live = jnp.logical_or(ka != 0, kb != 0)
    res = res * live[:, None].astype(jnp.uint16)
    card = jnp.sum(jax.lax.population_count(res).astype(jnp.int32), axis=-1)
    return res, card


def array_intersect_ref(a_arr: jax.Array, b_arr: jax.Array, cards: jax.Array):
    """searchsorted-based oracle for the batched array intersection."""
    card_a, card_b = cards[0::2], cards[1::2]

    def one(a, b, ca, cb):
        pos = jnp.searchsorted(b, a)
        pos_c = jnp.clip(pos, 0, ROW_WORDS - 1)
        found = (b[pos_c] == a) & (pos < cb)
        found = found & (jnp.arange(ROW_WORDS) < ca)
        return found.astype(jnp.uint16), jnp.sum(found.astype(jnp.int32))

    return jax.vmap(one)(a_arr, b_arr, card_a, card_b)


_KERNELS = D.make_and_kernels()


def intersect_dispatch_ref(a_data: jax.Array, b_data: jax.Array,
                           meta: jax.Array):
    """XLA mirror of the fused hybrid dispatch kernel.

    Same contract as ``kernel.intersect_dispatch_pallas``: per row, ``hits``
    is a 0/1 mask over the array side's slots (``out == 'mask_*'`` classes),
    or the word-op result (``'bits'`` classes: bitmap x bitmap AND, and the
    coverage-lifted run x bitmap / run x run forms); ``card`` is the exact
    intersection cardinality either way. ``meta`` is i32[6C] interleaved
    (kind_a, kind_b, card_a, card_b, nruns_a, nruns_b).
    """
    ka, kb, ca, cb, ra, rb = D.unpack_meta(meta)
    C = a_data.shape[0]
    a3 = a_data.reshape(C, *ROW_SHAPE)
    b3 = b_data.reshape(C, *ROW_SHAPE)

    # derived from the operands, so they vary with them under shard_map
    hits = jnp.zeros_like(a3)
    card = jnp.zeros_like(ca)
    for cls in D.AND_TABLE:
        pred = D.class_predicate(cls, ka, kb)
        fn = _KERNELS[cls.kernel]

        def run_class(args, fn=fn, cls=cls, pred=pred):
            hits, card = args

            def one(da, db, ca_i, cb_i, ra_i, rb_i):
                return fn(*D.bind_args(cls, da, db, ca_i, cb_i, ra_i, rb_i))

            h, c = jax.vmap(one)(a3, b3, ca, cb, ra, rb)
            sel = pred[:, None, None]
            return (jnp.where(sel, h, hits), jnp.where(pred, c, card))

        hits, card = jax.lax.cond(jnp.any(pred), run_class,
                                  lambda args: args, (hits, card))
    return hits.reshape(C, ROW_WORDS), card
