"""``PostingIndex`` — term -> Roaring posting rows in one stacked slab.

The whole vocabulary lives in ONE stacked ``repro.roaring.RoaringSlab``
``[n_rows, C]`` over a 32-bit document universe (``C = ceil(n_docs /
2^16)`` chunk rows per term, shared key row ``arange(C)``), built host-side
in one shot via the store's ``_stack_bitmaps`` placer — no per-term device
gathers. Row 0 is reserved as the empty posting so queries over unknown
terms resolve to well-formed empties instead of raising mid-batch.

On one device the index scores top-k queries by a scan plan
(``index.engine.plan_scan``) built once with it: each term's rows are read
in place over the slots its containers fill.

``shard(mesh)`` partitions the *term* axis across a device mesh
(``distributed.sharding.shard_postings``): each device owns a vocabulary
slice, per-term row fetches are device-local, and the stack is padded with
empty rows so the axis always divides. Batched query evaluation itself runs
on the service's replicated hot-term cache; the sharded stack is the
backing store those cache fills (the "sharded fetch") gather from, and what
``topk_by_card_sharded`` scores against (a sharded index has no scan
plan: it lifts its whole stack per scoring).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import roaring
from repro.core import jax_roaring as jr
from repro.core import py_roaring as pr
from repro.index import engine as _engine

__all__ = ["PostingIndex"]

EMPTY_ROW = 0          # reserved: the posting every unknown term maps to


def _chunks_for(n_docs: int) -> int:
    return max(1, -(-n_docs // jr.CHUNK_SIZE))


class PostingIndex:
    """Immutable inverted index: sorted term vocabulary over one stacked slab.

    ``stack`` is the ``[1 + n_terms (+ pad), C]`` stacked ``RoaringSlab``
    (row 0 reserved empty, then one posting row per term in sorted term
    order); ``terms`` is the sorted vocabulary tuple; ``mesh`` is set on
    sharded instances (``shard`` returns a new index, the host metadata is
    shared). ``plan`` is the scan plan of an index on one device, None
    when sharded.
    """

    def __init__(self, terms: Tuple[str, ...], stack: roaring.RoaringSlab,
                 n_docs: int, mesh=None, axis: str = "data"):
        self.terms = terms
        self.stack = stack
        self.n_docs = n_docs
        self.C = stack.C
        self.mesh = mesh
        self.axis = axis
        self._row = {t: EMPTY_ROW + 1 + i for i, t in enumerate(terms)}
        self.plan = _engine.plan_scan(stack) if mesh is None else None

    # -- constructors ---------------------------------------------------------
    @classmethod
    def from_postings(cls, postings: Dict[str, np.ndarray],
                      n_docs: int) -> "PostingIndex":
        """Raw ``term -> doc-id array`` postings -> index. Doc ids must lie
        in ``[0, n_docs)``; duplicates and ordering are normalized through
        the host oracle (best-of-three canonical containers, so index rows
        are byte-identical to what the engine's set algebra produces)."""
        from repro.store.store import _posting, _stack_bitmaps
        terms = tuple(sorted(postings))
        bitmaps = [pr.RoaringBitmap()]
        for t in terms:
            ids = np.unique(np.asarray(postings[t], np.int64))
            if ids.size and (ids[0] < 0 or ids[-1] >= n_docs):
                raise ValueError(
                    f"term {t!r} has doc ids outside [0, {n_docs})")
            bitmaps.append(_posting(ids))
        stack = _stack_bitmaps(bitmaps, n_docs, _chunks_for(n_docs))
        return cls(terms, stack, n_docs)

    @classmethod
    def from_store(cls, store, column: str) -> "PostingIndex":
        """Adopt a ``BitmapStore`` equality column as the vocabulary: each
        value ``v`` becomes term ``"{column}={v}"`` whose posting is the
        column's existing slot bitmap (host round-trip through
        ``slot_bitmap``; the store's slabs are not aliased)."""
        from repro.store.store import EqColumn
        col = store.column(column)
        if not isinstance(col, EqColumn):
            raise TypeError(f"column {column!r} is not an EqColumn")
        postings = {
            f"{column}={v}": store.slot_bitmap(col.base_slot + i).to_array()
            for i, v in enumerate(col.values)}
        return cls.from_postings(postings, store.n_rows)

    # -- lookups --------------------------------------------------------------
    @property
    def n_terms(self) -> int:
        return len(self.terms)

    @property
    def n_rows(self) -> int:
        """Stack rows including the reserved empty row and shard padding."""
        return self.stack.n_slabs

    def row(self, t: str) -> int:
        """Stack row for a term; unknown terms resolve to the reserved
        empty row (a query mentioning them is valid and matches nothing)."""
        return self._row.get(t, EMPTY_ROW)

    def term_of(self, row: int) -> Optional[str]:
        """Inverse of ``row`` (None for the reserved/padding rows)."""
        i = row - (EMPTY_ROW + 1)
        return self.terms[i] if 0 <= i < len(self.terms) else None

    def posting(self, t: str) -> roaring.RoaringSlab:
        """The term's posting as a single slab (row view of the stack)."""
        return self.stack[self.row(t)]

    # -- sharding -------------------------------------------------------------
    def shard(self, mesh, axis: str = "data") -> "PostingIndex":
        """New index whose stack is ``device_put`` with the term axis
        sharded over ``mesh[axis]`` — rows are padded with empty postings
        (key row preserved, kind 0) up to a multiple of the axis size so
        the partition always divides."""
        from repro.distributed.sharding import shard_postings
        stack = self.stack
        n = mesh.shape[axis]
        pad = (-stack.n_slabs) % n
        if pad:
            def padded(x, fill):
                shape = (pad,) + x.shape[1:]
                return jnp.concatenate([x, jnp.full(shape, fill, x.dtype)])
            stack = roaring.RoaringSlab(
                keys=jnp.concatenate(
                    [stack.keys, jnp.broadcast_to(stack.keys[:1],
                                                  (pad, stack.C))]),
                kinds=padded(stack.kinds, 0), cards=padded(stack.cards, 0),
                nruns=padded(stack.nruns, 0),
                payload=padded(stack.payload, 0), C=stack.C)
        stack = shard_postings(stack, mesh, axis)
        return PostingIndex(self.terms, stack, self.n_docs, mesh=mesh,
                            axis=axis)

    # -- scoring + accounting -------------------------------------------------
    def topk(self, query: roaring.RoaringSlab, k: int):
        """Top-k terms by ``|posting ∩ query|``: the scan plan's program on
        one device (``engine.topk_by_scan``), one batched-meta dispatch per
        mesh device when the index is sharded. Returns ``(scores i32[k],
        rows i32[k])`` — map rows back with ``term_of``."""
        if self.mesh is not None:
            return _engine.topk_by_card_sharded(self.stack, query, k,
                                                self.mesh, axis=self.axis)
        return _engine.topk_by_scan(self.stack, self.plan, query, k)

    def launch_model(self, expr) -> dict:
        """Analytic launch accounting for one lowered query expression —
        delegates to ``repro.index.launch_model`` (the service lowers term
        trees to these exprs; the obs plane cross-checks measured launch
        counters against this model)."""
        return _engine.launch_model(expr)

    def __repr__(self) -> str:
        return (f"PostingIndex(terms={self.n_terms}, docs={self.n_docs}, "
                f"C={self.C}" + (", sharded" if self.mesh else "") + ")")
