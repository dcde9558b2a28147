"""``BitmapStore`` — the paper's database scenario as a real store layer.

The 2014 paper's headline numbers (Table 3 / Figure 2) come from *bitmap
indexes*: per-column, per-value bitmaps over a table's rows, combined with
Boolean algebra to answer predicate queries. This module is that store:

  * **Equality columns** ingest to one posting slab per distinct value —
    the bitmap of row ids where ``column == value`` (the classic bitmap
    index).
  * **Bit-sliced columns** (``bsi=...`` at build time) ingest integer
    columns as one slab per *bit* of the value (O'Neil/Quass bit-sliced
    index) — ``b = max_value.bit_length()`` slabs answer any range or
    aggregate query, instead of one slab per distinct value.
  * All slabs — plus the row **universe** (slot 0) and a canonical **empty**
    slab (slot 1) — are ingested into ONE key-aligned stacked
    ``repro.roaring.RoaringSlab``, so a compiled predicate is an
    ``repro.index`` expression tree over stack members and every query runs
    through the fused executor (``execute(..., fused=True)``) and its
    Pallas→XLA degradation ladder unchanged.

Compilation is total: ``eq`` on an unseen value compiles to the empty slab,
``not_`` compiles to ``ANDNOT`` against the universe, ``range_`` on a
bit-sliced column compiles to the slice-comparison tree (``v <= K`` as the
MSB-down prefix walk), and ``range_`` on an integer-valued equality column
compiles to an OR over the stored values inside the bounds. The result is
bit-identical — values, cardinality, kinds, serialized bytes — to filtering
the raw records row by row (the differential oracle in
``tests/test_store.py`` checks exactly this).

Durability: ``save()`` emits every column slab through the portable
``RoaringFormatSpec`` codec (each blob is a standard Roaring interchange
stream a CRoaring/PyRoaring client can read) inside a small store container
format; ``load()`` treats the bytes as untrusted — see ``repro.store.io``.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro import index as ix
from repro.core import jax_roaring as jr
from repro.core import py_roaring as pr
from repro.kernels.roaring import ops as _kops
from repro.roaring import RoaringSlab
from repro.store import predicate as P

__all__ = ["BitmapStore", "EqColumn", "BsiColumn",
           "UNIVERSE_SLOT", "EMPTY_SLOT"]

UNIVERSE_SLOT = 0          # all rows — the NOT / open-range operand
EMPTY_SLOT = 1             # no rows — the unseen-value / empty-IN operand
_RESERVED_SLOTS = 2

_STORE_IDS = itertools.count()   # distinguishes per-store telemetry gauges


@dataclasses.dataclass(frozen=True)
class EqColumn:
    """An equality column: one posting slab per distinct value.

    ``values`` is the sorted tuple of distinct values (all int or all str —
    ``vkind`` names which); value ``values[i]`` lives at stack slot
    ``base_slot + i``.
    """

    name: str
    vkind: str                       # "int" | "str"
    values: Tuple
    base_slot: int

    @property
    def n_slabs(self) -> int:
        return len(self.values)


@dataclasses.dataclass(frozen=True)
class BsiColumn:
    """A bit-sliced integer column: slab ``base_slot + j`` holds the rows
    whose value has bit ``j`` set (LSB first), ``j < bits``."""

    name: str
    bits: int
    base_slot: int

    @property
    def n_slabs(self) -> int:
        return self.bits


def _chunks_for(n_rows: int) -> int:
    return max(1, -(-n_rows // jr.CHUNK_SIZE))


def _posting(row_ids: np.ndarray) -> pr.RoaringBitmap:
    """Sorted row ids -> best-of-three canonical host bitmap (canonical
    kinds are what make store bytes match the engine's query outputs)."""
    return pr.RoaringBitmap.from_sorted_unique(
        np.asarray(row_ids, np.int64)).run_optimize()


def _posting_of_mask(mask: np.ndarray) -> pr.RoaringBitmap:
    """Boolean row mask -> the same canonical host bitmap as ``_posting``
    of its row ids, with each dense chunk's bitmap words packed straight
    from the mask (no row ids, no per-value scatter)."""
    n_chunks = _chunks_for(mask.size)
    bits = np.zeros(n_chunks * pr.CHUNK_SIZE, bool)
    bits[: mask.size] = mask
    bits = bits.reshape(n_chunks, pr.CHUNK_SIZE)
    words = np.packbits(bits, axis=1, bitorder="little").view("<u8")
    cards = np.bitwise_count(words).sum(axis=1)
    keys, containers = [], []
    for k in np.flatnonzero(cards).tolist():
        keys.append(k)
        if cards[k] > pr.ARRAY_MAX:
            containers.append(pr.BitmapContainer(
                words[k].astype(np.uint64), int(cards[k])))
        else:
            containers.append(pr.ArrayContainer(
                np.flatnonzero(bits[k]).astype(np.uint16)))
    return pr.RoaringBitmap(keys, containers).run_optimize()


def _stack_bitmaps(bitmaps: Sequence[pr.RoaringBitmap], n_rows: int,
                   n_chunks: int) -> RoaringSlab:
    """Host bitmaps -> ONE stacked ``RoaringSlab`` aligned to the row
    universe's chunk keys.

    Every posting is a subset of ``[0, n_rows)``, so the shared key row is
    just ``arange(n_chunks)`` — no merge pass, no per-slab device gather
    (``roaring.stack`` would dispatch one gather per slab; a store routinely
    holds thousands of slabs, so rows are placed host-side in one shot).
    """
    N = len(bitmaps)
    kinds = np.zeros((N, n_chunks), np.int32)
    cards = np.zeros((N, n_chunks), np.int32)
    nruns = np.zeros((N, n_chunks), np.int32)
    payload = np.zeros((N, n_chunks, jr.ROW_WORDS), np.uint16)
    for s, rb in enumerate(bitmaps):
        for k, c in zip(rb.keys, rb.containers):
            cards[s, k] = c.cardinality
            if isinstance(c, pr.RunContainer):
                kinds[s, k] = jr.KIND_RUN
                nruns[s, k] = c.n_runs
                row = np.full((jr.ROW_WORDS,), 0xFFFF, np.uint16)
                row[0:2 * c.n_runs:2] = c.starts.astype(np.uint16)
                row[1:2 * c.n_runs:2] = c.lengths.astype(np.uint16)
                payload[s, k] = row
            elif isinstance(c, pr.BitmapContainer):
                kinds[s, k] = jr.KIND_BITMAP
                payload[s, k] = c.words.view(np.uint16)
            else:
                kinds[s, k] = jr.KIND_ARRAY
                row = np.full((jr.ROW_WORDS,), 0xFFFF, np.uint16)
                row[: c.arr.size] = c.arr
                payload[s, k] = row
    if n_rows > 0:
        keys_row = np.arange(n_chunks, dtype=np.int32)
    else:
        keys_row = np.full((n_chunks,), int(jr.KEY_SENTINEL), np.int32)
    keys = np.broadcast_to(keys_row, (N, n_chunks))
    return RoaringSlab(keys=jnp.asarray(keys), kinds=jnp.asarray(kinds),
                       cards=jnp.asarray(cards), nruns=jnp.asarray(nruns),
                       payload=jnp.asarray(payload), C=n_chunks)


def _norm_column(name: str, col: np.ndarray):
    """Column array -> (vkind, normalized values). Ints (any numpy integer
    dtype or bool) and strings are supported; anything else is rejected at
    ingest, not discovered at query time."""
    arr = np.asarray(col)
    if arr.ndim != 1:
        raise ValueError(f"column {name!r} must be 1-D, got shape "
                         f"{arr.shape}")
    if arr.dtype.kind in "iub":
        return "int", arr.astype(np.int64)
    if arr.dtype.kind in "US":
        return "str", arr.astype(str)
    if arr.dtype.kind == "O":
        kinds = {type(v) for v in arr.tolist()}
        if kinds <= {int, bool}:
            return "int", arr.astype(np.int64)
        if kinds == {str}:
            return "str", arr.astype(str)
        raise TypeError(f"column {name!r} mixes value types {sorted(k.__name__ for k in kinds)}")
    raise TypeError(f"column {name!r} has unsupported dtype {arr.dtype} "
                    "(store columns hold ints or strings)")


def _holds(atom: P.Pred, v) -> bool:
    """Whether value ``v`` of a column passes an atom on that column."""
    if isinstance(atom, P.Eq):
        return v == atom.value
    if isinstance(atom, P.In):
        return v in atom.values
    return ((atom.lo is None or v >= atom.lo)
            and (atom.hi is None or v <= atom.hi))


def _sum_program(plan, n_leaves: int, n_groups: int) -> Callable:
    """The program of one sum shape: ``(stack, members) -> i32[max(G, 1),
    B]`` cards, ``members`` the filter's leaves, then the groups, then the
    summed column's slices. ``store.filter`` names the leaves' lift; the
    filter's tape runs inside the aggregation kernel, under
    ``store.aggregate``."""

    def program(stack, members):
        leaves = groups = None
        if plan is not None:
            with jax.named_scope("store.filter"):
                leaves = ix.lift_members(stack, members[:n_leaves])
        with jax.named_scope("store.aggregate"):
            # lifted apart: a slice of one lift would be copied for the
            # kernel
            if n_groups:
                groups = ix.lift_members(
                    stack, members[n_leaves:n_leaves + n_groups])
            slices = ix.lift_members(stack, members[n_leaves + n_groups:])
            return ix.pair_cards(plan, leaves, groups, slices)

    return program


class BitmapStore:
    """Per-(column, value) Roaring bitmap index over columnar records."""

    def __init__(self, n_rows: int, columns: Sequence, bitmaps: Sequence):
        """Internal constructor — use ``build`` (from records) or ``load``
        (from a saved stream). ``bitmaps`` is the full slot-ordered list,
        including the universe and empty slots."""
        self.n_rows = int(n_rows)
        self.columns: Tuple = tuple(columns)
        self._bitmaps: List[pr.RoaringBitmap] = list(bitmaps)
        self._by_name: Dict[str, object] = {c.name: c for c in self.columns}
        self._eq_slot: Dict[Tuple[str, object], int] = {}
        for c in self.columns:
            if isinstance(c, EqColumn):
                for i, v in enumerate(c.values):
                    self._eq_slot[(c.name, v)] = c.base_slot + i
        self.n_chunks = _chunks_for(self.n_rows)
        self._stack = _stack_bitmaps(self._bitmaps, self.n_rows,
                                     self.n_chunks)
        # jitted whole-call executors per (expr, fused, backend): the engine
        # evaluates eagerly, where per-combine dispatch plus the root
        # finalize cost seconds per query; jitting the full tree makes the
        # steady state milliseconds (expression dataclasses are frozen, so
        # they hash as cache keys)
        self._query_fns: Dict[Tuple, Callable] = {}
        self._id = next(_STORE_IDS)
        self._cache_hits = 0       # key already held a jitted executor
        self._cache_misses = 0     # cold compile: new executor jitted
        self._cache_fallbacks = 0  # jitted call failed -> eager ladder

    # -- construction ---------------------------------------------------------
    @classmethod
    def build(cls, records: Dict[str, np.ndarray], *,
              bsi: Sequence[str] = ()) -> "BitmapStore":
        """Ingest columnar ``records`` (name -> equal-length 1-D arrays).

        Columns named in ``bsi`` must be non-negative integers and become
        bit-sliced-index columns (``range_`` / ``eq`` / ``in_`` / ``sum_``
        via slice algebra); every other column becomes an equality column
        with one posting slab per distinct value.
        """
        if not records:
            raise ValueError("build needs at least one column")
        bsi = set(bsi)
        unknown = bsi - set(records)
        if unknown:
            raise ValueError(f"bsi names not in records: {sorted(unknown)}")
        lengths = {name: len(np.asarray(col)) for name, col in records.items()}
        if len(set(lengths.values())) > 1:
            raise ValueError(f"column lengths differ: {lengths}")
        n_rows = next(iter(lengths.values()))

        universe = pr.RoaringBitmap.from_ranges([(0, n_rows)]) if n_rows \
            else pr.RoaringBitmap()
        bitmaps: List[pr.RoaringBitmap] = [universe, pr.RoaringBitmap()]
        columns: List = []
        for name, col in records.items():
            vkind, arr = _norm_column(name, col)
            if name in bsi:
                if vkind != "int":
                    raise TypeError(f"bsi column {name!r} must be integer")
                if n_rows and int(arr.min()) < 0:
                    raise ValueError(f"bsi column {name!r} holds negative "
                                     "values")
                bits = max(1, int(arr.max()).bit_length()) if n_rows else 1
                columns.append(BsiColumn(name, bits, len(bitmaps)))
                narrow = arr.astype(np.min_scalar_type((1 << bits) - 1))
                for j in range(bits):
                    bitmaps.append(_posting_of_mask(((narrow >> j) & 1) > 0))
            else:
                # stable argsort groups equal values with ascending row ids
                # (numpy radix-sorts 16-bit keys)
                key = arr
                if (vkind == "int" and n_rows
                        and int(arr.max()) - int(arr.min()) < (1 << 16)):
                    key = (arr - arr.min()).astype(np.uint16)
                order = np.argsort(key, kind="stable")
                svals = arr[order]
                if n_rows:
                    bounds = np.nonzero(svals[1:] != svals[:-1])[0] + 1
                    starts = np.concatenate(([0], bounds))
                    ends = np.concatenate((bounds, [n_rows]))
                else:
                    starts = ends = np.empty(0, np.int64)
                values = []
                base = len(bitmaps)
                for s, e in zip(starts.tolist(), ends.tolist()):
                    v = svals[s]
                    values.append(int(v) if vkind == "int" else str(v))
                    bitmaps.append(_posting(order[s:e]))
                columns.append(EqColumn(name, vkind, tuple(values), base))
        return cls(n_rows, columns, bitmaps)

    # -- persistence ----------------------------------------------------------
    def save(self) -> bytes:
        """Store -> durable byte stream (``repro.store.io`` container format;
        every slab is a portable ``RoaringFormatSpec`` blob)."""
        from repro.store import io as _io
        return _io.save_store(self)

    @classmethod
    def load(cls, data: bytes, *, limits=None, check: bool = False
             ) -> "BitmapStore":
        """Untrusted byte stream -> store (typed rejection on any structural
        violation; see ``repro.store.io.load_store``)."""
        from repro.store import io as _io
        return _io.load_store(data, limits=limits, check=check)

    # -- schema introspection --------------------------------------------------
    def column(self, name: str):
        """The ``EqColumn`` / ``BsiColumn`` schema entry for ``name``."""
        try:
            return self._by_name[name]
        except KeyError:
            raise KeyError(f"no column {name!r}; store has "
                           f"{sorted(self._by_name)}") from None

    @property
    def n_slabs(self) -> int:
        """Total stacked slabs (columns + the universe and empty slots)."""
        return len(self._bitmaps)

    def index_size_in_bytes(self) -> int:
        """Serialized size of the column slabs (the paper's index-size
        metric; the derivable universe/empty slots are excluded)."""
        return sum(rb.size_in_bytes()
                   for rb in self._bitmaps[_RESERVED_SLOTS:])

    def slot_bitmap(self, slot: int) -> pr.RoaringBitmap:
        """Host bitmap at a stack slot (interop/debug surface)."""
        return self._bitmaps[slot]

    # -- predicate compilation -------------------------------------------------
    def compile(self, pred: P.Pred) -> ix.Expr:
        """Predicate -> ``repro.index`` expression tree over the store's
        stacked slabs. Total: every well-typed predicate compiles, with
        unseen values landing on the empty slab."""
        if isinstance(pred, P.Eq):
            return self._compile_eq(pred.col, pred.value)
        if isinstance(pred, P.In):
            if not pred.values:
                return ix.leaf(EMPTY_SLOT)
            return ix.or_(*[self._compile_eq(pred.col, v)
                            for v in dict.fromkeys(pred.values)])
        if isinstance(pred, P.Range):
            return self._compile_range(pred.col, pred.lo, pred.hi)
        if isinstance(pred, P.AndP):
            return ix.and_(*[self.compile(c) for c in pred.children])
        if isinstance(pred, P.OrP):
            return ix.or_(*[self.compile(c) for c in pred.children])
        if isinstance(pred, P.NotP):
            return ix.andnot(ix.leaf(UNIVERSE_SLOT), self.compile(pred.child))
        raise TypeError(f"not a store predicate: {pred!r}")

    def _compile_eq(self, name: str, value) -> ix.Expr:
        col = self.column(name)
        if isinstance(col, EqColumn):
            if isinstance(value, str) != (col.vkind == "str"):
                raise TypeError(f"column {name!r} holds {col.vkind} values, "
                                f"predicate names {value!r}")
            slot = self._eq_slot.get((name, value))
            return ix.leaf(EMPTY_SLOT if slot is None else slot)
        v = int(value)
        if v < 0 or v >= (1 << col.bits):
            return ix.leaf(EMPTY_SLOT)
        # AND over all slices: bit set -> slice, bit clear -> NOT slice
        terms = [ix.leaf(col.base_slot + j) if (v >> j) & 1
                 else self._not(ix.leaf(col.base_slot + j))
                 for j in range(col.bits)]
        return ix.and_(*terms)

    def _compile_range(self, name: str, lo: Optional[int],
                       hi: Optional[int]) -> ix.Expr:
        col = self.column(name)
        if isinstance(col, EqColumn):
            if col.vkind != "int":
                raise TypeError(f"range_ over column {name!r} needs integer "
                                "values, column holds strings")
            hits = [col.base_slot + i for i, v in enumerate(col.values)
                    if (lo is None or v >= lo) and (hi is None or v <= hi)]
            if not hits:
                return ix.leaf(EMPTY_SLOT)
            return ix.or_(*[ix.leaf(s) for s in hits])
        # bit-sliced: [lo, hi] == LE(hi) ANDNOT LE(lo - 1)
        upper = self._bsi_le(col, hi) if hi is not None else \
            ix.leaf(UNIVERSE_SLOT)
        if lo is None or lo <= 0:
            return upper
        return ix.andnot(upper, self._bsi_le(col, lo - 1))

    def _bsi_le(self, col: BsiColumn, k: int) -> ix.Expr:
        """Rows with ``value <= k`` over the bit slices: the O'Neil/Quass
        MSB-down walk emitted as an expression tree — one OR of per-bit
        "strictly below at bit j" terms plus the all-bits-equal term, with
        the shared equality prefix reused as one sub-expression (the fused
        planner hash-conses it; the per-op path re-evaluates ``O(bits)``
        small combines)."""
        if k < 0:
            return ix.leaf(EMPTY_SLOT)
        if k >= (1 << col.bits) - 1:
            return ix.leaf(UNIVERSE_SLOT)
        below: List[ix.Expr] = []
        prefix: Optional[ix.Expr] = None      # "equal on all higher bits"
        for j in reversed(range(col.bits)):
            s_j = ix.leaf(col.base_slot + j)
            if (k >> j) & 1:
                term = self._not(s_j) if prefix is None else \
                    ix.and_(prefix, self._not(s_j))
                below.append(term)
                prefix = s_j if prefix is None else ix.and_(prefix, s_j)
            else:
                prefix = self._not(s_j) if prefix is None else \
                    ix.and_(prefix, self._not(s_j))
        return ix.or_(*below, prefix)

    @staticmethod
    def _not(e: ix.Expr) -> ix.Expr:
        return ix.andnot(ix.leaf(UNIVERSE_SLOT), e)

    # -- queries ---------------------------------------------------------------
    def cache_stats(self) -> dict:
        """Jit-query-cache accounting: ``hits`` (key already held a jitted
        executor), ``misses`` (cold compiles), ``fallbacks`` (jitted call
        failed and the query re-ran on the eager ladder — counted separately
        from cold compiles), ``entries``, and what the cache is keyed by.
        Also refreshes the ``store.query_cache.*{store=<id>}`` registry
        gauges."""
        self._publish_cache_gauges()
        return {"hits": self._cache_hits, "misses": self._cache_misses,
                "fallbacks": self._cache_fallbacks,
                "entries": len(self._query_fns),
                "keyed_by": "(expr, fused, backend)"}

    def _publish_cache_gauges(self) -> None:
        reg = obs.registry()
        sid = self._id
        reg.gauge("store.query_cache.hits", store=sid).set(self._cache_hits)
        reg.gauge("store.query_cache.misses",
                  store=sid).set(self._cache_misses)
        reg.gauge("store.query_cache.fallbacks",
                  store=sid).set(self._cache_fallbacks)
        reg.gauge("store.query_cache.entries",
                  store=sid).set(len(self._query_fns))

    def _run_cached(self, key: Tuple, make_fn: Callable, eager_fn: Callable,
                    *args):
        """One cached-executor run: cache lookup (hit/miss accounting), the
        jitted call on ``(stack, *args)`` under a ``store.execute`` span,
        and the eager-ladder fallback (its own span + counter) when the
        jitted call fails."""
        fn = self._query_fns.get(key)
        if fn is None:
            # jax.jit wrapping is lazy: the actual trace+compile cost lands
            # inside the first call, i.e. the cache=miss execute span
            self._cache_misses += 1
            cache = "miss"
            fn = make_fn()
            self._query_fns[key] = fn
        else:
            self._cache_hits += 1
            cache = "hit"
        try:
            with obs.span("store.execute", cache=cache):
                return fn(self._stack, *args)
        except Exception:
            self._cache_fallbacks += 1
            with obs.span("store.fallback_eager"):
                return eager_fn()

    def query(self, pred: P.Pred, *, fused: bool = False,
              backend: Optional[str] = None, max_retries: int = 1,
              backoff_s: float = 0.0) -> RoaringSlab:
        """Rows matching ``pred`` as a canonical ``RoaringSlab`` of row ids —
        one ``index.execute`` run (``fused=True`` = one kernel launch for the
        whole tree) through the engine's degradation ladder.

        The whole call is jitted per compiled tree shape (first use pays one
        compile, repeats are launch-only). A failure inside the jitted call
        falls back to the eager engine, whose runtime retry/backoff ladder
        the jit boundary would otherwise swallow. With telemetry enabled
        (``repro.obs.enable()``) the call records a compile -> execute span
        tree, output-kind histograms, and the query-cache gauges.
        """
        with obs.span("store.query", fused=fused):
            with obs.span("store.compile"):
                expr = self.compile(pred)
            out = self._run_cached(
                (expr, fused, backend),
                lambda: jax.jit(lambda stack: ix.execute(
                    stack, expr, fused=fused, backend=backend)),
                lambda: ix.execute(self._stack, expr, fused=fused,
                                   backend=backend, max_retries=max_retries,
                                   backoff_s=backoff_s))
            if obs.enabled():
                obs.record_kinds("store.output_kinds", out.kinds)
                self._publish_cache_gauges()
            return out

    def count(self, pred: P.Pred, *, fused: bool = False,
              backend: Optional[str] = None, max_retries: int = 1,
              backoff_s: float = 0.0) -> int:
        """|rows matching ``pred``| without materializing the result slab
        (jitted whole-call with the same cache/fallback as ``query``)."""
        with obs.span("store.count", fused=fused):
            with obs.span("store.compile"):
                expr = self.compile(pred)
            out = self._run_cached(
                ("card", expr, fused, backend),
                lambda: jax.jit(lambda stack: ix.execute_card(
                    stack, expr, fused=fused, backend=backend)),
                lambda: ix.execute_card(self._stack, expr, fused=fused,
                                        backend=backend,
                                        max_retries=max_retries,
                                        backoff_s=backoff_s))
            if obs.enabled():
                self._publish_cache_gauges()
            return int(out)

    def query_indices(self, pred: P.Pred, **kw) -> np.ndarray:
        """Matching row ids as a sorted host ``int64`` array."""
        return self.query(pred, **kw).to_roaring().to_array()

    def sum_(self, name: str, pred: Optional[P.Pred] = None) -> int:
        """Sum of a bit-sliced column over the rows matching ``pred`` (all
        rows when ``None``): ``group_sum``'s program with one group of every
        row."""
        return self._sum(name, None, pred)[None]

    def group_sum(self, name: str, by: str,
                  pred: Optional[P.Pred] = None) -> Dict[object, int]:
        """``{v: Σ name over the rows matching pred with by == v}`` for every
        stored value ``v`` of the equality column ``by``: a sum grouped by
        one column (FeatureBase's ``GroupBy(Rows(by), aggregate=Sum(name))``).

        One jitted program per (predicate shape, groups, slices) takes
        ``|filter ∩ {by = v} ∩ slice_j|`` for every group and bit slice
        (``index.pair_cards``): the filter runs as a fused tape inside the
        aggregation kernel and its result is never built. The host weighs
        the i32 cards by ``2^j`` in Python ints, so sums are exact past
        2^31. Atoms of a top-level AND on ``by`` itself pick the groups
        read (the others sum to 0) and leave the filter.
        """
        if not isinstance(self.column(by), EqColumn):
            raise TypeError(f"group_sum groups by an equality column; {by!r} "
                            "is bit-sliced")
        return self._sum(name, by, pred)

    def _sum(self, name: str, by: Optional[str],
             pred: Optional[P.Pred]) -> Dict[object, int]:
        col = self.column(name)
        if not isinstance(col, BsiColumn):
            raise TypeError(f"sums need a bit-sliced column; {name!r} is an "
                            "equality column")
        with obs.span("store.sum", column=name, by=by) as sp:
            with obs.span("store.compile"):
                values, groups, pred = self._groups(by, pred)
                plan, leaves = (None, ()) if pred is None else \
                    ix.lower(self.compile(pred))
            slices = tuple(range(col.base_slot, col.base_slot + col.bits))
            L, G, B = len(leaves), len(groups), len(slices)
            # the predicate may rule out every group: nothing is read
            rows_read = (L + G + B) * self.n_chunks if values else 0
            for k, v in (("groups", G), ("slices", B), ("leaves", L),
                         ("rows_read", rows_read)):
                sp.set_attr(k, v)
            sums = dict.fromkeys(
                (None,) if by is None else self.column(by).values, 0)
            if not values:
                return sums
            obs.registry().counter("store.rows_read").inc(rows_read)
            members = jnp.asarray(leaves + groups + slices, jnp.int32)
            program = _sum_program(plan, L, G)

            def eager():
                with _kops.backend_scope("xla"):
                    return program(self._stack, members)

            cards = self._run_cached(("sum", plan, L, G, B),
                                     lambda: jax.jit(program), eager, members)
            with obs.span("store.sync"):
                cards = np.asarray(cards).tolist()
        for v, row in zip(values, cards):
            sums[v] = sum(c << j for j, c in enumerate(row))
        return sums

    def _groups(self, by: Optional[str], pred: Optional[P.Pred]):
        """``(values, slots, filter)``: the values of ``by`` whose groups are
        read, their slots, and the predicate left to filter by (no ``by``:
        one group of every row, ``(None,)``, with no slot).
        Atoms on ``by`` in a top-level AND (or ``pred`` itself) pick the
        groups: the values outside them sum to 0, and for those inside the
        atom holds on every row of the group, so it leaves the filter."""
        if by is None:
            return (None,), (), pred
        col = self.column(by)
        atoms = () if pred is None else (
            pred.children if isinstance(pred, P.AndP) else (pred,))
        on_by = [a for a in atoms if isinstance(a, (P.Eq, P.In, P.Range))
                 and a.col == by]
        rest = [a for a in atoms if not any(a is b for b in on_by)]
        for a in on_by:
            self.compile(a)             # the same type checks as a query
        values = [v for v in col.values if all(_holds(a, v) for a in on_by)]
        groups = tuple(self._eq_slot[(by, v)] for v in values)
        return values, groups, P.and_(*rest) if rest else None

    def __repr__(self) -> str:
        parts = ", ".join(
            f"{c.name}:{'bsi' + str(c.bits) if isinstance(c, BsiColumn) else len(c.values)}"
            for c in self.columns)
        return (f"BitmapStore(n_rows={self.n_rows}, slabs={self.n_slabs}, "
                f"columns=[{parts}])")
