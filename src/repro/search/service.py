"""``SearchService`` — batched Boolean term queries over a ``PostingIndex``.

Three moving parts, each instrumented on the ``repro.obs`` plane:

**Planning** (``search.plan`` span): a term query tree (``Term`` / ``And``
/ ``Or`` / ``AndNot``) lowers to a ``repro.index`` expression whose leaves
are *positions* in the request's deduplicated term list. The lowered
expression is the request's structural signature: two requests batch
together exactly when their lowered expressions are equal — same tree
shape, any terms.

**Hot-term cache** (``search.cache`` span): query leaves never gather from
the (possibly mesh-sharded) posting stack directly. An LRU-managed
*resident cache stack* ``[cache_slots, C]`` holds the hot vocabulary on
the local device; cache misses fill in ONE batched gather from the backing
stack (the "sharded fetch"), hits skip it entirely. Hit / miss / eviction
counts land on ``search.cache.{hits,misses,evictions}``.

**Batched execution** (``search.execute`` span): a batch of B same-shape
requests packs into a single pseudo-stacked slab — leaf ``i`` of every
request concatenates along the container axis into row ``[i, B*C]`` (keys
``tile(arange(C), B)``), so the engine's row-state evaluators run the
whole batch as ONE expression over a ``C' = B*C`` stack: one fused
``fused_tree`` launch (``fused=True``) or one per-op tree-reduce,
amortizing the per-launch dispatch floor across the batch. Per-request
results are the ``[B, C]`` unflattening — bit-identical to evaluating each
request alone (the differential suite in ``tests/test_search.py`` holds
this against ``py_roaring`` and brute-force numpy). Execution runs under
the engine's degradation ladder (fused -> per-op -> XLA-ref), so injected
faults and backend failures degrade, not crash.

Admission mirrors ``serve.engine``: a FIFO queue, same-signature packing up
to ``max_batch``, capacity backpressure when a batch's distinct terms would
overflow the cache (the deferred request stays queued and ``requeues``
counts it), and the shared admission-queue gauge vocabulary
(``obs.publish_service_gauges`` under the ``search.`` prefix).

While telemetry is on, every host phase of ``step`` is a span carrying the
request ids it serves (``rid`` / ``rids``): ``search.admit`` (``queued``,
the queue length it walked), ``search.cache``, ``search.execute`` (one per
batch program) and inside it ``search.slots`` (the batch's cache slots
moved to the device), ``search.score`` (one per top-k scoring: the query slab's
build and the scoring's dispatch), ``search.sync`` (each host pull of
device results) and ``search.decode``; ``search.plan`` covers ``submit``,
and each admitted request files ``search.queued`` (submit to admission).
``search.execute`` and ``search.score`` carry ``rows_lifted``: the rows
their program hands to ``dispatch.lift_rows`` on the chip's path (the fused
batch program; the scoring: the query's rows and the scan plan's residual
rows), counted per call from the program's static shapes. ``search.score``
also carries ``slots_scanned`` and ``slots_total``: the container slots the
scoring reads and the slots the stack holds (``score_cost``).
With telemetry off the service writes nothing per request.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro import roaring
from repro.core import jax_roaring as jr
from repro.index import engine as _engine
from repro.search.index import PostingIndex

__all__ = ["Term", "And", "Or", "AndNot", "term", "and_", "or_", "andnot",
           "SearchService"]

MODES = ("count", "docs", "topk")


# =============================================================================
# query trees (term-level; lowered to repro.index expressions at submit)
# =============================================================================

@dataclasses.dataclass(frozen=True)
class Query:
    """Base class for Boolean term-query trees (static structure)."""


@dataclasses.dataclass(frozen=True)
class Term(Query):
    """One vocabulary term; unknown terms match the empty posting."""

    term: str


@dataclasses.dataclass(frozen=True)
class And(Query):
    children: Tuple[Query, ...]


@dataclasses.dataclass(frozen=True)
class Or(Query):
    children: Tuple[Query, ...]


@dataclasses.dataclass(frozen=True)
class AndNot(Query):
    a: Query
    b: Query


def term(t: str) -> Term:
    return Term(t)


def and_(*children: Query) -> Query:
    if not children:
        raise ValueError("and_() needs at least one child")
    return children[0] if len(children) == 1 else And(tuple(children))


def or_(*children: Query) -> Query:
    if not children:
        raise ValueError("or_() needs at least one child")
    return children[0] if len(children) == 1 else Or(tuple(children))


def andnot(a: Query, b: Query) -> AndNot:
    return AndNot(a, b)


def lower(q: Query) -> Tuple[_engine.Expr, Tuple[str, ...]]:
    """Term tree -> (positional ``repro.index`` expression, deduplicated
    term list). A term referenced twice lowers to the same leaf position —
    it occupies one cache slot and streams once. The expression doubles as
    the batching signature."""
    terms: List[str] = []
    pos: Dict[str, int] = {}

    def visit(n: Query) -> _engine.Expr:
        if isinstance(n, Term):
            if n.term not in pos:
                pos[n.term] = len(terms)
                terms.append(n.term)
            return _engine.Leaf(pos[n.term])
        if isinstance(n, And):
            return _engine.and_(*[visit(c) for c in n.children])
        if isinstance(n, Or):
            return _engine.or_(*[visit(c) for c in n.children])
        if isinstance(n, AndNot):
            return _engine.andnot(visit(n.a), visit(n.b))
        raise TypeError(f"not a search Query: {n!r}")

    return visit(q), tuple(terms)


# =============================================================================
# the LRU hot-term slab cache
# =============================================================================

# the cache's two programs: the sharded fetch (one batched gather from the
# possibly mesh-sharded posting stack) and the fill of the cache's slots
# (``jit_search_fetch`` / ``jit_search_fill`` in a device trace)
def search_fetch(sp, sc, sk, sn, rows):
    return sp[rows], sc[rows], sk[rows], sn[rows]


def search_fill(cp, cc, ck, cn, sp, sc, sk, sn, sl):
    return (cp.at[sl].set(sp), cc.at[sl].set(sc), ck.at[sl].set(sk),
            cn.at[sl].set(sn))


fetch_rows = jax.jit(search_fetch)
fill_slots = jax.jit(search_fill)


class _SlabCache:
    """Resident cache stack ``[slots, C]`` + LRU term -> slot map.

    The cache leaves mirror the posting stack's (payload / cards / kinds /
    nruns; the shared key row lives on the service). ``ensure`` is the only
    mutator: hits refresh recency, misses allocate (evicting LRU-first) and
    fill in one batched device gather from the backing stack."""

    def __init__(self, index: PostingIndex, slots: int):
        st = index.stack
        self.index = index
        self.slots = slots
        # one extra scratch row (index ``slots``): every fill pads its
        # miss list to the fixed width ``slots`` targeting it, so the
        # jitted fill compiles exactly ONE shape class — a variable-width
        # fill retraces per distinct miss count (measured ~240 ms per
        # novel shape on CPU), dwarfing the O(slots) gather it avoids
        # the cache lives on ONE device, where the query kernels run: a
        # Pallas kernel cannot be partitioned over a mesh
        self.device = min(st.payload.devices(), key=lambda d: d.id)
        self.payload, self.cards, self.kinds, self.nruns = jax.device_put(
            (jnp.zeros((slots + 1, index.C, jr.ROW_WORDS), st.payload.dtype),
             jnp.zeros((slots + 1, index.C), st.cards.dtype),
             jnp.zeros((slots + 1, index.C), st.kinds.dtype),
             jnp.zeros((slots + 1, index.C), st.nruns.dtype)), self.device)
        self.lru: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()
        self._free = list(range(slots - 1, -1, -1))

    def ensure(self, terms: Sequence[str]) -> Dict[str, int]:
        """Make every term resident; return term -> cache slot. Hits are
        moved to MRU *before* any eviction, so a batch that fits
        (``len(terms) <= slots``, guaranteed by admission) can never evict
        its own members."""
        reg = obs.registry()
        hits = [t for t in terms if t in self.lru]
        misses = [t for t in terms if t not in self.lru]
        for t in hits:
            self.lru.move_to_end(t)
        if hits:
            reg.counter("search.cache.hits").inc(len(hits))
        if misses:
            reg.counter("search.cache.misses").inc(len(misses))
            alloc = []
            for t in misses:
                if self._free:
                    s = self._free.pop()
                else:
                    _, s = self.lru.popitem(last=False)       # evict LRU
                    reg.counter("search.cache.evictions").inc()
                alloc.append(s)
                self.lru[t] = s
            st = self.index.stack
            pad = self.slots - len(misses)
            rows = jnp.asarray([self.index.row(t) for t in misses]
                               + [0] * pad, jnp.int32)
            sl = jnp.asarray(alloc + [self.slots] * pad, jnp.int32)
            # the sharded fetch: one batched gather from the (possibly
            # mesh-sharded) posting stack, moved to the cache's device
            fetched = jax.device_put(
                fetch_rows(st.payload, st.cards, st.kinds, st.nruns, rows),
                self.device)
            self.payload, self.cards, self.kinds, self.nruns = fill_slots(
                self.payload, self.cards, self.kinds, self.nruns, *fetched,
                sl)
        return {t: self.lru[t] for t in terms}

    def resident(self) -> Tuple[str, ...]:
        """Resident terms in LRU -> MRU order (test/introspection surface)."""
        return tuple(self.lru)


def query_programs(expr: _engine.Expr, B: int, L: int, C: int,
                   want_rows: bool):
    """(fused, per-op) batch evaluators of one (signature, B) class, over
    the cache arrays ``(payload, cards, kinds, nruns)`` and the batch's
    ``i32[B, L]`` cache slots. They run where the cache lives, one device.

    The batch packs as a pseudo-stack: leaf ``i``'s rows for all B requests
    concatenate along the container axis (``[L, B*C]``; keys
    ``tile(arange(C), B)``), so one engine evaluation covers the whole
    batch and per-request results are the ``[B, C]`` unflattening. Returns
    per-request counts ``i32[B]``, or with ``want_rows`` the per-row cards
    ``i32[B, C]`` and bitmap-domain rows ``u16[B, C, 4096]``. Their programs
    are ``jit_search_batch_fused`` / ``jit_search_batch_per_op``.
    """
    def make(ev, name):
        def f(payload, cards, kinds, nruns, slots):
            keys_flat = jnp.tile(jnp.arange(C, dtype=jnp.int32), B)

            def fl(x):
                x = jnp.swapaxes(x[slots], 0, 1)          # [L, B, C, ...]
                return x.reshape((L, B * C) + x.shape[3:])
            flat = roaring.RoaringSlab(
                keys=jnp.broadcast_to(keys_flat, (L, B * C)),
                kinds=fl(kinds), cards=fl(cards), nruns=fl(nruns),
                payload=fl(payload), C=B * C)
            data, card, kind = ev(flat, keys_flat, expr)
            rc = card.reshape(B, C)
            if not want_rows:
                return jnp.sum(rc, axis=1)
            words = jr._lift_rows(data, card, kind)
            return rc, words.reshape(B, C, jr.ROW_WORDS)
        f.__name__ = name
        return f

    return (make(_engine._fused_eval, "search_batch_fused"),
            make(_engine._eval, "search_batch_per_op"))


def batch_rows_lifted(B: int, L: int, C: int, want_rows: bool) -> int:
    """Rows the fused batch program hands to ``dispatch.lift_rows``: its
    ``L`` leaves' rows for each of ``B`` requests, and with ``want_rows``
    the ``B`` result rows."""
    return (L + want_rows) * B * C


def score_rows_lifted(n_rows: int, C: int) -> int:
    """Rows a scoring that lifts the whole stack (a sharded index's) hands
    to ``dispatch.lift_rows``: every stack row, and the query's rows
    once."""
    return (n_rows + 1) * C


def score_cost(index: PostingIndex) -> dict:
    """What one top-k scoring of ``index`` reads: ``rows_lifted``,
    ``slots_scanned`` (container slots read, a lifted row's 4096) and
    ``slots_total`` (the slots of the stack's rows)."""
    total = index.n_rows * index.C * jr.ROW_WORDS
    if index.plan is None:
        return {"rows_lifted": score_rows_lifted(index.n_rows, index.C),
                "slots_scanned": total, "slots_total": total}
    return {"rows_lifted": index.plan.rows_lifted,
            "slots_scanned": index.plan.slots_scanned, "slots_total": total}


# =============================================================================
# the service
# =============================================================================

@dataclasses.dataclass
class _Request:
    rid: int
    expr: _engine.Expr
    terms: Tuple[str, ...]
    mode: str
    k: int
    t_submit: float

    @property
    def gkey(self):
        return (self.expr, len(self.terms), self.mode, self.k)


class SearchService:
    """Batching query frontend over one ``PostingIndex``.

    ``mode`` per request: ``"count"`` -> int cardinality; ``"docs"`` ->
    sorted ``np.ndarray`` of matching doc ids; ``"topk"`` -> the k highest-
    scoring ``(term, |posting ∩ result|)`` pairs over the whole vocabulary
    (scored through the batched-meta dispatch, sharded over the mesh when
    the index is).

    ``jit_queries=False`` runs the evaluators eagerly — the configuration
    fault-injection tests use, since injected dispatch faults fire at
    Python dispatch time (under jit they can only fault at trace time).
    """

    def __init__(self, index: PostingIndex, *, max_batch: int = 8,
                 cache_slots: int = 64, fused: bool = True,
                 backend: Optional[str] = None, max_retries: int = 1,
                 backoff_s: float = 0.0, jit_queries: bool = True):
        if cache_slots < 1:
            raise ValueError("cache_slots must be >= 1")
        self.index = index
        self.max_batch = max_batch
        self.cache_slots = cache_slots
        self.fused = fused
        self.backend = backend
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.jit_queries = jit_queries
        self.queue: List[_Request] = []
        self.steps_run = 0
        self.requeues = 0
        self._cache = _SlabCache(index, cache_slots)
        self._keys_row = jnp.asarray(np.arange(index.C, dtype=np.int32))
        self._score_cost = score_cost(index)
        self._results: Dict[int, object] = {}
        self._fns: Dict[tuple, tuple] = {}
        self._next_rid = 0

    # -- request lifecycle ----------------------------------------------------
    def submit(self, q: Query, mode: str = "count", k: int = 10) -> int:
        """Lower + enqueue one query; returns the request id."""
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        rid = self._next_rid
        with obs.span("search.plan", mode=mode, rid=rid):
            expr, terms = lower(q)
        if len(terms) > self.cache_slots:
            raise ValueError(
                f"query has {len(terms)} distinct terms but the cache holds "
                f"{self.cache_slots} slots — it can never be admitted")
        self._next_rid += 1
        self.queue.append(_Request(rid, expr, terms, mode,
                                   k if mode == "topk" else 0,
                                   time.monotonic()))
        return rid

    def step(self) -> List[int]:
        """One service iteration: admit a same-signature batch, make its
        terms resident, execute it as one stacked launch. Returns the
        completed request ids."""
        with obs.span("search.admit", queued=len(self.queue)):
            batch = self._admit()
        if batch:
            rids = [r.rid for r in batch]
            batch_terms = []
            for r in batch:
                batch_terms.extend(t for t in r.terms
                                   if t not in batch_terms)
            with obs.span("search.cache", terms=len(batch_terms), rids=rids):
                slot_of = self._cache.ensure(batch_terms)
            with obs.span("search.execute", batch=len(batch),
                          mode=batch[0].mode, rids=rids,
                          rows_lifted=batch_rows_lifted(
                              len(batch), len(batch[0].terms), self.index.C,
                              batch[0].mode != "count")):
                self._execute(batch, slot_of)
        self.steps_run += 1
        if obs.enabled():
            obs.publish_service_gauges(
                "search", queue_depth=len(self.queue), active=len(batch),
                requeues=self.requeues, steps=self.steps_run,
                **{"cache.resident": len(self._cache.lru)})
        return [r.rid for r in batch]

    def run_until_done(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.queue:
                return
            self.step()
        raise RuntimeError(f"queue not drained after {max_steps} steps")

    def poll(self) -> Dict[int, object]:
        """All results completed since the last poll (drained)."""
        out, self._results = self._results, {}
        return out

    def take(self, rid: int):
        """Pop one completed request's result (KeyError when pending)."""
        return self._results.pop(rid)

    def search(self, q: Query, mode: str = "count", k: int = 10):
        """Synchronous single query: submit + drain + take."""
        rid = self.submit(q, mode, k)
        self.run_until_done()
        return self.take(rid)

    def search_many(self, queries: Sequence[Query], mode: str = "count",
                    k: int = 10) -> list:
        """Submit many queries, drain, return results in submit order —
        the batched entry (same-shape trees pack into stacked launches)."""
        rids = [self.submit(q, mode, k) for q in queries]
        self.run_until_done()
        return [self.take(r) for r in rids]

    def launch_model(self, q: Query) -> dict:
        """Analytic launch accounting for one request's lowered expression
        (batching does not change it: B same-shape requests run the same
        launches ONCE over a B-times-wider stack)."""
        expr, _ = lower(q)
        return self.index.launch_model(expr)

    def cache_stats(self) -> dict:
        reg = obs.registry()
        return {"hits": int(reg.value("search.cache.hits")),
                "misses": int(reg.value("search.cache.misses")),
                "evictions": int(reg.value("search.cache.evictions")),
                "resident": self._cache.resident()}

    # -- admission ------------------------------------------------------------
    def _admit(self) -> List[_Request]:
        """Greedy same-signature packing in FIFO order. Requests whose
        terms would overflow the cache defer to a later step (counted in
        ``requeues`` — the serve engine's backpressure convention);
        different-signature requests simply wait their turn."""
        if not self.queue:
            return []
        gkey = self.queue[0].gkey
        batch: List[_Request] = []
        remaining: List[_Request] = []
        terms: set = set()
        deferred = False
        for r in self.queue:
            if (deferred or r.gkey != gkey
                    or len(batch) >= self.max_batch):
                remaining.append(r)
                continue
            grown = terms | set(r.terms)
            if len(grown) > self.cache_slots:
                self.requeues += 1
                deferred = True
                remaining.append(r)
                continue
            batch.append(r)
            terms = grown
        self.queue = remaining
        if obs.enabled():
            now = time.monotonic()
            for r in batch:
                obs.record_span("search.queued", r.t_submit, now, rid=r.rid)
        return batch

    # -- execution ------------------------------------------------------------
    def _build_fns(self, expr: _engine.Expr, B: int, L: int,
                   want_rows: bool):
        """(fused, per-op) batch evaluators for one (signature, B) class,
        jitted unless ``jit_queries=False``."""
        fns = query_programs(expr, B, L, self.index.C, want_rows)
        return tuple(map(jax.jit, fns)) if self.jit_queries else fns

    def _execute(self, batch: List[_Request], slot_of: Dict[str, int]):
        B, L = len(batch), len(batch[0].terms)
        mode, k, expr = batch[0].mode, batch[0].k, batch[0].expr
        key = (expr, B, L, mode != "count")
        if key not in self._fns:
            self._fns[key] = self._build_fns(expr, B, L, mode != "count")
        fused_fn, per_op_fn = self._fns[key]
        c = self._cache
        rids = [r.rid for r in batch]
        with obs.span("search.slots", rids=rids):
            slots = jnp.asarray(
                np.asarray([[slot_of[t] for t in r.terms] for r in batch],
                           np.int32))
        args = (c.payload, c.cards, c.kinds, c.nruns, slots)
        out = _engine._run_query(
            lambda: fused_fn(*args), lambda: per_op_fn(*args),
            self.fused, self.backend, self.max_retries, self.backoff_s)
        if mode == "count":
            with obs.span("search.sync", rids=rids):
                counts = np.asarray(out)
            for i, r in enumerate(batch):
                self._results[r.rid] = int(counts[i])
            return
        rc, words = out
        if mode == "docs":
            with obs.span("search.sync", rids=rids):
                words_np = np.asarray(words)
            with obs.span("search.decode", rids=rids):
                for i, r in enumerate(batch):
                    self._results[r.rid] = _decode_docs(words_np[i])
            return
        C = self.index.C
        zeros = jnp.zeros((C,), jnp.int32)
        for i, r in enumerate(batch):
            with obs.span("search.score", rid=r.rid, **self._score_cost):
                qslab = roaring.RoaringSlab(
                    keys=self._keys_row,
                    kinds=jnp.where(rc[i] > 0, jr.KIND_BITMAP,
                                    jr.KIND_EMPTY).astype(jnp.int32),
                    cards=rc[i], nruns=zeros, payload=words[i], C=C)
                scores, rows = self.index.topk(qslab, r.k)
            with obs.span("search.sync", rids=[r.rid]):
                scores, rows = np.asarray(scores), np.asarray(rows)
            self._results[r.rid] = [(self.index.term_of(int(row)), int(s))
                                    for s, row in zip(scores, rows)]


def _decode_docs(words: np.ndarray) -> np.ndarray:
    """Bitmap-domain result rows ``u16[C, 4096]`` -> sorted doc ids. Rows
    are key-aligned to ``arange(C)``, so the flattened bit index IS the
    doc id (u16 words little-endian, bit order LSB-first)."""
    bits = np.unpackbits(words.reshape(-1).view(np.uint8),
                         bitorder="little")
    return np.nonzero(bits)[0].astype(np.int64)
