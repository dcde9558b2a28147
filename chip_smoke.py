#!/usr/bin/env python3
"""Chip smoke test: the served query paths, once, on a TPU, at a real size.

    python chip_smoke.py            # one chip: the search and store phases
    python chip_smoke.py --chips 4  # four chips: the sharded search path only
    python chip_smoke.py --small    # the same phases at a tiny size; runs on
                                    # the CPU too (rehearsal and CPU tests)

Phases (one process; every answer is checked exactly against numpy over the
same generated data, made from ``--seed``):

* **search** — ``PostingIndex.from_postings`` over Zipf postings at the MS
  MARCO passage collection's size (8,841,823 documents, arXiv:1611.09268;
  135 chunk rows per term, 2,048 terms: about 2.27 GB of posting rows on
  the device), served by ``SearchService`` at its defaults. 200 queries of
  2-4 terms as AND, OR and ANDNOT trees in ``count``, ``docs`` and ``topk``
  modes.
* **store** — ``BitmapStore.build`` over census-like records at census1881's
  row count (4,277,807, ``benchmarks/synth.REAL_SPECS``), BSI on the integer
  columns; a dozen predicates through ``query``, ``count`` and ``sum_``,
  fused and per-op.
* **sharded** (``--chips 4`` only) — the same index sharded over four
  devices, ``SearchService`` on top, ``topk_by_card_sharded`` and
  ``batched_and_card_sharded``, each compared with the one-device index and
  numpy.

After the phases the run fails unless no dispatch failed, no query fell back
to a lower rung or to the store's eager path, and every launch ran on the
chip's backend (Pallas). Timings printed here are smoke timings, not
benchmark numbers. Without a TPU the script exits non-zero before any phase,
unless ``--small`` is given. The last line of standard output is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

MSMARCO_PASSAGES = 8_841_823
CENSUS1881_ROWS = 4_277_807


def log(*parts) -> None:
    print(*parts, flush=True)


# =============================================================================
# numpy oracle (independent of the library under test)
# =============================================================================

class SearchOracle:
    """Term queries evaluated on packed little-endian numpy bitsets."""

    def __init__(self, postings: dict, n_docs: int):
        import numpy as np
        self.np = np
        self.postings = postings
        self.n_docs = n_docs
        self._bits: dict = {}

    def bits(self, t: str):
        np = self.np
        if t not in self._bits:
            dense = np.zeros(self.n_docs, bool)
            dense[self.postings.get(t, np.empty(0, np.int64))] = True
            self._bits[t] = np.packbits(dense, bitorder="little")
        return self._bits[t]

    def eval(self, q):
        from repro.search import service as S
        if isinstance(q, S.Term):
            return self.bits(q.term)
        if isinstance(q, S.And):
            out = self.eval(q.children[0])
            for c in q.children[1:]:
                out = out & self.eval(c)
            return out
        if isinstance(q, S.Or):
            out = self.eval(q.children[0])
            for c in q.children[1:]:
                out = out | self.eval(c)
            return out
        if isinstance(q, S.AndNot):
            return self.eval(q.a) & ~self.eval(q.b)
        raise TypeError(q)

    def count(self, bits) -> int:
        return int(self.np.bitwise_count(bits).sum())

    def docs(self, bits):
        np = self.np
        dense = np.unpackbits(bits, bitorder="little")[: self.n_docs]
        return np.flatnonzero(dense).astype(np.int64)

    def scores(self, bits) -> dict:
        """term -> |posting ∩ result| for the whole vocabulary."""
        dense = self.np.unpackbits(bits, bitorder="little")[: self.n_docs]
        return {t: int(dense[ids].sum()) for t, ids in self.postings.items()}


def check_topk(got, scores: dict, k: int, what: str) -> None:
    """Top-k agrees with the oracle up to the order of tied scores. The
    index ranks its reserved empty row too (term ``None``, score 0)."""
    scores = {**scores, None: 0}
    want = sorted(scores.values(), reverse=True)[:k]
    assert [s for _, s in got] == want, (what, got, want)
    for t, s in got:
        assert scores[t] == s, (what, t, s, scores[t])


# =============================================================================
# phases
# =============================================================================

def _queries(terms, per_shape, seed: int):
    """(query, mode) pairs: AND, OR and ANDNOT trees of 2-4 Zipf-drawn terms
    in count, docs and topk modes. ``per_shape(n)`` sizes each shape from
    its full-size count; full-size counts are multiples of the service's
    batch of 8, so every batch of a shape has the same size."""
    from repro.search import loadgen
    from repro.search import service as S

    def draw(n, k, op, s):
        return loadgen.zipf_queries(terms, n, 1.0, s, terms_per_query=k,
                                    op=op)

    def andnot2(q):
        return S.andnot(q.children[0], q.children[1])

    def andnot3(q):
        return S.andnot(S.and_(q.children[0], q.children[1]), q.children[2])

    mix = [  # (full-size count, mode, maker): 200 queries
        (32, "count", lambda n, s: draw(n, 2, "and", s)),
        (24, "count", lambda n, s: draw(n, 3, "and", s)),
        (24, "count", lambda n, s: draw(n, 2, "or", s)),
        (16, "count", lambda n, s: draw(n, 4, "or", s)),
        (24, "count", lambda n, s: [andnot2(q) for q in draw(n, 2, "and", s)]),
        (16, "count", lambda n, s: [andnot3(q) for q in draw(n, 3, "and", s)]),
        (16, "docs", lambda n, s: draw(n, 2, "and", s)),
        (16, "docs", lambda n, s: draw(n, 2, "or", s)),
        (16, "docs", lambda n, s: [andnot3(q) for q in draw(n, 3, "and", s)]),
        (8, "topk", lambda n, s: draw(n, 2, "and", s)),
        (8, "topk", lambda n, s: draw(n, 3, "or", s)),
    ]
    out = []
    for i, (n, mode, make) in enumerate(mix):
        out += [(q, mode) for q in make(per_shape(n), seed * 100 + i)]
    return out


def search_phase(args, intended: str) -> dict:
    import jax
    import numpy as np

    from benchmarks import synth
    from repro.search.index import PostingIndex
    from repro.search.service import SearchService

    t0 = time.perf_counter()
    raw = synth.gen_zipf_postings(args.terms, args.docs, 1.0, args.seed)
    terms = [f"t{r:05d}" for r in range(args.terms)]   # rank order == sorted
    postings = dict(zip(terms, raw))
    index = PostingIndex.from_postings(postings, args.docs)
    jax.block_until_ready(index.stack.payload)
    setup_s = time.perf_counter() - t0
    st = index.stack
    resident = sum(int(x.nbytes) for x in
                   (st.payload, st.keys, st.kinds, st.cards, st.nruns))
    log(f"search: {index!r}, {sum(p.size for p in raw)} postings, "
        f"posting rows resident on the device: {resident} bytes")

    svc = SearchService(index)
    oracle = SearchOracle(postings, args.docs)
    work = _queries(terms, args.per_shape, args.seed)
    t0 = time.perf_counter()
    rids = [(svc.submit(q, mode, k=10), q, mode) for q, mode in work]
    svc.run_until_done()
    results = [(svc.take(rid), q, mode) for rid, q, mode in rids]
    wall_s = time.perf_counter() - t0
    log(f"search: served in {wall_s:.1f} s, checking against numpy")

    n_docs_checked = n_topk_checked = 0
    for got, q, mode in results:
        bits = oracle.eval(q)
        if mode == "count":
            assert got == oracle.count(bits), (q, got, oracle.count(bits))
        elif mode == "docs":
            want = oracle.docs(bits)
            assert np.array_equal(np.asarray(got), want), q
            n_docs_checked += 1
        else:
            check_topk(got, oracle.scores(bits), 10, q)
            n_topk_checked += 1
    log(f"search: {len(results)} queries answered "
        f"({sum(m == 'count' for _, _, m in results)} counts, "
        f"{n_docs_checked} doc lists, {n_topk_checked} top-k lists), all "
        "equal to numpy; service steps "
        f"{svc.steps_run}, cache {svc.cache_stats()['hits']} hits / "
        f"{svc.cache_stats()['misses']} misses")
    _topk_memory(index, terms[0])
    return {"setup_s": setup_s, "wall_s": wall_s, "queries": len(results),
            "resident_bytes": resident}


def _topk_memory(index, term: str) -> None:
    """One top-k scoring of the whole stack alone: the compiled program's
    own count of its temporaries beside the device's memory counters."""
    import jax
    from repro.index import engine as E

    st = index.stack
    args = (st.payload, st.cards, st.kinds, st.nruns,
            *E._align_query(st, index.posting(term)))
    mem = E.index_score.lower(*args).compile().memory_analysis()
    dev = jax.devices()[0]
    before = dev.memory_stats() or {}
    jax.block_until_ready(E.index_score(*args))
    after = dev.memory_stats() or {}
    log("search: one top-k scoring of "
        f"{st.kinds.shape[0]} x {index.C} rows: compiled "
        + json.dumps({k: getattr(mem, k, None) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes")})
        + "; device memory before "
        + json.dumps({k: before.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use")})
        + ", after "
        + json.dumps({k: after.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use")}))


def _store_predicates(small: bool):
    """(predicate, paths) pairs; ``paths`` names the calls beside the fused
    ``count``: ``q`` fused ``query``, ``C`` / ``Q`` per-op count / query.
    A ``query`` compiles the best-of-three finalize into each tree's
    program (about 25 s per tree for the chip), and the per-op path
    compiles a tree combine by combine (the BSI range trees slowest), so
    those paths take a few trees each, one BSI range tree among them; the
    fused count takes all."""
    from repro.store import predicate as P
    preds = [
        (P.eq("cat0", 1), "qCQ"),
        (P.in_("cat2", (3, 5, 7)), "C"),
        (P.range_("int0", 30, 45), "qCQ"),
        (P.range_("int1", None, 20_000), ""),
        (P.and_(P.eq("cat1", 2), P.range_("int0", 18, 65)), "q"),
        (P.or_(P.eq("cat3", 4), P.eq("cat3", 9)), "C"),
        (P.not_(P.eq("cat0", 0)), "C"),
        (P.and_(P.eq("cat1", 2), P.in_("cat2", (3, 5, 7))), "qCQ"),
        (P.and_(P.in_("cat1", (1, 2, 3)),
                P.not_(P.range_("int1", 50_000, None))), ""),
        (P.or_(P.and_(P.eq("cat0", 1), P.eq("cat2", 4)),
               P.range_("int0", 80, None)), ""),
        (P.eq("int0", 42), ""),
        (P.in_("int0", (20, 30, 40)), ""),
        (P.and_(P.range_("int0", 25, 35), P.range_("int1", 10_000, 30_000)),
         ""),
    ]
    if small:   # per-op, the BSI range tree compiles for ~100 s on a CPU
        return [(preds[2][0], "q"), preds[6], preds[7]]
    return preds


def _mask(records, pred):
    """numpy row filter for a store predicate."""
    import numpy as np
    from repro.store import predicate as P
    if isinstance(pred, P.Eq):
        return records[pred.col] == pred.value
    if isinstance(pred, P.In):
        return np.isin(records[pred.col], list(pred.values))
    if isinstance(pred, P.Range):
        col = records[pred.col]
        m = np.ones(col.shape, bool)
        if pred.lo is not None:
            m &= col >= pred.lo
        if pred.hi is not None:
            m &= col <= pred.hi
        return m
    if isinstance(pred, P.AndP):
        return np.logical_and.reduce([_mask(records, c)
                                      for c in pred.children])
    if isinstance(pred, P.OrP):
        return np.logical_or.reduce([_mask(records, c) for c in pred.children])
    if isinstance(pred, P.NotP):
        return ~_mask(records, pred.child)
    raise TypeError(pred)


def store_phase(args, intended: str) -> dict:
    import jax
    import numpy as np

    from benchmarks import synth
    from repro.store.store import BitmapStore

    t0 = time.perf_counter()
    records = synth.gen_census_like(args.rows, args.seed)
    store = BitmapStore.build(records, bsi=("int0", "int1"))
    jax.block_until_ready(store._stack.payload)
    setup_s = time.perf_counter() - t0
    log(f"store: {store!r}, stack rows resident: "
        f"{int(store._stack.payload.nbytes)} bytes")

    t0 = time.perf_counter()
    preds = _store_predicates(args.small)
    n = 0
    for i, (pred, paths) in enumerate(preds):
        log(f"store: predicate {i} at {time.perf_counter() - t0:.1f} s")
        want_rows = np.flatnonzero(_mask(records, pred))
        for fused, count, query in ((True, True, "q" in paths),
                                    (False, "C" in paths, "Q" in paths)):
            if count:
                assert store.count(pred, fused=fused) == want_rows.size, \
                    (pred, fused)
                n += 1
            if query:
                got = store.query_indices(pred, fused=fused)
                assert np.array_equal(got, want_rows), (pred, fused)
                n += 1
    # sum_ filters through a per-op query: the predicates already run so
    eq_preds = [p for p, paths in preds if "Q" in paths]
    for name, pred in (("int0", None), ("int1", eq_preds[0]),
                       ("int0", eq_preds[-1])):
        mask = np.ones(args.rows, bool) if pred is None else \
            _mask(records, pred)
        assert store.sum_(name, pred) == int(records[name][mask].sum()), \
            (name, pred)
        n += 1
    wall_s = time.perf_counter() - t0
    stats = store.cache_stats()
    assert stats["fallbacks"] == 0, stats
    log(f"store: {n} answers from {len(preds)} predicates (query, count, "
        f"sum_; fused and per-op), all equal to numpy; query cache {stats}")
    return {"setup_s": setup_s, "wall_s": wall_s, "answers": n}


def sharded_phase(args, intended: str) -> dict:
    import jax
    import numpy as np

    from benchmarks import synth
    from repro import index as ix
    from repro.core import jax_roaring as jr
    from repro.launch.mesh import make_test_mesh
    from repro.roaring import RoaringSlab
    from repro.search import service as S
    from repro.search.index import PostingIndex
    from repro.search.service import SearchService

    n_dev = args.chips
    t0 = time.perf_counter()
    raw = synth.gen_zipf_postings(args.terms, args.docs, 1.0, args.seed)
    terms = [f"t{r:05d}" for r in range(args.terms)]
    postings = dict(zip(terms, raw))
    one = PostingIndex.from_postings(postings, args.docs)
    mesh = make_test_mesh(data=n_dev, model=1)
    sharded = one.shard(mesh, axis="data")
    jax.block_until_ready(sharded.stack.payload)
    setup_s = time.perf_counter() - t0

    rows = sharded.n_rows
    for name in ("payload", "kinds", "cards", "nruns"):
        shards = getattr(sharded.stack, name).addressable_shards
        assert len({s.device for s in shards}) == n_dev, name
        assert all(s.data.shape[0] * n_dev == rows for s in shards), name
    log(f"sharded: {rows} term rows over {n_dev} devices, "
        f"{rows // n_dev} rows each")

    oracle = SearchOracle(postings, args.docs)
    # one query of each AND / OR count and top-k shape: top-k is the path
    # the mesh shards; count queries fill the cache from the sharded stack
    work = [(q, m) for q, m in _queries(terms, lambda n: 1, args.seed)
            if m == "topk" or (m == "count" and isinstance(q, (S.And, S.Or)))]
    t0 = time.perf_counter()
    svc_one, svc_sh = SearchService(one), SearchService(sharded)
    cache = svc_sh._cache
    # the hot-term cache and its query kernels stay on one device: a Pallas
    # kernel in a program partitioned over the mesh does not lower
    assert all(len(x.devices()) == 1 for x in
               (cache.payload, cache.cards, cache.kinds, cache.nruns))
    got_one = [svc_one.search(q, m, k=10) for q, m in work]
    got_sh = [svc_sh.search(q, m, k=10) for q, m in work]
    assert len(cache.payload.devices()) == 1
    for (q, m), a, b in zip(work, got_one, got_sh):
        bits = oracle.eval(q)
        if m == "count":
            assert a == b == oracle.count(bits), (q, a, b)
        else:
            scores = oracle.scores(bits)
            check_topk(a, scores, 10, q)
            check_topk(b, scores, 10, q)

    # the engine entries directly: the sharded stack scored against queries
    C = one.C
    for q, _ in work:
        bits = oracle.eval(q)
        dense = np.unpackbits(bits, bitorder="little")[: args.docs]
        qslab = RoaringSlab.from_values(np.flatnonzero(dense), C,
                                        C * jr.CHUNK_SIZE)
        s_one = np.asarray(ix.batched_and_card(one.stack, qslab))
        s_sh = np.asarray(ix.batched_and_card_sharded(
            sharded.stack, qslab, mesh, axis="data"))
        assert np.array_equal(s_sh[: s_one.size], s_one), q
        assert not s_sh[s_one.size:].any(), q        # padding rows score 0
        scores = oracle.scores(bits)
        want = np.asarray([0] + [scores[t] for t in terms])
        assert np.array_equal(s_one, want), q
        v1, r1 = ix.topk_by_card(one.stack, qslab, 10)
        v4, r4 = ix.topk_by_card_sharded(sharded.stack, qslab, 10, mesh,
                                         axis="data")
        assert np.array_equal(np.asarray(v1), np.asarray(v4)), q
        assert np.array_equal(np.asarray(v1), np.sort(want)[::-1][:10]), q
        want_sh = np.zeros(s_sh.size, want.dtype)      # + padding rows
        want_sh[: want.size] = want
        assert np.array_equal(want_sh[np.asarray(r4)], np.asarray(v4)), q
    wall_s = time.perf_counter() - t0
    log(f"sharded: {len(work)} service queries on one device and on "
        f"{n_dev}, each also scored by batched_and_card / topk_by_card, "
        "all equal to each other and to numpy")
    return {"setup_s": setup_s, "wall_s": wall_s, "queries": len(work)}


# =============================================================================
# entry point
# =============================================================================

def _compile_seconds():
    """Accumulate JAX's tracing, lowering and backend-compile durations."""
    import jax
    total = {"s": 0.0}

    def on_event(event, duration, **_):
        if event.startswith("/jax/core/compile/") or \
                event.startswith("/jax/compilation_cache/"):
            if event.endswith("_duration"):
                total["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return total


def _check_backends(intended: str) -> dict:
    import repro.obs as obs
    reg = obs.registry()
    failures = int(reg.value("index.dispatch_failures"))
    fallbacks = int(reg.value("index.fallbacks"))
    rungs, launches = {}, {}
    for key, c in reg.counters():
        name, labels = key[0], dict(key[1])
        if name == "index.rung_taken":
            rungs[f"{labels['kind']}/{labels['backend']}"] = c.value
        elif name == "roaring.launches":
            launches[f"{labels['entry']}/{labels['backend']}"] = c.value
    log(f"ladder: dispatch_failures={failures} fallbacks={fallbacks} "
        f"rungs={json.dumps(rungs, sort_keys=True)}")
    log(f"launch counters (entry/backend; counted per dispatch or trace): "
        f"{json.dumps(launches, sort_keys=True)}")
    assert failures == 0 and fallbacks == 0, (failures, fallbacks)
    assert rungs and all(k.endswith("/" + intended) for k in rungs), rungs
    assert launches and all(k.endswith("/" + intended) for k in launches), \
        launches
    return launches


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path over four devices")
    ap.add_argument("--small", action="store_true",
                    help="tiny sizes; allowed without a TPU")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.small:
        args.terms, args.docs, args.rows = 48, 3 * 65_536 + 4_321, 70_000
        args.per_shape = lambda n: 2
    else:
        args.terms, args.docs, args.rows = 2048, MSMARCO_PASSAGES, \
            CENSUS1881_ROWS
        args.per_shape = lambda n: n

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    log(f"device: platform={platform} kind={kind} count={len(devices)}")
    if platform != "tpu" and not args.small:
        log("no TPU found: the smoke test runs on the chip only "
            "(use --small for the CPU rehearsal)")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, "
            f"found {len(devices)}")
        return 2
    if not args.small:
        from benchmarks.compile_cache import use_compile_cache
        log(f"compile cache: {use_compile_cache()}")

    import repro.obs as obs
    from repro.kernels.roaring import ops as kops

    intended = "pallas" if platform == "tpu" else kops.current_backend()
    compile_s = _compile_seconds()
    obs.reset_metrics()
    obs.enable()
    phases = (("sharded", sharded_phase),) if args.chips > 1 else \
        (("search", search_phase), ("store", store_phase))
    try:
        for name, phase in phases:
            c0 = compile_s["s"]
            out = phase(args, intended)
            out["compile_s"] = compile_s["s"] - c0
            log(f"{name} smoke timings (not benchmark numbers): "
                + json.dumps(out, sort_keys=True))
        _check_backends(intended)
    finally:
        obs.disable()
    stats = devices[0].memory_stats() or {}
    log("device memory: " + json.dumps(
        {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                               "bytes_limit") if k in stats}))
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
