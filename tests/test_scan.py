"""The planned top-k scoring (``index.engine.plan_scan`` and the
``roaring_card_scan`` kernel) against the whole-stack lift
(``batched_and_card``) and a numpy reference.

The stack holds every kind of row: the reserved empty row and empty rows
inside postings, array rows with exactly ``W`` and ``W + 1`` values at the
class edges and with 4,096, arrays holding 0 and 65,535 (the pad value),
bitmap rows, a run row (its term scored by the lift), a term with no
documents, and identical postings (tied scores). The scan runs through the
XLA mirror and through the Pallas kernel in interpret mode.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro import index, roaring
from repro.core import jax_roaring as jr
from repro.index import engine as E
from repro.kernels.roaring import dispatch as D
from repro.kernels.roaring import ops, scan as S
from repro.search import PostingIndex

C = 3
N_DOCS = C * 65_536
_RNG = np.random.default_rng(1414)


def _sparse(chunk, n, extra=()):
    """``n`` scattered values of one chunk (an array container), plus
    ``extra`` low bits, as doc ids."""
    lo = _RNG.choice(np.arange(1, 65_535), n - len(extra), replace=False)
    return chunk * 65_536 + np.union1d(lo, np.asarray(extra, np.int64))


POSTINGS = {
    "a128": np.concatenate([_sparse(0, 128), _sparse(1, 5)]),
    "a129": _sparse(0, 129),
    "a256": _sparse(1, 256),
    "a257": np.concatenate([_sparse(0, 7), _sparse(2, 257)]),
    "a4096": np.concatenate([_sparse(0, 4096), _sparse(1, 3)]),
    "bitmap": np.concatenate([_sparse(0, 10_000), _sparse(1, 50)]),
    "bitmaps": np.concatenate([_sparse(c, 9_000) for c in range(C)]),
    "edges": np.concatenate([_sparse(0, 40, (0, 65_535)),
                             _sparse(2, 300, (65_535,))]),
    "nothing": np.zeros(0, np.int64),
    "run": np.concatenate([_sparse(0, 10), 65_536 + np.arange(1000, 9000)]),
    "tie_a": _sparse(2, 60),
}
POSTINGS["tie_b"] = POSTINGS["tie_a"].copy()
POSTINGS["tie_c"] = POSTINGS["a129"].copy()

# a query of every kind of row: a dense chunk, a sparse one, a run
QUERY_DOCS = np.union1d(
    np.union1d(_RNG.choice(65_536, 30_000, replace=False),
               _sparse(1, 2_000)),
    2 * 65_536 + np.arange(0, 40_000))


@pytest.fixture(scope="module")
def idx():
    return PostingIndex.from_postings(POSTINGS, N_DOCS)


def _bitmap_query(docs):
    """The query as the service hands it over: bitmap-domain rows."""
    dense = np.zeros(C * 65_536, bool)
    dense[docs] = True
    bits = np.packbits(dense, bitorder="little").view(np.uint16)
    cards = dense.reshape(C, -1).sum(1).astype(np.int32)
    return roaring.RoaringSlab(
        keys=jnp.arange(C, dtype=jnp.int32),
        kinds=jnp.asarray(np.where(cards > 0, jr.KIND_BITMAP,
                                   jr.KIND_EMPTY).astype(np.int32)),
        cards=jnp.asarray(cards), nruns=jnp.zeros((C,), jnp.int32),
        payload=jnp.asarray(bits.reshape(C, -1)), C=C)


def _query(kind):
    if kind == "bitmap_rows":
        return _bitmap_query(QUERY_DOCS)
    return PostingIndex.from_postings({"q": QUERY_DOCS}, N_DOCS).stack[1]


def _want(idx):
    """numpy: |posting ∩ query| per stack row (row 0 the reserved one)."""
    return np.asarray([0] + [np.intersect1d(POSTINGS[t], QUERY_DOCS).size
                             for t in idx.terms])


def _class_rows(plan, i):
    """Stack rows of the groups class ``i`` visits, -1 where masked."""
    rows = (np.asarray(plan.groups[i])[:, None] * S.GROUP
            + np.arange(S.GROUP))
    return np.where(np.asarray(plan.codes[i]).any(axis=2), rows, -1)


def _row_sets(idx, plan):
    return {w: {idx.term_of(int(r)) for r in _class_rows(plan, i).ravel()
                if r >= 0}
            for i, w in enumerate(plan.widths)}


def test_the_stack_holds_every_kind(idx):
    kinds = np.asarray(idx.stack.kinds)
    cards = np.asarray(idx.stack.cards)
    assert {int(k) for k in np.unique(kinds)} == {
        D.KIND_EMPTY, D.KIND_ARRAY, D.KIND_BITMAP, D.KIND_RUN}
    row = idx.row
    assert cards[row("a4096"), 0] == 4096
    assert kinds[row("a4096"), 0] == D.KIND_ARRAY
    assert kinds[row("run"), 1] == D.KIND_RUN
    assert (kinds[0] == D.KIND_EMPTY).all()
    q = _query("raw_slab")
    assert {int(k) for k in np.asarray(q.kinds)} == {
        D.KIND_ARRAY, D.KIND_BITMAP, D.KIND_RUN}


def test_a_term_takes_the_class_of_its_largest_array_card(idx):
    plan = idx.plan
    assert plan.widths == (128, 256, 512, 4096)
    assert _row_sets(idx, plan) == {
        128: {"a128", "tie_a", "tie_b"},
        256: {"a129", "a256", "tie_c"},
        512: {"a257", "edges"},
        4096: {"a4096", "bitmap", "bitmaps"},
    }
    assert plan.bitmaps == (False, False, False, True)


def test_run_terms_are_residual_and_empty_terms_in_no_class(idx):
    plan = idx.plan
    assert [idx.term_of(int(r)) for r in plan.residual] == ["run"]
    classed = {t for rows in _row_sets(idx, plan).values() for t in rows}
    assert "nothing" not in classed and "run" not in classed
    assert len(classed) + 1 + 1 == idx.n_terms


def test_the_order_puts_each_rows_score_back_in_term_order(idx):
    plan = idx.plan
    placed = np.concatenate([_class_rows(plan, i).ravel()
                             for i in range(len(plan.widths))]
                            + [np.asarray(plan.residual), [-1]])
    got = placed[np.asarray(plan.order)]
    scored = np.asarray([idx.term_of(r) not in (None, "nothing")
                         for r in range(idx.n_rows)])
    assert (got[scored] == np.flatnonzero(scored)).all()
    assert (got[~scored] == -1).all()


def test_the_plan_counts_one_scoring(idx):
    """Each class visits the one group of this stack's 14 rows and reads
    all 16 of its rows at its width; the run's rows are lifted whole."""
    plan = idx.plan
    assert [g.tolist() for g in plan.groups] == [[0]] * 4
    assert plan.rows_lifted == C * 2           # the query's and the run's
    assert plan.slots_scanned == C * (S.GROUP * (128 + 256 + 512 + 4096)
                                      + 4096 * 1)
    assert plan.slots_total == idx.n_rows * C * 4096


def test_the_plan_places_only_its_classs_array_rows(idx):
    """A class's flags mark its own rows that hold an array row: not the
    rows of other classes, nor a row of bitmaps alone, nor padding."""
    plan = idx.plan
    placed = {w: {idx.term_of(r) for r in np.flatnonzero(np.asarray(p))}
              for w, p in zip(plan.widths, plan.places)}
    sets = _row_sets(idx, plan)
    assert placed == {w: sets[w] - {"bitmaps"} for w in plan.widths}


@pytest.mark.parametrize("query", ["bitmap_rows", "raw_slab"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_scan_scores_equal_the_lift_and_numpy(idx, backend, query):
    """The traced scoring under each backend (the jitted entry caches one
    backend per shape, so the body runs unjitted here)."""
    q = _query(query)
    with ops.backend_scope(backend):
        got = E._scan_scores(idx.stack.payload, idx.stack.kinds,
                             idx.stack.cards, idx.stack.nruns, idx.plan, q)
    want = _want(idx)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(index.batched_and_card(idx.stack, q)), want)


@pytest.mark.parametrize("query", ["bitmap_rows", "raw_slab"])
def test_topk_by_scan_ranks_every_row_as_batched_and_card(idx, query):
    q = _query(query)
    scores, rows = index.topk_by_scan(idx.stack, idx.plan, q, idx.n_rows)
    want = np.asarray(index.batched_and_card(idx.stack, q))
    np.testing.assert_array_equal(np.asarray(scores),
                                  want[np.asarray(rows)])
    assert sorted(np.asarray(rows).tolist()) == list(range(idx.n_rows))


@pytest.mark.parametrize("k", [1, 4, 14])
def test_topk_order_and_ties_equal_topk_by_card(idx, k):
    q = _bitmap_query(QUERY_DOCS)
    want = _want(idx)
    assert want[idx.row("tie_a")] == want[idx.row("tie_b")] > 0
    assert want[idx.row("tie_c")] == want[idx.row("a129")] > 0
    got = index.topk_by_scan(idx.stack, idx.plan, q, k)
    lift = index.topk_by_card(idx.stack, q, k)
    for a, b in zip(got, lift):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert idx.topk(q, k)[1].tolist() == np.asarray(lift[1]).tolist()


@pytest.mark.parametrize("cls", range(4))
def test_the_kernel_equals_its_mirror_in_each_class(idx, cls):
    plan = idx.plan
    q = _bitmap_query(QUERY_DOCS)
    words = D.lift_rows(q.payload, q.kinds, q.cards, q.nruns, "scatter")
    got = [np.asarray(ops.card_scan(
        idx.stack.payload, plan.groups[cls], plan.places[cls],
        plan.codes[cls], words,
        width=plan.widths[cls], bitmaps=plan.bitmaps[cls], use_pallas=p,
        interpret=p)) for p in (True, False)]
    np.testing.assert_array_equal(got[0], got[1])
    rows = _class_rows(plan, cls)
    np.testing.assert_array_equal(
        got[0], np.where(rows >= 0, _want(idx)[np.maximum(rows, 0)], 0))


def test_scan_codes():
    kind = np.asarray([D.KIND_EMPTY, D.KIND_ARRAY, D.KIND_BITMAP, D.KIND_RUN])
    card = np.asarray([0, 77, 5000, 9000])
    assert S.scan_codes(kind, card).tolist() == [0, 77, S.BITMAP_CODE, 0]


def test_the_kernel_places_only_the_flagged_rows(idx):
    """With no row flagged, the kernel still scores the bitmap rows and
    places no array row: the flags, not the codes, spare the placements."""
    plan = idx.plan
    cls = plan.widths.index(4096)
    q = _bitmap_query(QUERY_DOCS)
    words = D.lift_rows(q.payload, q.kinds, q.cards, q.nruns, "scatter")
    got = np.asarray(ops.card_scan(
        idx.stack.payload, plan.groups[cls],
        jnp.zeros_like(plan.places[cls]), plan.codes[cls], words,
        width=4096, bitmaps=True, use_pallas=True, interpret=True))
    want = {t: int(np.intersect1d(POSTINGS[t], QUERY_DOCS).size)
            for t in POSTINGS}
    rows = _class_rows(plan, cls)[0]
    terms = [idx.term_of(int(r)) if r >= 0 else None for r in rows]
    assert got[0, terms.index("bitmaps")] == want["bitmaps"] > 0
    assert got[0, terms.index("a4096")] == 0 < want["a4096"]
    bitmap_part = np.intersect1d(POSTINGS["bitmap"][:10_000], QUERY_DOCS)
    assert got[0, terms.index("bitmap")] == bitmap_part.size
