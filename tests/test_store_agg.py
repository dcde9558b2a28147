"""The store's aggregation: ``BitmapStore.group_sum`` and ``sum_`` against
numpy on seeded random columns over a few chunks, with array, bitmap and
run containers among the filter's leaves, the groups and the summed
slices; the program cache; the rung they take; and their span."""

import numpy as np
import pytest

import repro.obs as obs
from repro import index as ix
from repro import store
from repro.kernels.roaring import ops as kops

N_ROWS = 3 * 65_536 + 777           # four chunk rows, the last one partial


def _records(seed: int = 7) -> dict:
    rng = np.random.default_rng(seed)
    n = N_ROWS
    a = rng.integers(0, 5, n)
    a[70_000:140_000] = 4           # one run over a whole chunk
    g = rng.integers(0, 4, n)
    g[rng.random(n) < 0.9] = 1      # groups 0, 2, 3 as array containers
    g[10_000:12_000] = 9            # a group of one run in chunk 0
    v = rng.integers(0, 1000, n)
    v[150_000:190_000] = 1023       # every slice holds a run there
    v[rng.random(n) < 0.01] += 3000  # the top slices are arrays elsewhere
    q = rng.integers(1, 51, n)
    return {"a": a, "g": g, "v": v, "q": q}


@pytest.fixture(scope="module")
def agg():
    records = _records()
    return store.BitmapStore.build(records, bsi=("v", "q")), records


def _mask(records, pred):
    if pred is None:
        return np.ones(N_ROWS, bool)
    if isinstance(pred, store.Eq):
        return records[pred.col] == pred.value
    if isinstance(pred, store.In):
        return np.isin(records[pred.col], pred.values)
    if isinstance(pred, store.Range):
        col = records[pred.col]
        lo = -np.inf if pred.lo is None else pred.lo
        hi = np.inf if pred.hi is None else pred.hi
        return (col >= lo) & (col <= hi)
    if isinstance(pred, store.AndP):
        return np.logical_and.reduce([_mask(records, c)
                                      for c in pred.children])
    if isinstance(pred, store.OrP):
        return np.logical_or.reduce([_mask(records, c)
                                     for c in pred.children])
    return ~_mask(records, pred.child)


def _group_sums(records, name, by, pred):
    m = _mask(records, pred)
    return {int(v): int(records[name][m & (records[by] == v)].sum())
            for v in np.unique(records[by])}


def test_the_containers_cover_every_kind(agg):
    s, _ = agg
    kinds = {name: {type(c).__name__ for j in range(s.column(name).n_slabs)
                    for c in s.slot_bitmap(s.column(name).base_slot + j)
                    .containers} for name in ("a", "g", "v")}
    for name in ("a", "g", "v"):
        assert kinds[name] >= {"BitmapContainer", "RunContainer"}, kinds
    assert "ArrayContainer" in kinds["g"] and "ArrayContainer" in kinds["v"]


PREDICATES = [
    None,
    store.eq("a", 2),
    store.and_(store.eq("a", 4), store.range_("q", None, 24)),
    store.and_(store.range_("q", 26, 35), store.in_("g", [0, 9])),
    store.or_(store.eq("a", 0), store.not_(store.range_("q", 5, None))),
    store.and_(store.eq("a", 1), store.eq("a", 3)),          # no row
    store.and_(store.eq("g", 2), store.eq("a", 4)),
    store.and_(store.range_("g", 5, 8), store.eq("a", 1)),   # no group
]


@pytest.mark.parametrize("pred", PREDICATES)
def test_group_sum_and_sum_equal_numpy(agg, pred):
    s, records = agg
    assert s.group_sum("v", "g", pred) == _group_sums(records, "v", "g",
                                                     pred)
    want = int(records["v"][_mask(records, pred)].sum())
    assert s.sum_("v", pred) == want
    # a value of ``by`` that no row holds is no group
    assert 5 not in s.group_sum("v", "g", pred)


def test_the_pallas_kernel_equals_numpy(agg):
    s, records = agg
    pred = store.and_(store.eq("a", 4), store.range_("q", None, 24),
                      store.in_("g", [1, 9]))
    with kops.backend_scope("pallas"):
        fresh = store.BitmapStore.build(records, bsi=("v", "q"))
        assert fresh.group_sum("v", "g", pred) == _group_sums(
            records, "v", "g", pred)


def test_the_q1_revenue_is_the_weighted_group_sum(agg):
    """SSB Q1.1's ``sum(price * discount)``: ``a`` plays the year, ``g``
    the discount, ``q`` the quantity and ``v`` the price."""
    s, records = agg
    pred = store.and_(store.eq("a", 4), store.range_("g", 1, 3),
                      store.range_("q", None, 24))
    m = _mask(records, pred)
    want = int((records["v"][m] * records["g"][m]).sum())
    sums = s.group_sum("v", "g", pred)
    assert sum(v * x for v, x in sums.items()) == want


def test_sums_are_exact_past_32_bits():
    n = 2 * 65_536
    records = {"v": np.full(n, (1 << 24) - 1), "g": np.arange(n) % 2}
    s = store.BitmapStore.build(records, bsi=("v",))
    assert s.sum_("v") == n * ((1 << 24) - 1) > 1 << 32
    assert s.group_sum("v", "g") == {0: n // 2 * ((1 << 24) - 1),
                                     1: n // 2 * ((1 << 24) - 1)}


def test_a_second_predicate_of_one_shape_compiles_nothing(agg):
    s, records = agg
    shape = [store.and_(store.eq("a", a), store.range_("q", None, 24),
                        store.range_("g", 1, 2)) for a in (0, 3)]
    s.group_sum("v", "g", shape[0])
    misses, entries = s.cache_stats()["misses"], s.cache_stats()["entries"]
    got = s.group_sum("v", "g", shape[1])
    assert got == _group_sums(records, "v", "g", shape[1])
    stats = s.cache_stats()
    assert (stats["misses"], stats["entries"]) == (misses, entries)
    fns = [f for k, f in s._query_fns.items() if k[0] == "sum"]
    assert all(f._cache_size() == 1 for f in fns)


def test_sum_never_takes_the_per_op_rung(agg):
    s, records = agg
    reg = obs.registry()
    before = reg.total("index.rung_taken", kind="per_op")
    pred = store.and_(store.eq("a", 1), store.range_("q", 10, 40))
    assert s.sum_("v", pred) == int(records["v"][_mask(records, pred)].sum())
    s.group_sum("v", "g", pred)
    assert reg.total("index.rung_taken", kind="per_op") == before
    assert s.cache_stats()["fallbacks"] == 0


def test_the_sum_span_counts_the_rows_read(agg):
    s, _ = agg
    pred = store.and_(store.eq("a", 4), store.range_("q", None, 24),
                      store.range_("g", 1, 2))
    filt = store.and_(store.eq("a", 4), store.range_("q", None, 24))
    _, leaves = ix.lower(s.compile(filt))
    counted = obs.registry().value("store.rows_read")
    obs.reset_traces()
    with obs.telemetry_scope():
        s.group_sum("v", "g", pred)
    root, = [t for t in obs.span_trees() if t.name == "store.sum"]
    rows = (len(leaves) + 2 + s.column("v").bits) * s.n_chunks
    assert root.attrs == {"column": "v", "by": "g", "groups": 2,
                          "slices": s.column("v").bits,
                          "leaves": len(leaves), "rows_read": rows}
    assert [c.name for c in root.children] == [
        "store.compile", "store.execute", "store.sync"]
    assert obs.registry().value("store.rows_read") == counted + rows
    obs.reset_traces()


def test_the_store_refuses_what_it_cannot_sum(agg):
    s, _ = agg
    with pytest.raises(TypeError):
        s.sum_("a")
    with pytest.raises(TypeError):
        s.group_sum("v", "q")
    with pytest.raises(TypeError):
        s.group_sum("v", "g", store.eq("g", "x"))
