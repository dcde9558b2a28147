"""Fused expression-tree execution (PR 7): the single-launch mega-kernel.

Contract under test: ``execute(..., fused=True)`` is byte-identical — values,
cards, kinds, serialized stream — to the per-op tree-reduce path AND to
``py_roaring`` set algebra, on all three backends (Pallas interpret, the
tape-mirroring XLA evaluator, and the per-op reference it degrades to);
plans retrace once per expression shape; the degradation ladder falls back
from the fused rung bit-identically; and empty columns come out empty from
the pairwise kernels and the fused kernel alike.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import index, roaring
from repro.core import jax_roaring as jr
from repro.core.py_roaring import RoaringBitmap
from repro.kernels.roaring import dispatch as D
from repro.kernels.roaring import fused as F
from repro.kernels.roaring import kernel as K
from repro.kernels.roaring import ops as kops
from repro.kernels.roaring import ref as kref
from repro.roaring import RoaringFormatSpec


def _rand_set(n, universe, seed):
    r = np.random.default_rng(seed)
    return np.unique(r.integers(0, universe, size=n))


def _run_set(seed, universe, n_ranges=120, max_len=400):
    r = np.random.default_rng(seed)
    starts = np.sort(r.integers(0, universe, n_ranges))
    lens = r.integers(1, max_len, n_ranges)
    vals = np.concatenate([np.arange(s, min(s + l, universe))
                           for s, l in zip(starts, lens)])
    return np.unique(vals)


def _mixed_slabs(capacity=8, seed=0):
    """Operands covering all four kinds: sparse arrays, dense bitmaps, run
    rows, and slabs that leave whole chunks empty."""
    universe = capacity << 16
    vals = [
        _rand_set(1500, universe, seed + 1),          # array rows
        _rand_set(120_000, universe, seed + 2),       # bitmap rows
        _run_set(seed + 3, universe),                 # run rows
        _rand_set(3000, universe // capacity, seed + 4),  # chunk 0 only
    ]
    slabs = [roaring.RoaringSlab.from_values(v, capacity, 1 << 18)
             for v in vals]
    return slabs, [set(v.tolist()) for v in vals]


def _assert_matches(result, expect_set, tag=""):
    """Byte-level identity against the py_roaring oracle: serialized stream
    plus the decoded fields."""
    oracle = RoaringBitmap.from_array(np.fromiter(sorted(expect_set),
                                                  np.int64, len(expect_set)))
    assert result.serialize() == RoaringFormatSpec.serialize(oracle), tag
    assert int(result.card()) == len(expect_set), tag


def _check_tri_backend(stack, expr, expect_set, tag=""):
    """fused-pallas == fused-xla == per-op (values, cards, kinds, payload
    arrays) and all byte-identical to py_roaring."""
    per_op = index.execute(stack, expr, backend="xla")
    outs = {"fused-xla": index.execute(stack, expr, backend="xla",
                                       fused=True),
            "fused-pallas": index.execute(stack, expr, backend="pallas",
                                          fused=True)}
    for name, got in outs.items():
        np.testing.assert_array_equal(np.asarray(got.keys),
                                      np.asarray(per_op.keys),
                                      err_msg=f"{tag}/{name}")
        np.testing.assert_array_equal(np.asarray(got.kinds),
                                      np.asarray(per_op.kinds),
                                      err_msg=f"{tag}/{name}")
        np.testing.assert_array_equal(np.asarray(got.cards),
                                      np.asarray(per_op.cards),
                                      err_msg=f"{tag}/{name}")
        np.testing.assert_array_equal(np.asarray(got.payload),
                                      np.asarray(per_op.payload),
                                      err_msg=f"{tag}/{name}")
        _assert_matches(got, expect_set, f"{tag}/{name}")
        c = int(index.execute_card(stack, expr, fused=True,
                                   backend=name.split("-")[1]))
        assert c == len(expect_set), f"{tag}/{name}/card"
    _assert_matches(per_op, expect_set, f"{tag}/per_op")


# ------------------------------------------------------------------ planner
def test_plan_tape_left_fold_slots():
    plan = F.plan_tape(("and", 0, 1, 2, 3))
    # n-ary left fold: 2 slots regardless of width
    assert plan.n_slots == 2
    assert plan.n_loads == 4 and plan.n_ops == 3
    deep = F.plan_tape(("and", 0, ("or", 1, ("andnot", 2, ("and", 3, 4)))))
    assert plan.tape[0] == ("load", 0, 0)
    assert deep.n_slots == 5            # one live slot per nesting level


def test_plan_tape_hash_consed():
    a = F.plan_tape(("or", 0, ("and", 1, 2)))
    b = F.plan_tape(("or", 0, ("and", 1, 2)))
    assert a is b


def test_plan_tape_rejects_malformed():
    with pytest.raises(ValueError):
        F.plan_tape(("nand", 0, 1))
    with pytest.raises(ValueError):
        F.plan_tape(("andnot", 0, 1, 2))
    with pytest.raises(ValueError):
        F.plan_tape(("and",))


def test_plan_stats_model():
    plan = F.plan_tape(("and", 0, 1, 2, 3))
    stats = F.plan_stats(plan, 16)
    assert stats["launches_fused"] == 1
    assert stats["launches_per_op"] == 3
    assert stats["hbm_bytes_fused"] < stats["hbm_bytes_per_op"]


# ---------------------------------------------------- tri-backend identity
def test_all_kinds_tri_backend():
    slabs, vals = _mixed_slabs()
    stack = roaring.stack(slabs, capacity=8)
    expect = ((vals[0] | vals[1]) - (vals[2] & vals[3])) | vals[2]
    expr = index.or_(
        index.andnot(index.or_(index.leaf(0), index.leaf(1)),
                     index.and_(index.leaf(2), index.leaf(3))),
        index.leaf(2))
    _check_tri_backend(stack, expr, expect, "all_kinds")


def test_array_bitmap_boundaries():
    # result cardinalities straddling the 4096 array/bitmap threshold
    for n in (4095, 4096, 4097):
        a = np.arange(2 * n, dtype=np.int64)
        b = np.arange(0, 4 * n, 2, dtype=np.int64)[:n]
        sa = roaring.RoaringSlab.from_values(a, 2, 1 << 15)
        sb = roaring.RoaringSlab.from_values(b, 2, 1 << 15)
        stack = roaring.stack([sa, sb], capacity=2)
        expect = set(a.tolist()) & set(b.tolist())
        assert len(expect) == n
        _check_tri_backend(stack, index.and_(index.leaf(0), index.leaf(1)),
                           expect, f"boundary_{n}")


def test_deep_tree():
    slabs, vals = _mixed_slabs(seed=50)
    extra = [_rand_set(20_000, 8 << 16, 60 + i) for i in range(2)]
    slabs += [roaring.RoaringSlab.from_values(v, 8, 1 << 18) for v in extra]
    vals += [set(v.tolist()) for v in extra]
    stack = roaring.stack(slabs, capacity=8)
    # depth 5: andnot(or(and(or(and(l0,l1),l2),l3),l4),l5)
    expr = index.andnot(
        index.or_(
            index.and_(
                index.or_(
                    index.and_(index.leaf(0), index.leaf(1)),
                    index.leaf(2)),
                index.leaf(3)),
            index.leaf(4)),
        index.leaf(5))
    expect = (((vals[0] & vals[1]) | vals[2]) & vals[3] | vals[4]) - vals[5]
    _check_tri_backend(stack, expr, expect, "deep")


def test_wide_tree_n32():
    rng = np.random.default_rng(77)
    base = np.unique(rng.integers(0, 8 << 16, 50_000))
    keep_sets, slabs = [], []
    for i in range(32):
        keep = base[rng.random(base.size) > 0.02]
        keep_sets.append(set(keep.tolist()))
        slabs.append(roaring.RoaringSlab.from_values(keep, 8, 1 << 18))
    stack = roaring.stack(slabs, capacity=8)
    expect = set.intersection(*keep_sets)
    _check_tri_backend(stack, index.and_(*[index.leaf(i) for i in range(32)]),
                       expect, "wide_and")
    expect = set.union(*keep_sets)
    _check_tri_backend(stack, index.or_(*[index.leaf(i) for i in range(32)]),
                       expect, "wide_or")


def test_andnot_of_or():
    slabs, vals = _mixed_slabs(seed=90)
    stack = roaring.stack(slabs, capacity=8)
    expr = index.andnot(index.or_(index.leaf(0), index.leaf(1)),
                        index.or_(index.leaf(2), index.leaf(3)))
    _check_tri_backend(stack, expr, (vals[0] | vals[1]) - (vals[2] | vals[3]),
                       "andnot_of_or")


def test_slab_leaves_and_dedup():
    slabs, vals = _mixed_slabs(seed=130)
    q = slabs[1]
    # same leaf twice (deduped to one streamed operand) + a slab leaf
    expr = index.and_(index.leaf(0), index.leaf(0), index.leaf(q))
    stack = roaring.stack(slabs[:1] * 2, capacity=8)
    plan, data, _ = index.engine._fused_compile(
        stack, stack.keys[0],
        index.and_(index.leaf(0), index.leaf(0), index.leaf(q)))
    assert plan.n_operands == 2 and data.shape[0] == 2
    got = index.execute(stack, expr, fused=True)
    _assert_matches(got, vals[0] & vals[1], "dedup")


# ------------------------------------------------------------ retrace guard
def test_fused_retrace_once_per_shape():
    kops._fused_tree.clear_cache()
    F.plan_tape.cache_clear()
    for seed in (1, 2, 3):
        slabs = [roaring.RoaringSlab.from_values(
            _rand_set(4000, 4 << 16, seed * 10 + i), 4, 1 << 17)
            for i in range(4)]
        stack = roaring.stack(slabs, capacity=4)
        # fresh Expr objects each loop: equal structure must reuse the plan
        expr = index.andnot(index.or_(index.leaf(0), index.leaf(1)),
                            index.and_(index.leaf(2), index.leaf(3)))
        index.execute(stack, expr, fused=True)
        index.execute_card(stack, expr, fused=True)
    assert F.plan_cache_size() == 1
    assert kops._fused_tree._cache_size() == 1


# --------------------------------------------------------- fault injection
def test_fused_ladder_degrades_bit_identical():
    from repro.runtime.fault_tolerance import FaultPlan, fault_scope

    slabs, vals = _mixed_slabs(seed=170)
    stack = roaring.stack(slabs, capacity=8)
    expr = index.andnot(index.or_(index.leaf(0), index.leaf(1)),
                        index.and_(index.leaf(2), index.leaf(3)))
    good = index.execute(stack, expr, backend="xla")
    index.reset_degradation()
    with fault_scope(FaultPlan(every=1, backend="pallas")):
        degraded = index.execute(stack, expr, backend="pallas", fused=True)
    stats = index.degradation_stats()
    # fused-pallas (1 try + 1 retry) and per-op-pallas all fault; the
    # XLA-ref rung completes the query
    assert stats.fallbacks == 2
    assert stats.dispatch_failures == 3
    assert degraded.serialize() == good.serialize()
    np.testing.assert_array_equal(np.asarray(degraded.payload),
                                  np.asarray(good.payload))
    index.reset_degradation()


def test_fused_xla_rung_failure_propagates():
    from repro.runtime.fault_tolerance import FaultPlan, InjectedFault, \
        fault_scope

    slabs, _ = _mixed_slabs(seed=210)
    stack = roaring.stack(slabs, capacity=8)
    expr = index.and_(index.leaf(0), index.leaf(1))
    index.reset_degradation()
    with fault_scope(FaultPlan(every=1, backend="xla")):
        with pytest.raises(InjectedFault):
            index.execute(stack, expr, backend="xla", fused=True)
    index.reset_degradation()


# ------------------------------------------------ leaf lifts and empty rows
def _packed_rows(value_sets):
    rows = np.full((len(value_sets), jr.ROW_WORDS), 0xFFFF, np.uint16)
    for i, v in enumerate(value_sets):
        rows[i, : len(v)] = v
    return rows


def _bitmap_rows(value_sets):
    rows = np.zeros((len(value_sets), jr.ROW_WORDS), np.uint16)
    for i, v in enumerate(value_sets):
        v = np.asarray(v, np.int64)
        np.bitwise_or.at(rows[i], v >> 4,
                         (np.uint16(1) << (v & 15)).astype(np.uint16))
    return rows


@pytest.mark.parametrize("cards", [(0, 1, 4096), (17, 4095, 300)],
                         ids=["edges", "mixed"])
def test_array_coverage_by_matmul_exact(cards):
    """The one-hot-product array lift places every value exactly, including
    65535 (the padding fill's value), a full 4096-value row and card 0."""
    rng = np.random.default_rng(sum(cards))
    sets = [np.sort(rng.choice(1 << 16, c, replace=False)) for c in cards]
    if cards[1] == 1:
        sets[1] = np.asarray([65535])
    got = D.array_coverage_by_matmul(
        jnp.asarray(_packed_rows(sets)).reshape(-1, 32, 128),
        jnp.asarray(cards, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got).reshape(len(cards), -1),
                                  _bitmap_rows(sets))


@pytest.mark.parametrize("n_rows", [300, 257 * 3], ids=["divisor", "padded"])
def test_lift_rows_chunked_over_many_rows(n_rows):
    """Stacks taller than one lift chunk are lifted chunk by chunk (by a
    divisor of the row count, or padded to whole chunks) to the same
    bitmaps, for every kind, in the u32 word view."""
    rng = np.random.default_rng(n_rows)
    kinds = rng.integers(0, 3, n_rows).astype(np.int32)   # empty/array/bitmap
    sets = [np.sort(rng.choice(1 << 16, int(rng.integers(1, 64)),
                               replace=False)) for _ in range(n_rows)]
    packed, bits = _packed_rows(sets), _bitmap_rows(sets)
    rows = np.where((kinds == D.KIND_BITMAP)[:, None], bits, packed)
    want = np.where((kinds == D.KIND_EMPTY)[:, None], 0, bits)
    cards = np.asarray([len(v) for v in sets], np.int32)
    got = D.lift_rows(jnp.asarray(rows)[None], jnp.asarray(kinds)[None],
                      jnp.asarray(cards)[None],
                      jnp.zeros((1, n_rows), jnp.int32), "matmul")
    np.testing.assert_array_equal(np.asarray(D.from_words(got[0])), want)
    np.testing.assert_array_equal(np.asarray(D.to_words(jnp.asarray(want))),
                                  np.asarray(got[0]))


@pytest.mark.parametrize("array_lift", sorted(D.ARRAY_LIFTS))
def test_lift_rows_every_kind(array_lift):
    """Each array lift, inside ``lift_rows``, gives the oracle's bitmap for
    rows of every kind: empty, array (65535 and a full row included),
    bitmap and run."""
    rng = np.random.default_rng(11)
    arrays = [np.asarray([65535]), np.arange(4096) * 16,
              np.sort(rng.choice(1 << 16, 300, replace=False))]
    bitmap = np.sort(rng.choice(1 << 16, 9000, replace=False))
    ranges = [(5, 40), (100, 4000), (65500, 65536)]
    run = jr.from_ranges(ranges, 1)
    assert int(run.kind[0]) == jr.KIND_RUN
    rows = np.concatenate([np.zeros((1, jr.ROW_WORDS), np.uint16),
                           _packed_rows(arrays), _bitmap_rows([bitmap]),
                           np.asarray(run.data[:1])])
    kinds = np.asarray([jr.KIND_EMPTY] + [jr.KIND_ARRAY] * 3
                       + [jr.KIND_BITMAP, jr.KIND_RUN], np.int32)
    cards = np.asarray([0] + [len(a) for a in arrays] + [bitmap.size,
                       int(run.card[0])], np.int32)
    nruns = np.asarray([0] * 5 + [len(ranges)], np.int32)
    got = D.lift_rows(jnp.asarray(rows), jnp.asarray(kinds),
                      jnp.asarray(cards), jnp.asarray(nruns), array_lift)
    run_vals = np.concatenate([np.arange(a, b) for a, b in ranges])
    want = np.concatenate([np.zeros((1, jr.ROW_WORDS), np.uint16),
                           _bitmap_rows(arrays + [bitmap, run_vals])])
    np.testing.assert_array_equal(np.asarray(D.from_words(got)), want)


def test_stacked_dispatch_shared_query_matches_broadcast():
    """A query slab shared by all N stacked slabs (u16[C, 4096]) gives the
    same hits and cards as the query broadcast to every slab, on the Pallas
    path and the XLA reference."""
    rng = np.random.default_rng(7)
    N, C = 3, 4
    sets = [np.sort(rng.choice(1 << 16, int(rng.integers(1, 3000)),
                               replace=False)) for _ in range(N * C + C)]
    a = jnp.asarray(_packed_rows(sets[: N * C]).reshape(N, C, -1))
    q = jnp.asarray(_bitmap_rows(sets[N * C:]))
    ka = np.full((N, C), D.KIND_ARRAY)
    ca = np.asarray([len(v) for v in sets[: N * C]]).reshape(N, C)
    cq = np.broadcast_to([len(v) for v in sets[N * C:]], (N, C))
    z = np.zeros((N, C))
    meta = jnp.asarray(np.stack([ka, np.full((N, C), D.KIND_BITMAP), ca, cq,
                                 z, z], axis=2).reshape(N, 6 * C), jnp.int32)
    want_h, want_c = K.intersect_dispatch_stacked_pallas(
        a, jnp.broadcast_to(q, (N, C, q.shape[-1])), meta)
    for use_pallas in (True, False):
        h, c = kops.intersect_dispatch_stacked(a, q, meta,
                                               use_pallas=use_pallas,
                                               interpret=use_pallas)
        np.testing.assert_array_equal(np.asarray(h), np.asarray(want_h))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(want_c))
    for n in range(N):
        for i in range(C):
            want = len(set(sets[n * C + i].tolist())
                       & set(sets[N * C + i].tolist()))
            assert int(want_c[n, i]) == want


def test_container_op_empty_columns_skip():
    rng = np.random.default_rng(5)
    C = 6
    a = jnp.asarray(rng.integers(0, 1 << 16, (C, 4096)), jnp.uint16)
    b = jnp.asarray(rng.integers(0, 1 << 16, (C, 4096)), jnp.uint16)
    kinds = np.full(2 * C, D.KIND_BITMAP, np.int32)
    kinds[2 * 1], kinds[2 * 1 + 1] = 0, 0            # column 1 fully empty
    kinds[2 * 4], kinds[2 * 4 + 1] = 0, 0            # column 4 fully empty
    kinds = jnp.asarray(kinds)
    out_p, card_p = K.container_op_pallas(a, b, kinds, "or", interpret=True)
    out_r, card_r = kref.container_op_ref(a, b, kinds, "or")
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_r))
    np.testing.assert_array_equal(np.asarray(card_p), np.asarray(card_r))
    assert int(card_p[1]) == 0 and int(card_p[4]) == 0


def test_dispatch_empty_columns_skip():
    rng = np.random.default_rng(6)
    C = 5
    a = jnp.asarray(rng.integers(0, 1 << 16, (C, 4096)), jnp.uint16)
    b = jnp.asarray(rng.integers(0, 1 << 16, (C, 4096)), jnp.uint16)
    ka = [D.KIND_BITMAP, D.KIND_EMPTY, D.KIND_BITMAP, D.KIND_EMPTY,
          D.KIND_BITMAP]
    kb = [D.KIND_BITMAP, D.KIND_EMPTY, D.KIND_EMPTY, D.KIND_BITMAP,
          D.KIND_BITMAP]
    meta = jnp.asarray(np.stack(
        [ka, kb, [4096] * C, [4096] * C, [0] * C, [0] * C],
        axis=1).reshape(-1), jnp.int32)
    hits_p, card_p = K.intersect_dispatch_pallas(a, b, meta, interpret=True)
    hits_r, card_r = kref.intersect_dispatch_ref(a, b, meta)
    np.testing.assert_array_equal(np.asarray(hits_p), np.asarray(hits_r))
    np.testing.assert_array_equal(np.asarray(card_p), np.asarray(card_r))
    for i in (1, 2, 3):
        assert int(card_p[i]) == 0


def test_fused_kernel_inherits_empty_skip():
    """Columns where every operand is empty must produce empty canonical
    rows through the fused Pallas kernel (whose index_map redirects their
    DMA) — identical to the XLA mirror and the per-op path."""
    # operands live only in chunk 0 of a 6-chunk stack: columns 1..5 dead
    slabs, vals = _mixed_slabs(capacity=6, seed=250)
    small = [roaring.RoaringSlab.from_values(
        _rand_set(2000, 1 << 16, 260 + i), 6, 1 << 17) for i in range(3)]
    sets = [set(np.asarray(_rand_set(2000, 1 << 16, 260 + i)).tolist())
            for i in range(3)]
    stack = roaring.stack(small, capacity=6)
    expr = index.or_(index.and_(index.leaf(0), index.leaf(1)),
                     index.leaf(2))
    _check_tri_backend(stack, expr, (sets[0] & sets[1]) | sets[2],
                       "fused_empty_cols")


# ----------------------------------------------------------- ops entry point
def test_fused_tree_entry_backend_scope():
    slabs, vals = _mixed_slabs(seed=300)
    stack = roaring.stack(slabs, capacity=8)
    expr = index.and_(index.leaf(0), index.leaf(1))
    plan, data, meta = index.engine._fused_compile(stack, stack.keys[0],
                                                   expr)
    with kops.backend_scope("xla"):
        bx, cx = kops.fused_tree(data, meta, plan)
    with kops.backend_scope("pallas"):
        bp, cp = kops.fused_tree(data, meta, plan)
    np.testing.assert_array_equal(np.asarray(bx), np.asarray(bp))
    np.testing.assert_array_equal(np.asarray(cx), np.asarray(cp))
    assert int(jnp.sum(cx)) == len(vals[0] & vals[1])
