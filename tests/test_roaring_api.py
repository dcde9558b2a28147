"""The ``repro.roaring`` object API: pytree registration, operator algebra
vs the oracle, the portable serialization codec, retrace guards, and the
deprecation shims over the old ``slab_*`` free functions.

Covers the PR 5 checklist: flatten/unflatten round-trip through
``jax.tree_util``, ``serialize``/``deserialize`` identity across all four
container kinds including the 4095/4096/4097 and ``4*n_runs == 8192``
boundaries, operator-vs-oracle bit-identity on random slabs (hypothesis
when installed, the deterministic fallback otherwise), jit/vmap/shard_map
flow of ``a & b | c`` over stacked slabs, and jit-cache stability (no
retrace on same-shape inputs).
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro import index, roaring
from repro.core import RoaringBitmap
from repro.core import jax_roaring as jr
from repro.core import py_roaring as pr
from repro.roaring import RoaringFormatSpec, RoaringSlab

_KIND_OF = {pr.ArrayContainer: jr.KIND_ARRAY,
            pr.BitmapContainer: jr.KIND_BITMAP,
            pr.RunContainer: jr.KIND_RUN}


def _rand_set(n, universe, seed):
    r = np.random.default_rng(seed)
    return np.unique(r.integers(0, universe, size=n))


def _slab_and_oracle(vals, cap=8):
    vals = np.asarray(sorted(set(int(v) for v in vals)), np.int64)
    rb = RoaringBitmap.from_sorted_unique(vals)
    return RoaringSlab.from_roaring(rb, cap), rb


def _assert_matches(slab: RoaringSlab, oracle: RoaringBitmap, tag=""):
    """values, card, kind, and packed payload must all match the oracle —
    the serialized byte streams are a complete proxy for all four."""
    assert int(slab.card()) == len(oracle), tag
    keys = np.asarray(slab.keys)
    kinds = np.asarray(slab.kinds)
    assert list(keys[kinds != jr.KIND_EMPTY]) == list(oracle.keys), tag
    assert slab.serialize() == RoaringFormatSpec.serialize(oracle), tag


# ------------------------------------------------------------------- pytree
def test_pytree_flatten_unflatten_round_trip():
    s, _ = _slab_and_oracle(_rand_set(5000, 1 << 18, 0))
    leaves, treedef = jax.tree_util.tree_flatten(s)
    assert len(leaves) == 5                       # keys/kinds/cards/nruns/payload
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(back, RoaringSlab) and back.C == s.C
    for a, b in zip(leaves, jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # tree_map preserves structure and static capacity
    mapped = jax.tree.map(lambda x: x, s)
    assert isinstance(mapped, RoaringSlab) and mapped.capacity == s.capacity
    # two same-capacity slabs share a treedef (jit cache key sanity)
    t, _ = _slab_and_oracle(_rand_set(100, 1 << 18, 1))
    assert jax.tree_util.tree_structure(s) == jax.tree_util.tree_structure(t)


def test_pytree_capacity_is_static_aux_data():
    s, _ = _slab_and_oracle(_rand_set(500, 1 << 18, 2), cap=4)
    t, _ = _slab_and_oracle(_rand_set(500, 1 << 18, 3), cap=8)
    assert jax.tree_util.tree_structure(s) != jax.tree_util.tree_structure(t)


@settings(max_examples=15, deadline=None)
@given(st.sets(st.integers(0, (1 << 18) - 1), max_size=400))
def test_pytree_round_trip_property(vals):
    s, _ = _slab_and_oracle(vals, cap=4)
    leaves, treedef = jax.tree_util.tree_flatten(s)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.serialize() == s.serialize()


# -------------------------------------------------------- operators vs oracle
def _pair(seed):
    r = np.random.default_rng(seed)
    kind = seed % 3
    if kind == 0:     # scattered arrays
        va = _rand_set(3000, 1 << 18, seed)
        vb = _rand_set(4000, 1 << 18, seed + 100)
    elif kind == 1:   # dense bitmaps vs arrays
        va = _rand_set(60_000, 4 << 16, seed)
        vb = _rand_set(2500, 4 << 16, seed + 100)
    else:             # run-shaped vs scattered
        starts = np.sort(r.integers(0, 1 << 18, 25))
        ra = RoaringBitmap.from_ranges(
            [(int(s), int(s) + int(l)) for s, l in
             zip(starts, r.integers(1, 400, 25))])
        va = ra.to_array()
        vb = _rand_set(3000, 1 << 18, seed + 100)
    a, rba = _slab_and_oracle(va)
    b, rbb = _slab_and_oracle(vb)
    return a, b, rba, rbb


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_operators_bit_identical_to_oracle(seed):
    a, b, ra, rb = _pair(seed)
    _assert_matches(a & b, ra & rb, f"and {seed}")
    _assert_matches(a | b, ra | rb, f"or {seed}")
    _assert_matches(a ^ b, ra ^ rb, f"xor {seed}")
    _assert_matches(a - b, ra.andnot(rb), f"andnot {seed}")
    assert int(a.and_card(b)) == len(ra & rb)
    assert int(a.or_card(b)) == len(ra | rb)


@settings(max_examples=12, deadline=None)
@given(st.sets(st.integers(0, (1 << 17) - 1), max_size=300),
       st.sets(st.integers(0, (1 << 17) - 1), max_size=300))
def test_operator_property_random_slabs(va, vb):
    a, ra = _slab_and_oracle(va, cap=4)
    b, rb = _slab_and_oracle(vb, cap=4)
    _assert_matches(a & b, ra & rb, "and")
    _assert_matches(a | b, ra | rb, "or")
    _assert_matches(a ^ b, ra ^ rb, "xor")
    _assert_matches(a - b, ra.andnot(rb), "andnot")


def test_method_surface_matches_oracle():
    vals = np.concatenate([np.arange(1000, 9000),          # run-shaped chunk
                           (2 << 16) + _rand_set(300, 1 << 16, 7)])
    s, rb = _slab_and_oracle(vals, cap=4)
    s = s.run_optimize()
    rb.run_optimize()
    assert int(s.card()) == len(rb)
    assert int(s.size_in_bytes()) == rb.size_in_bytes()
    q = np.asarray([0, 1000, 8999, 9000, (2 << 16) + 1])
    got = np.asarray(s.contains(jnp.asarray(q)))
    assert got.tolist() == [rb.contains(int(x)) for x in q]
    assert int(s.rank(jnp.int32(8999))) == rb.rank(8999)
    assert int(s.select(jnp.int32(17))) == rb.select(17)
    assert int(s.select(jnp.int32(len(rb)))) == -1
    dense = s.to_dense()
    assert dense.sum() == len(rb) and dense[vals].all()
    idx, valid = s.to_indices(1 << 14)
    np.testing.assert_array_equal(np.asarray(idx)[np.asarray(valid)],
                                  rb.to_array())


# ------------------------------------------------------ serialization codec
def _codec_round_trip(rb: RoaringBitmap, cap=4):
    blob = RoaringFormatSpec.serialize(rb)
    back = RoaringFormatSpec.deserialize(blob)
    assert back.keys == rb.keys
    for c1, c2 in zip(back.containers, rb.containers):
        assert type(c1) is type(c2)
    np.testing.assert_array_equal(back.to_array(), rb.to_array())
    # slab side: byte-identical stream, kind-identical slab after reload
    s = RoaringSlab.from_roaring(rb, cap)
    assert s.serialize() == blob
    s2 = RoaringSlab.deserialize(blob, capacity=cap)
    np.testing.assert_array_equal(np.asarray(s2.kinds), np.asarray(s.kinds))
    np.testing.assert_array_equal(np.asarray(s2.cards), np.asarray(s.cards))
    assert s2.serialize() == blob


@pytest.mark.parametrize("card", [4095, 4096, 4097])
def test_serialize_array_bitmap_boundary(card):
    vals = np.arange(0, 2 * card, 2)[:card]           # no runs: 2-gaps
    rb = RoaringBitmap.from_sorted_unique(vals)
    want = pr.ArrayContainer if card <= 4096 else pr.BitmapContainer
    assert type(rb.containers[0]) is want
    _codec_round_trip(rb)


def test_serialize_all_four_kinds_one_stream():
    rb = RoaringBitmap.from_ranges([(0, 70000)])              # run rows
    rb.ior(RoaringBitmap.from_sorted_unique(
        (4 << 16) + _rand_set(200, 1 << 16, 0)))              # array row
    rb.ior(RoaringBitmap.from_sorted_unique(
        (5 << 16) + _rand_set(30000, 1 << 16, 1)))            # bitmap row
    kinds = {type(c) for c in rb.containers}
    assert kinds == {pr.ArrayContainer, pr.BitmapContainer, pr.RunContainer}
    _codec_round_trip(rb, cap=8)


def test_serialize_run_size_tie_boundary():
    """A container with 4*n_runs == 8192 (2048 runs): the codec must carry
    the run encoding verbatim, while runOptimize flips it — the strict
    best-of-three rule never keeps a run at the tie."""
    starts = np.arange(0, 4096, 2, dtype=np.int64)            # 2048 1-runs
    rb = RoaringBitmap()
    rb.keys.append(0)
    rb.containers.append(pr.RunContainer(starts, np.zeros(2048, np.int64)))
    assert 4 * rb.containers[0].n_runs == 8192
    _codec_round_trip(rb, cap=2)
    s = RoaringSlab.from_roaring(rb, 2)
    assert int(s.nruns[0]) == 2048
    opt = s.run_optimize()
    # card 2048 <= 4096 and 2*card = 4096 < 8192: array must win
    assert int(opt.kinds[0]) == jr.KIND_ARRAY


def test_serialize_small_run_stream_no_offset_header():
    """< NO_OFFSET_THRESHOLD containers with runs: the offset header is
    absent — layout must still round-trip."""
    rb = RoaringBitmap.from_ranges([(10, 5000), (70000, 70100)])
    assert len(rb.keys) < RoaringFormatSpec.NO_OFFSET_THRESHOLD
    _codec_round_trip(rb)


def test_serialize_empty_and_garbage():
    rb = RoaringBitmap()
    _codec_round_trip(rb, cap=1)
    with pytest.raises(ValueError):
        RoaringFormatSpec.deserialize(b"\x00\x01\x02\x03\x04")


@settings(max_examples=15, deadline=None)
@given(st.sets(st.integers(0, (1 << 18) - 1), max_size=500))
def test_serialize_round_trip_property(vals):
    s, rb = _slab_and_oracle(vals, cap=4)
    if len(rb.keys) == 0:
        _codec_round_trip(rb, cap=1)
        return
    _codec_round_trip(rb)
    assert RoaringSlab.deserialize(s.serialize()).serialize() == s.serialize()


# ------------------------------------------------- jit / vmap / shard_map
def _stacked_triple(cap=4, n=4):
    A = [_rand_set(3000, 1 << 18, 10 + i) for i in range(n)]
    B = [_rand_set(4000, 1 << 18, 20 + i) for i in range(n)]
    C = [_rand_set(2000, 1 << 18, 30 + i) for i in range(n)]
    st_ = lambda xs: roaring.stack(
        [RoaringSlab.from_values(x, cap, 1 << 14) for x in xs], align=False)
    want = [len((RoaringBitmap.from_sorted_unique(A[i])
                 & RoaringBitmap.from_sorted_unique(B[i]))
                | RoaringBitmap.from_sorted_unique(C[i]))
            for i in range(n)]
    return st_(A), st_(B), st_(C), want


def test_jit_vmap_expression_over_stacked_slabs():
    a, b, c, want = _stacked_triple()
    f = jax.jit(lambda a, b, c: (a & b | c).card())
    assert np.asarray(f(a, b, c)).tolist() == want
    g = jax.vmap(lambda a, b, c: (a & b | c).card())
    assert np.asarray(g(a, b, c)).tolist() == want
    # single & stacked broadcast
    one = a[0]
    sc = np.asarray(b.and_card(one))
    assert len(sc) == b.n_slabs


def test_shard_map_expression_over_stacked_slabs():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    a, b, c, want = _stacked_triple()
    mesh = Mesh(np.asarray(jax.devices()).reshape(-1), ("data",))
    if a.n_slabs % mesh.shape["data"]:
        pytest.skip("slab axis must divide the mesh axis")
    f = jax.jit(shard_map(
        lambda a, b, c: (a & b | c).card(), mesh=mesh,
        in_specs=(P("data"),) * 3, out_specs=P("data")))
    assert np.asarray(f(a, b, c)).tolist() == want


# ------------------------------------------------------------ retrace guard
def test_jitted_ops_do_not_retrace_on_same_shapes():
    """Same-shape inputs must hit the jit cache (the PR 4 lesson: eager
    lax.cond closures re-trace every call — jitted entry points must not)."""
    f_and = jax.jit(lambda a, b: a & b)
    f_card = jax.jit(lambda a, b: a.and_card(b))
    for seed in (0, 1, 2):
        a, _ = _slab_and_oracle(_rand_set(2000, 1 << 18, 40 + seed))
        b, _ = _slab_and_oracle(_rand_set(3000, 1 << 18, 50 + seed))
        jax.block_until_ready(f_and(a, b).cards)
        jax.block_until_ready(f_card(a, b))
    assert f_and._cache_size() == 1, f_and._cache_size()
    assert f_card._cache_size() == 1, f_card._cache_size()


def test_jitted_execute_does_not_retrace_on_same_shapes():
    expr = index.and_(index.or_(index.leaf(0), index.leaf(1)), index.leaf(2))
    f = jax.jit(lambda st: index.execute_card(st, expr))
    g = jax.jit(lambda st: index.execute(st, expr).cards)
    for seed in (0, 1, 2):
        slabs = [RoaringSlab.from_values(_rand_set(2000, 1 << 18, seed + i), 4,
                                         1 << 14) for i in range(3)]
        stck = roaring.stack(slabs, capacity=4)
        jax.block_until_ready(f(stck))
        jax.block_until_ready(g(stck))
    assert f._cache_size() == 1, f._cache_size()
    assert g._cache_size() == 1, g._cache_size()


# -------------------------------------------------------- engine integration
def test_execute_with_slab_leaves_and_stack_members():
    sets = [_rand_set(2500 + 400 * i, 1 << 18, 60 + i) for i in range(4)]
    rbs = [RoaringBitmap.from_sorted_unique(s) for s in sets]
    slabs = [RoaringSlab.from_values(s, 8, 1 << 14) for s in sets]
    # slab leaves only, no stack bookkeeping
    got = index.execute(index.andnot(index.leaf(slabs[0]),
                                     index.or_(index.leaf(slabs[1]),
                                               index.leaf(slabs[2]))),
                        capacity=8)
    want = rbs[0].andnot(rbs[1] | rbs[2])
    assert isinstance(got, RoaringSlab)
    _assert_matches(got, want, "slab leaves")
    # int leaves over a stack still work and return the object type
    stck = roaring.stack(slabs, capacity=8)
    got2 = index.execute(stck, index.andnot(
        index.leaf(0), index.or_(index.leaf(1), index.leaf(2))))
    assert got2.serialize() == got.serialize()


def test_intersect_all_shared_keys_beyond_capacity():
    """Regression: alignment must use the *intersected* key set — with a
    union-key alignment, keys shared by all operands could be truncated
    past min(C) and silently dropped from the intersection."""
    va = np.concatenate([np.arange(3) << 16, [(100 << 16) + 7]])
    vb = np.concatenate([(np.arange(3, 6) << 16) + 1, [(100 << 16) + 7]])
    a = RoaringSlab.from_values(va, 4, 16)     # chunks {0,1,2,100}, C=4
    b = RoaringSlab.from_values(vb, 4, 16)     # chunks {3,4,5,100}, C=4
    # merged distinct keys exceed min(C)=4; only chunk 100 is shared
    got = roaring.intersect_all([a, b])
    assert int(got.card()) == int((a & b).card()) == 1
    assert int(got.select(jnp.int32(0))) == (100 << 16) + 7


def test_union_all_and_intersect_all():
    sets = [_rand_set(2000 + 300 * i, 1 << 18, 70 + i) for i in range(5)]
    rbs = [RoaringBitmap.from_sorted_unique(s) for s in sets]
    slabs = [RoaringSlab.from_values(s, 8, 1 << 14) for s in sets]
    from repro.core import union_many
    _assert_matches(roaring.union_all(slabs, capacity=8), union_many(rbs),
                    "union_all")
    want = rbs[0]
    for r in rbs[1:]:
        want = want & r
    _assert_matches(roaring.intersect_all(slabs), want, "intersect_all")


# --------------------------------------------------------- deprecation shims
def test_slab_free_functions_warn_and_still_work():
    va, vb = _rand_set(500, 1 << 17, 80), _rand_set(600, 1 << 17, 81)
    a = jr.from_dense_array(va, 4, 1 << 12)
    b = jr.from_dense_array(vb, 4, 1 << 12)
    ra = RoaringBitmap.from_sorted_unique(va)
    rb = RoaringBitmap.from_sorted_unique(vb)
    with pytest.warns(DeprecationWarning, match="slab_and is deprecated"):
        got = jr.slab_and(a, b, capacity=4)
    assert int(got.cardinality) == len(ra & rb)
    with pytest.warns(DeprecationWarning, match="slab_or "):
        assert int(jr.slab_or(a, b).cardinality) == len(ra | rb)
    with pytest.warns(DeprecationWarning, match="slab_and_card"):
        assert int(jr.slab_and_card(a, b)) == len(ra & rb)
    with pytest.warns(DeprecationWarning, match="slab_select"):
        assert int(jr.slab_select(a, 0)) == int(va[0])
    with pytest.warns(DeprecationWarning, match="slab_run_optimize"):
        jr.slab_run_optimize(a)
    with pytest.warns(DeprecationWarning, match="stack_from_slabs"):
        index.stack_from_slabs([RoaringSlab.from_values(va, 4, 1 << 12)],
                               capacity=4)
    with pytest.warns(DeprecationWarning, match="union_many_batched"):
        index.union_many_batched(
            [RoaringSlab.from_values(va, 4, 1 << 12)], capacity=4)


def test_object_api_emits_no_deprecation_warnings():
    va, vb = _rand_set(500, 1 << 17, 82), _rand_set(600, 1 << 17, 83)
    a = RoaringSlab.from_values(va, 4, 1 << 12)
    b = RoaringSlab.from_values(vb, 4, 1 << 12)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        (a & b).card()
        (a | b).serialize()
        a.and_card(b)
        a.run_optimize()
        roaring.union_all([a, b], capacity=8)
        index.execute(index.and_(index.leaf(a), index.leaf(b)), capacity=4)
