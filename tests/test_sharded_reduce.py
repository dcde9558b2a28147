"""Sharded wide-reduce byte-identity + the capacity-truncation regression.

``roaring.intersect_all`` / ``union_all`` inside ``shard_map`` over a
multi-device mesh must serialize byte-identically to the single-device
evaluation and to the host oracle. The multi-device half runs in a
subprocess because ``--xla_force_host_platform_device_count`` must be set
before jax initializes; the child re-executes THIS file with ``--child``
(see the bottom of the module) and the pytest side asserts its verdict
line. The in-process tests pin the explicit-``capacity`` regression:
``intersect_all`` used to slice operands to ``capacity`` rows BEFORE
aligning/reducing, so any shared chunk sorted past the capacity cut was
silently dropped — capacity must bound the *live result* rows instead.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro import roaring
from repro.roaring.format import RoaringFormatSpec

CHILD_OK = "SHARDED-REDUCE-OK"


# =============================================================================
# in-process: the capacity-truncation regression (single device)
# =============================================================================

def _cap_operands():
    """a and b share chunk keys {0, 1, 3} but only chunk 3 intersects:
    chunk 0 holds evens vs odds, chunk 1 disjoint halves, chunk 3 an
    overlapping range. The buggy pre-align capacity slice dropped chunk 3
    (the ONLY live result chunk) for every capacity < 3."""
    a_vals = np.concatenate([np.arange(0, 2000, 2),
                             (1 << 16) + np.arange(0, 1000),
                             (3 << 16) + np.arange(0, 5000)])
    b_vals = np.concatenate([np.arange(1, 2000, 2),
                             (1 << 16) + np.arange(40000, 41000),
                             (3 << 16) + np.arange(2500, 9000)])
    a = roaring.RoaringSlab.from_values(a_vals, capacity=4, max_elems=1 << 16)
    b = roaring.RoaringSlab.from_values(b_vals, capacity=4, max_elems=1 << 16)
    return a, b


@pytest.mark.parametrize("cap", [1, 2, 3, 4, 6])
def test_intersect_all_capacity_bounds_live_rows_not_shared_keys(cap):
    a, b = _cap_operands()
    got = jax.jit(
        lambda x, y: roaring.intersect_all([x, y], capacity=cap))(a, b)
    want = RoaringFormatSpec.serialize(a.to_roaring() & b.to_roaring())
    assert got.C == cap                      # explicit capacity is honored
    assert got.serialize() == want           # ... without losing live rows
    assert int(got.card()) == 2500


def test_union_all_explicit_capacity_keeps_live_rows():
    a, b = _cap_operands()
    got = jax.jit(
        lambda x, y: roaring.union_all([x, y], capacity=3))(a, b)
    want = RoaringFormatSpec.serialize(a.to_roaring() | b.to_roaring())
    assert got.serialize() == want


# =============================================================================
# subprocess: >= 2 host devices
# =============================================================================

def test_sharded_reduce_matches_single_device_bytes():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, __file__, "--child"],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert CHILD_OK in proc.stdout, proc.stdout + proc.stderr


def _child() -> None:
    """Runs in the 2-device subprocess (XLA_FLAGS already in the env)."""
    import jax.numpy as jnp  # noqa: F401
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from repro import search
    from repro.core import py_roaring as pr

    assert len(jax.devices()) >= 2, jax.devices()
    mesh = Mesh(np.asarray(jax.devices()).reshape(-1), ("data",))

    rng = np.random.default_rng(7)
    C, B = 4, 4
    n_docs = C << 16

    def mk(size):
        return roaring.RoaringSlab.from_values(
            np.unique(rng.integers(0, n_docs, size=size)), capacity=C,
            max_elems=1 << 16)

    a_sl = [mk(int(s)) for s in rng.integers(100, 30000, B)]
    b_sl = [mk(int(s)) for s in rng.integers(100, 30000, B)]
    a = roaring.stack(a_sl, capacity=C, align=False)
    b = roaring.stack(b_sl, capacity=C, align=False)

    def inter(x, y):
        return roaring.intersect_all([x, y])

    def uni(x, y):
        return roaring.union_all([x, y], capacity=C)

    for name, fn, op in (("intersect_all", inter, "and"),
                         ("union_all", uni, "or")):
        plain = jax.jit(fn)(a, b)
        sharded = jax.jit(shard_map(fn, mesh=mesh,
                                    in_specs=(P("data"), P("data")),
                                    out_specs=P("data")))(a, b)
        for i in range(B):
            ra, rb = a_sl[i].to_roaring(), b_sl[i].to_roaring()
            want = RoaringFormatSpec.serialize(
                ra & rb if op == "and" else ra | rb)
            single = plain[i].serialize()
            multi = sharded[i].serialize()
            assert single == want, f"{name}[{i}]: single != oracle"
            assert multi == single, f"{name}[{i}]: sharded != single"

    # the capacity regression must hold inside shard_map too
    ca, cb = _cap_operands()
    sa = roaring.stack([ca, ca], capacity=4, align=False)
    sb = roaring.stack([cb, cb], capacity=4, align=False)
    out = jax.jit(shard_map(
        lambda x, y: roaring.intersect_all([x, y], capacity=3),
        mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=P("data")))(sa, sb)
    want = RoaringFormatSpec.serialize(ca.to_roaring() & cb.to_roaring())
    for i in range(2):
        assert out[i].serialize() == want, f"capacity regression row {i}"

    # a sharded PostingIndex answers queries identically to the local one,
    # and padding makes any vocabulary size divide the mesh axis
    postings = {f"w{i}": np.unique(rng.integers(0, n_docs, 2000))
                for i in range(5)}            # 1 + 5 rows -> pads to 8
    idx = search.PostingIndex.from_postings(postings, n_docs)
    sharded_idx = idx.shard(mesh)
    assert sharded_idx.n_rows % mesh.shape["data"] == 0
    qs = [search.and_(search.term("w0"), search.term(f"w{i}"))
          for i in range(1, 5)]
    local = search.SearchService(idx, max_batch=4).search_many(qs, "docs")
    svc = search.SearchService(sharded_idx, max_batch=4)
    remote = svc.search_many(qs, "docs")
    # the hot-term cache, where every query kernel runs, stays on one
    # device: a Pallas kernel is not partitioned over a mesh
    c = svc._cache
    assert all(len(x.devices()) == 1
               for x in (c.payload, c.cards, c.kinds, c.nruns))
    for q, l, r in zip(qs, local, remote):
        assert np.array_equal(l, r), f"sharded service diverged on {q}"
        t0, t1 = q.children
        want_ids = (pr.RoaringBitmap.from_array(postings[t0.term])
                    & pr.RoaringBitmap.from_array(postings[t1.term]))
        assert np.array_equal(l, want_ids.to_array())

    print(CHILD_OK)


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child()
    else:
        sys.exit("run via pytest; --child is the subprocess entry")
