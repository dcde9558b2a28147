"""Compile the main path's kernels for a TPU v5e without the chip.

The TPU compiler is installed beside JAX, so a described (not attached) v5e
chip compiles the Pallas kernels exactly as the chip's compiler would — a
kernel Mosaic refuses fails here, in the CPU test run, and not on the chip.
Nothing runs: these tests say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: one process at a time may load the TPU library, and the
suite runs under several pytest-xdist workers that all import this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.roaring import fused as F
from repro.kernels.roaring import kernel as K
from repro.kernels.roaring import ref as R

C = 135                 # chunk rows per posting at 8.8 M documents
ROW = 4096


@pytest.fixture(scope="module")
def topology():
    """The four chips of a v5e:2x2 host, described, not attached."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topology):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture
def chip_backend(monkeypatch):
    """Auto-selected launches resolve as they do on the chip: the Pallas
    kernel, compiled (not interpreted), and the chip's array lift. Traces
    made for the CPU are dropped first, so none is reused."""
    from repro.kernels.roaring import dispatch as D
    jax.clear_caches()
    monkeypatch.setattr(D, "on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _four_chip_mesh(topology):
    """The host's four chips as the (4, 1) ("data", "model") mesh of
    ``launch.mesh.make_test_mesh``."""
    from jax.sharding import AxisType, Mesh
    devices = np.asarray(topology.devices).reshape(4, 1)
    return Mesh(devices, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, sharding, *shapes):
    args = [_spec(s, d, sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _tree(n: int):
    """A mixed AND / OR / ANDNOT tree over ``n`` leaves."""
    if n == 2:
        return ("and", 0, 1)
    pairs = [("and", i, i + 1) if (i // 2) % 2 == 0 else ("andnot", i, i + 1)
             for i in range(0, n - 1, 2)]
    return ("or",) + tuple(pairs) + ((n - 1,) if n % 2 else ())


def _fused_args(n):
    return ((n, C, ROW), jnp.uint16), ((3 * n * C,), jnp.int32)


@pytest.mark.parametrize("n", [2, 16, 64])
def test_fused_tree_compiles_for_v5e(one_chip, n):
    plan = F.plan_tape(_tree(n))
    assert plan.n_operands == n
    compiled = _compile(
        lambda d, m: F.fused_eval_pallas(d, m, plan=plan, interpret=False),
        one_chip, *_fused_args(n))
    assert "tpu_custom_call" in compiled.as_text()


def test_intersect_dispatch_compiles_for_v5e(one_chip):
    compiled = _compile(
        lambda a, b, m: K.intersect_dispatch_pallas(a, b, m, interpret=False),
        one_chip, ((C, ROW), jnp.uint16), ((C, ROW), jnp.uint16),
        ((6 * C,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shared_query", [False, True],
                         ids=["pairs", "shared_query"])
def test_intersect_dispatch_stacked_compiles_for_v5e(one_chip, shared_query):
    n = 64
    b_shape = (C, ROW) if shared_query else (n, C, ROW)
    compiled = _compile(
        lambda a, b, m: K.intersect_dispatch_stacked_pallas(
            a, b, m, interpret=False),
        one_chip, ((n, C, ROW), jnp.uint16), (b_shape, jnp.uint16),
        ((n, 6 * C), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["and", "or", "xor", "andnot"])
def test_container_op_compiles_for_v5e(one_chip, op):
    compiled = _compile(
        lambda a, b, k: K.container_op_pallas(a, b, k, op, interpret=False),
        one_chip, ((C, ROW), jnp.uint16), ((C, ROW), jnp.uint16),
        ((2 * C,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_references_compile_for_v5e(one_chip):
    n = 64
    plan = F.plan_tape(_tree(n))
    _compile(lambda d, m: F.fused_eval_ref(d, m, plan=plan), one_chip,
             *_fused_args(n))
    _compile(R.intersect_dispatch_ref, one_chip, ((C, ROW), jnp.uint16),
             ((C, ROW), jnp.uint16), ((6 * C,), jnp.int32))


# ---------------------------------------------- the four-chip sharded path
_STACK_LEAVES = (jnp.uint16, jnp.int32, jnp.int32, jnp.int32, jnp.int32)


def _slab_specs(lead, sharding):
    """(payload, keys, kinds, cards, nruns) specs of slabs ``lead + [C]``."""
    return [_spec(lead + (C, ROW) if i == 0 else lead + (C,), d, sharding)
            for i, d in enumerate(_STACK_LEAVES)]


def test_sharded_scoring_compiles_for_v5e_2x2(topology, chip_backend):
    """``batched_and_card_sharded`` / ``topk_by_card_sharded`` over the four
    chips: each chip scores its quarter of the stack with the Pallas kernel
    under ``shard_map``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import index as ix
    from repro.roaring import RoaringSlab

    mesh = _four_chip_mesh(topology)
    n = 4 * 16

    def score(stack_leaves, query_leaves):
        stack, query = (RoaringSlab(payload=p, keys=k, kinds=t, cards=c,
                                    nruns=r, C=C)
                        for p, k, t, c, r in (stack_leaves, query_leaves))
        return (ix.batched_and_card_sharded(stack, query, mesh),
                ix.topk_by_card_sharded(stack, query, 10, mesh))

    compiled = jax.jit(score).lower(
        _slab_specs((n,), NamedSharding(mesh, P("data"))),
        _slab_specs((), NamedSharding(mesh, P()))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_service_programs_compile_for_v5e_2x2(topology, one_chip,
                                                      chip_backend):
    """The sharded service: its cache fill gathers from the stack sharded
    over the four chips, and its query programs run where the cache lives,
    on ONE chip, with the Pallas kernel. Over cache arrays spread on the
    mesh the same program does not compile: Mosaic does not partition a
    Pallas kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.search import service as S

    mesh = _four_chip_mesh(topology)
    sharded, replicated = (NamedSharding(mesh, P("data")),
                           NamedSharding(mesh, P()))
    n, slots, B = 4 * 16, 64, 8
    stack = [_spec((n, C, ROW), jnp.uint16, sharded)] + [
        _spec((n, C), jnp.int32, sharded)] * 3
    S.fetch_rows.lower(*stack, _spec((slots,), jnp.int32, replicated)
                       ).compile()

    def cache(sharding):
        return [_spec((slots + 1, C, ROW), jnp.uint16, sharding)] + [
            _spec((slots + 1, C), jnp.int32, sharding)] * 3

    one = cache(one_chip)
    fetched = [_spec((slots,) + x.shape[1:], x.dtype, one_chip) for x in one]
    S.fill_slots.lower(*one, *fetched,
                       _spec((slots,), jnp.int32, one_chip)).compile()
    expr, terms = S.lower(S.andnot(S.term("a"), S.term("b")))
    batch_slots = _spec((B, len(terms)), jnp.int32, one_chip)
    for want_rows in (False, True):
        fused, _ = S.query_programs(expr, B, len(terms), C, want_rows)
        compiled = jax.jit(fused).lower(*one, batch_slots).compile()
        assert "tpu_custom_call" in compiled.as_text()
    with pytest.raises(NotImplementedError, match="partitioned"):
        jax.jit(fused).lower(*cache(replicated),
                             _spec((B, len(terms)), jnp.int32, replicated))


# ------------------------------------------------- the planned top-k scoring
# the width classes of the facet cell's 4,096 Zipf-sized terms: the groups
# of 16 stack rows each class visits, from 128 slots to 4,096 (the last
# holds the bitmap rows)
_FACET_CLASSES = {128: 237, 256: 12, 512: 6, 1024: 3, 2048: 2, 4096: 2}


@pytest.mark.parametrize("chunks", [C, 16], ids=["chunk_major", "row_major"])
def test_planned_scoring_compiles_for_v5e(one_chip, chip_backend, chunks):
    """``topk_by_scan`` over 4,097 stack rows with the facet cell's classes:
    one ``roaring_card_scan`` launch per width class, no lifted words over
    the stack, in well under the 9.59 GB of temporaries the whole-stack lift
    takes. The stack has the layout the chip gives it (as every other
    program over it expects): chunk-major at the cell's 135 chunk rows,
    read in place with no copy; row-major at 16, transposed once per
    scoring."""
    import re

    from repro import index as ix
    from repro.kernels.roaring import scan as S
    from repro.roaring import RoaringSlab

    N = 4097

    def i32(*shape):
        return _spec(shape, jnp.int32, one_chip)

    groups = _FACET_CLASSES.values()
    plan = ix.ScanPlan(
        keys=i32(chunks), groups=tuple(i32(g) for g in groups),
        places=tuple(i32(g * S.GROUP) for g in groups),
        codes=tuple(i32(g, S.GROUP, chunks) for g in groups),
        residual=i32(0), order=i32(N), widths=tuple(_FACET_CLASSES),
        bitmaps=(False,) * 5 + (True,), rows_lifted=chunks, slots_scanned=0,
        slots_total=0)
    stack = RoaringSlab(
        payload=_spec((N, chunks, ROW), jnp.uint16, one_chip),
        keys=i32(N, chunks), kinds=i32(N, chunks), cards=i32(N, chunks),
        nruns=i32(N, chunks), C=chunks)
    query = RoaringSlab(payload=_spec((chunks, ROW), jnp.uint16, one_chip),
                        keys=i32(chunks), kinds=i32(chunks),
                        cards=i32(chunks), nruns=i32(chunks), C=chunks)
    compiled = jax.jit(lambda st, p, q: ix.topk_by_scan(st, p, q, 10)).lower(
        stack, plan, query).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == \
        len(_FACET_CLASSES)
    stack_bytes = N * chunks * ROW * 2
    copies = re.findall(rf"u16\[({N},{chunks}|{chunks},{N}),{ROW}\]"
                        r"\S* (?:copy|transpose)\(", text)
    assert len(copies) == (0 if chunks == C else 1)
    assert compiled.memory_analysis().temp_size_in_bytes < \
        len(copies) * stack_bytes + (1 << 30)
    for dims in re.findall(r"u32\[([\d,]+)\]", text):
        shape = [int(d) for d in dims.split(",")]
        if shape[-2:] == [16, 128]:
            assert np.prod(shape[:-2]) < N * chunks, shape
