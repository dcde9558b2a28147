"""The kind-dispatch engine: one declarative registry of per-pair-class row
kernels, consumed by the slab layer, the XLA reference and the Pallas path.

  * ``AND_TABLE`` — one ``PairClass`` row per live ``(kind_a, kind_b)`` pair,
    naming the row kernel, the output semantic the slab layer must apply, and
    whether the kernel sees the operands swapped;
  * ``make_and_kernels()`` — the row-kernel implementations of the XLA
    reference (galloping, bit probes, word AND, gallop-in-ranges, run
    coverage), vmapped per row and selected per class by mask;
  * ``lift_rows`` — THE kind -> bitmap-domain lift, of every word-op
    evaluation: the slab layer's OR / ANDNOT pipelines, the XLA reference
    and the Pallas word-op kernel. Array rows are lifted by scatter or by
    one-hot matrix products (``ARRAY_LIFTS``; the caller names one, and
    ``device_array_lift`` picks per device); on the chip every row is lifted first,
    the kernel then runs AND / OR / ANDNOT and the popcount over u32 words,
    and the ``mask_*`` classes probe the result at the array side's values
    (``k_probe``), so both backends return the same bytes;
  * union / andnot routing policy (``union_route`` / ``andnot_route``) so the
    slab layer's OR/XOR/ANDNOT pipelines classify from the same table.

Row kernels all share one signature ``fn(x, y, cx, cy, rx, ry)`` over
``(32, 128)`` u16 tiles (one 8 kB container row), returning
``(hits_tile, card)``. ``swap`` in a table row means the kernel receives
``(b, a)`` — e.g. ``bitmap x array`` reuses the ``array x bitmap`` probe with
the roles reversed, and the slab layer compacts the hit mask against the
``b`` side (``out == 'mask_b'``).

Output semantics (``PairClass.out``):
  * ``'bits'``   — ``hits`` is a bitmap-domain row (word-op result);
  * ``'mask_a'`` — ``hits`` is a 0/1 mask over ``a``'s packed array slots;
  * ``'mask_b'`` — same, over ``b``'s slots.

``run x run`` is special-cased by the slab layer: the registry routes it to
the *run-merge* row kernel (``slab_route == 'run_merge'``), a scatter/argsort
formulation that stays entirely in run domain (``jax_roaring._run_merge_row``)
— the ``run_cov_and`` kernel (coverage AND + popcount) is the dispatch
formulation of the same class, kept bit-identical for the backend tests and
for callers that consume the dispatch output directly.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

ROW_WORDS = 4096
ROW_SHAPE = (32, 128)          # u16[32,128] == one 8 kB container row
WORD_SHAPE = (16, 128)         # u32[16,128] == the same row as 32-bit words
MAX_RUNS = ROW_WORDS // 2      # (start, length-1) u16 pairs per row

KIND_EMPTY = 0
KIND_ARRAY = 1
KIND_BITMAP = 2
KIND_RUN = 3

# The registry's public surface (consumed by jax_roaring, ref.py, kernel.py,
# and the repro.index engine). Documented in docs/API.md; tests/test_docs.py
# asserts the two stay in sync.
__all__ = [
    "ROW_WORDS", "ROW_SHAPE", "WORD_SHAPE", "MAX_RUNS",
    "KIND_EMPTY", "KIND_ARRAY", "KIND_BITMAP", "KIND_RUN",
    "PairClass", "AND_TABLE", "class_predicate", "out_mask", "route_mask",
    "union_route", "andnot_route",
    "coverage_by_scatter", "array_coverage_by_scatter",
    "array_coverage_by_matmul", "ARRAY_LIFTS", "on_tpu", "device_array_lift",
    "make_and_kernels", "lift_rows", "to_words", "from_words",
    "bind_args", "META_FIELDS", "unpack_meta",
]


@dataclasses.dataclass(frozen=True)
class PairClass:
    """One cell of the dispatch grid."""

    name: str
    kind_a: int
    kind_b: int
    kernel: str                # row-kernel id in make_and_kernels()
    out: str                   # 'bits' | 'mask_a' | 'mask_b'
    swap: bool = False         # kernel receives (b, a) instead of (a, b)
    slab_route: str = ""       # non-default slab-layer routing ('run_merge')


AND_TABLE: Tuple[PairClass, ...] = (
    PairClass("array_array", KIND_ARRAY, KIND_ARRAY, "gallop", "mask_a"),
    PairClass("array_bitmap", KIND_ARRAY, KIND_BITMAP, "probe", "mask_a"),
    PairClass("bitmap_array", KIND_BITMAP, KIND_ARRAY, "probe", "mask_b",
              swap=True),
    PairClass("bitmap_bitmap", KIND_BITMAP, KIND_BITMAP, "word_and", "bits"),
    PairClass("run_run", KIND_RUN, KIND_RUN, "run_cov_and", "bits",
              slab_route="run_merge"),
    PairClass("array_run", KIND_ARRAY, KIND_RUN, "run_gallop", "mask_a"),
    PairClass("run_array", KIND_RUN, KIND_ARRAY, "run_gallop", "mask_b",
              swap=True),
    PairClass("run_bitmap", KIND_RUN, KIND_BITMAP, "run_mask", "bits"),
    PairClass("bitmap_run", KIND_BITMAP, KIND_RUN, "run_mask", "bits",
              swap=True),
)


def class_predicate(cls: PairClass, ka, kb):
    """Row-selection predicate for one grid cell (jnp, scalar or batched)."""
    return jnp.logical_and(ka == cls.kind_a, kb == cls.kind_b)


def out_mask(out: str, ka, kb):
    """Batched predicate: rows whose AND output has the given semantic,
    honoring the slab-layer override for run x run."""
    acc = jnp.zeros_like(ka, dtype=bool)
    for cls in AND_TABLE:
        if cls.out == out and not cls.slab_route:
            acc = acc | class_predicate(cls, ka, kb)
    return acc


def route_mask(route: str, ka, kb):
    """Batched predicate: rows the slab layer routes specially."""
    acc = jnp.zeros_like(ka, dtype=bool)
    for cls in AND_TABLE:
        if cls.slab_route == route:
            acc = acc | class_predicate(cls, ka, kb)
    return acc


def union_route(ka, kb, ca, cb, array_max: int):
    """OR/XOR routing policy: packed sorted-merge only for array-ish pairs
    whose merged size provably stays under the threshold; every other live
    pair goes through the (kind-aware, run-lift-cheap) bitmap domain."""
    arrayish = (ka != KIND_BITMAP) & (ka != KIND_RUN) & \
               (kb != KIND_BITMAP) & (kb != KIND_RUN)
    small = arrayish & (ca + cb <= array_max)
    live = (ka != KIND_EMPTY) | (kb != KIND_EMPTY)
    return small, live & ~small


def andnot_route(ka, kb):
    """ANDNOT routing: array-A rows probe B in place (any B kind — the result
    is provably <= card_a); bitmap- or run-A rows go bitmap domain."""
    probe = ka == KIND_ARRAY
    lift = (ka == KIND_BITMAP) | (ka == KIND_RUN)
    return probe, lift


# =============================================================================
# row kernels of the XLA reference (vmapped per container row)
# =============================================================================

def _flat_pos():
    return (jax.lax.broadcasted_iota(jnp.int32, ROW_SHAPE, 0) * ROW_SHAPE[1]
            + jax.lax.broadcasted_iota(jnp.int32, ROW_SHAPE, 1))


def _take_flat(row, idx):
    """Gather from a (32,128) tile by flat element index."""
    return jnp.take(row.reshape(ROW_WORDS), idx)


def _run_upper_bound(run_row, n_runs, p):
    """#run-starts <= p, searching the packed (start, len-1) pairs at even
    slots. 12 halvings resolve a window of up to 2048 runs."""
    lo = jnp.zeros_like(p + n_runs)     # varies with both under shard_map
    hi = lo + n_runs

    def body(_, lohi):
        lo, hi = lohi
        open_ = lo < hi                      # empty windows must not probe
        mid = (lo + hi) // 2
        s = _take_flat(run_row, jnp.clip(2 * mid, 0, ROW_WORDS - 2)).astype(
            jnp.int32)
        go_right = open_ & (s <= p)
        return (jnp.where(go_right, mid + 1, lo),
                jnp.where(open_ & ~go_right, mid, hi))

    lo, _ = jax.lax.fori_loop(0, 12, body, (lo, hi))
    return lo


def _run_covered(run_row, n_runs, p):
    """Is position ``p`` inside one of the row's runs? (binary search of the
    run list — the gallop-in-ranges probe.)"""
    idx = _run_upper_bound(run_row, n_runs, p) - 1
    idx_c = jnp.clip(idx, 0, MAX_RUNS - 1)
    s = _take_flat(run_row, 2 * idx_c).astype(jnp.int32)
    l = _take_flat(run_row, 2 * idx_c + 1).astype(jnp.int32)
    return (idx >= 0) & (p <= s + l)


def coverage_by_scatter(run_row, n_runs):
    """Run row -> coverage bitmap tile via difference-array scatter,
    O(n_runs + ROW_WORDS)."""
    flat = run_row.reshape(ROW_WORDS)
    pairs = flat.reshape(MAX_RUNS, 2).astype(jnp.int32)
    s, l = pairs[:, 0], pairs[:, 1]
    valid = (s + l) < (1 << 16)                  # 0xFFFF padding fails this
    e = s + l
    fw, lw = s >> 4, e >> 4
    mask_a = ((0xFFFF << (s & 15)) & 0xFFFF)
    mask_b = (0xFFFF >> (15 - (e & 15)))
    same = fw == lw
    m_first = jnp.where(same, mask_a & mask_b, mask_a)
    partial = jnp.zeros((ROW_WORDS,), jnp.int32)
    partial = partial.at[jnp.where(valid, fw, ROW_WORDS)].add(
        m_first, mode="drop")
    partial = partial.at[jnp.where(valid & ~same, lw, ROW_WORDS)].add(
        mask_b, mode="drop")
    span = valid & (lw > fw)
    diff = jnp.zeros((ROW_WORDS + 1,), jnp.int32)
    diff = diff.at[jnp.where(span, fw + 1, ROW_WORDS + 1)].add(1, mode="drop")
    diff = diff.at[jnp.where(span, lw, ROW_WORDS + 1)].add(-1, mode="drop")
    full = jnp.where(jnp.cumsum(diff)[:ROW_WORDS] > 0, 0xFFFF, 0)
    return (partial | full).astype(jnp.uint16).reshape(ROW_SHAPE)


def array_coverage_by_scatter(arr_row, card):
    """Packed sorted array row -> membership bitmap tile by scatter-add,
    O(ROW_WORDS). Distinct elements set distinct bits, so the add is an
    exact OR."""
    lo = arr_row.reshape(ROW_WORDS).astype(jnp.int32)
    valid = jnp.arange(ROW_WORDS) < card
    word = jnp.where(valid, lo >> 4, ROW_WORDS)           # OOB index dropped
    vals = jnp.where(valid, jnp.uint16(1) << (lo & 15).astype(jnp.uint16),
                     jnp.uint16(0))
    return jnp.zeros((ROW_WORDS,), jnp.uint16).at[word].add(
        vals, mode="drop").reshape(ROW_SHAPE)


def array_coverage_by_matmul(arr_rows, card):
    """Packed sorted array rows -> membership bitmap rows, as one-hot
    matrix products (batched over rows; the MXU does the placing that a
    scatter would do element by element).

    arr_rows: u16[R, 32, 128]; card: i32[R]. A value ``v`` lands in word
    ``w = v >> 4`` at bit ``v & 15``; writing ``w = 128 r + l``, the words
    are ``W[r, l] = sum_j [r_j = r] 2^(b_j) [l_j = l]`` — the product of a
    ``32 x 4096`` and a ``4096 x 128`` one-hot matrix per row. Exact: the
    entries are powers of two (exact in bf16) and each output sums at most
    16 distinct ones (< 2^24, exact in the f32 accumulator).
    """
    R = arr_rows.shape[0]
    v = arr_rows.reshape(R, 1, ROW_WORDS).astype(jnp.int32)
    valid = jnp.arange(ROW_WORDS)[None, None, :] < card[:, None, None]
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, ROW_SHAPE[0], 1), 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, ROW_SHAPE[1], 1), 1)
    a = jnp.where(valid & ((v >> 11) == rows),
                  (1 << (v & 15)).astype(jnp.float32), 0.0)
    b = ((v >> 4) & (ROW_SHAPE[1] - 1)) == lanes
    words = jnp.einsum("rij,rkj->rik", a.astype(jnp.bfloat16),
                       b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return words.astype(jnp.int32).astype(jnp.uint16)


# the array lifts ``lift_rows`` takes, batched over rows: u16[R, 32, 128],
# i32[R] -> u16[R, 32, 128]. The same bytes out; the scatter is the cheap
# one on the CPU, the products on the TPU, whose scatters cost tens of
# microseconds per row
ARRAY_LIFTS = {
    "scatter": jax.vmap(array_coverage_by_scatter),
    "matmul": array_coverage_by_matmul,
}


def on_tpu() -> bool:
    """Does this process run on a TPU? The one device test of the kernel
    layer: the backend ``ops`` auto-selects and the array lift below."""
    return jax.default_backend() == "tpu"


def device_array_lift() -> str:
    """The array lift for the default device: ``"matmul"`` on a TPU,
    ``"scatter"`` elsewhere."""
    return "matmul" if on_tpu() else "scatter"


# rows lifted per step of the chunked lift: bounds the lift temporaries
# (one-hot operands, run scatter rows) when a whole posting stack is lifted
_LIFT_CHUNK_ROWS = 256


def to_words(rows):
    """Bitmap-domain rows u16[..., 4096] -> u32[..., 16, 128]: the same row
    as 32-bit words, the vector unit's lane width and the widest integer
    popcount the TPU lowers. Word ``(s, l)`` holds u16 ``(s, l)`` in its low
    half and u16 ``(s + 16, l)`` in its high half (rows of the ``32 x 128``
    tile), so the conversion moves no data across lanes. Word ops and
    popcounts are the same in either view."""
    halves = rows.reshape(*rows.shape[:-1], 2, *WORD_SHAPE).astype(
        jnp.uint32)
    return halves[..., 0, :, :] | (halves[..., 1, :, :] << 16)


def from_words(words):
    """Inverse of ``to_words``: u32[..., 16, 128] -> u16[..., 4096]."""
    halves = jnp.stack([words & 0xFFFF, words >> 16], axis=-3)
    return halves.astype(jnp.uint16).reshape(*words.shape[:-2], ROW_WORDS)


def _one_run_coverage(rows):
    """Coverage words of rows holding one run: the run ``(start, len-1)`` is
    the row's first pair, and word ``w`` covers values ``16w`` to
    ``16w + 15``, so its bits come from two clipped shifts, with no
    scatter."""
    start = rows[:, :1, :1].astype(jnp.int32)
    end = start + rows[:, :1, 1:2].astype(jnp.int32) + 1
    first = 16 * _flat_pos()[None]
    lo = jnp.clip(start - first, 0, 16)
    hi = jnp.clip(end - first, 0, 16)
    return ((1 << hi) - (1 << lo)).astype(jnp.uint16)


def _lift_flat(rows, kind, card, nruns, array_lift):
    """``lift_rows`` over flat ``[R, 32, 128]`` rows: bitmap rows pass
    through, empty rows become zero, rows of one run are covered in place,
    and each of the array / many-run lifts runs only when the chunk holds
    a row of that kind."""
    one_run = (kind == KIND_RUN) & (nruns == 1)
    bits = jnp.where((kind == KIND_BITMAP)[:, None, None], rows,
                     jnp.where(one_run[:, None, None],
                               _one_run_coverage(rows), jnp.uint16(0)))
    lifts = ((kind == KIND_ARRAY,
              lambda: ARRAY_LIFTS[array_lift](rows, card)),
             ((kind == KIND_RUN) & ~one_run,
              lambda: jax.vmap(coverage_by_scatter)(rows, nruns)))
    for pred, lift in lifts:
        def run(b, pred=pred, lift=lift):
            return jnp.where(pred[:, None, None], lift(), b)

        bits = jax.lax.cond(jnp.any(pred), run, lambda b: b, bits)
    return to_words(bits.reshape(-1, ROW_WORDS))


def lift_rows(rows, kind, card, nruns, array_lift: str):
    """Raw container rows -> their membership bitmaps as 32-bit words,
    whatever their kind.

    rows: u16[..., 4096]; kind/card/nruns: i32[...] (the same leading
    shape); array_lift: a key of ``ARRAY_LIFTS``. Returns u32[..., 16, 128]
    bitmap-domain words (``to_words``; empty rows are all zero). This is
    the leaf load of every word-op evaluation: once each operand is in
    bitmap domain, AND / OR / ANDNOT and the cardinality are word ops and
    popcounts, which is all the Pallas kernel does. Array rows are lifted
    by ``array_lift``, run rows by scatter, in chunks of rows so the
    temporaries stay bounded for a stack of any height. Every op it emits
    sits under the named scope ``roaring.lift``, the lift's stable name in
    a device trace.
    """
    with jax.named_scope("roaring.lift"):
        return _lift_chunked(rows, kind, card, nruns, array_lift)


def _lift_chunked(rows, kind, card, nruns, array_lift):
    lead = kind.shape
    R = kind.size
    flat = (rows.reshape(R, *ROW_SHAPE), kind.reshape(R), card.reshape(R),
            nruns.reshape(R))
    if R <= _LIFT_CHUNK_ROWS:
        return _lift_flat(*flat, array_lift).reshape(*lead, *WORD_SHAPE)
    g = max(d for d in range(1, _LIFT_CHUNK_ROWS + 1) if R % d == 0)
    pad = 0 if 4 * g > _LIFT_CHUNK_ROWS else (-R) % _LIFT_CHUNK_ROWS
    if pad:          # few divisors: pad with empty rows to whole chunks
        g = _LIFT_CHUNK_ROWS
        flat = tuple(jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:],
                                                   x.dtype)])
                     for x in flat)
    out = jax.lax.map(lambda xs: _lift_flat(*xs, array_lift),
                      tuple(x.reshape(-1, g, *x.shape[1:]) for x in flat))
    return out.reshape(R + pad, *WORD_SHAPE)[:R].reshape(*lead, *WORD_SHAPE)


def make_and_kernels() -> Dict[str, Callable]:
    """The AND row kernels of the XLA reference, one per registry cell.

    Every kernel: ``fn(x, y, cx, cy, rx, ry) -> (hits u16[32,128], card)``
    where ``x``/``y`` are the (possibly swapped — see ``PairClass.swap``)
    container-row tiles and ``cx/cy/rx/ry`` their cardinalities / run counts.
    """

    def k_gallop(x, y, cx, cy, rx, ry):
        # vectorized galloping: every lane of x binary-searches y's packed
        # sorted prefix. 13 steps: lower_bound over a window of up to 4096
        # needs ceil(log2(4096)) + 1 halvings.
        a = x.astype(jnp.int32)
        # carries derived from both operands, so they vary with them
        # under shard_map
        lo = jnp.zeros_like(a + cy)
        hi = lo + cy

        def body(_, lohi):
            lo, hi = lohi
            mid = (lo + hi) // 2
            vals = _take_flat(y, jnp.clip(mid, 0, ROW_WORDS - 1)).astype(
                jnp.int32)
            go_right = vals < a
            return (jnp.where(go_right, mid + 1, lo),
                    jnp.where(go_right, hi, mid))

        lo, _ = jax.lax.fori_loop(0, 13, body, (lo, hi))
        found = _take_flat(y, jnp.clip(lo, 0, ROW_WORDS - 1)).astype(
            jnp.int32) == a
        found = found & (lo < cy) & (_flat_pos() < cx)
        return found.astype(jnp.uint16), jnp.sum(found.astype(jnp.int32))

    def k_probe(x, y, cx, cy, rx, ry):
        # bit probes: x's <=4096 packed values index y's bitmap words
        # directly — the 2^16-bit domain is never materialized
        arr = x.astype(jnp.int32)
        word = _take_flat(y, arr >> 4).astype(jnp.int32)
        hit = (((word >> (arr & 15)) & 1) == 1) & (_flat_pos() < cx)
        return hit.astype(jnp.uint16), jnp.sum(hit.astype(jnp.int32))

    def k_word_and(x, y, cx, cy, rx, ry):
        # Algorithm 3: word AND with the popcount fused into the same pass
        res = jnp.bitwise_and(x, y)
        return res, jnp.sum(jax.lax.population_count(res).astype(jnp.int32))

    def k_run_gallop(x, y, cx, cy, rx, ry):
        # gallop-in-ranges: x's packed values binary-search y's run list
        hit = _run_covered(y, ry, x.astype(jnp.int32)) & (_flat_pos() < cx)
        return hit.astype(jnp.uint16), jnp.sum(hit.astype(jnp.int32))

    def k_run_mask(x, y, cx, cy, rx, ry):
        # range-mask: lift x's runs to coverage words, AND with y's bitmap
        res = jnp.bitwise_and(coverage_by_scatter(x, rx), y)
        return res, jnp.sum(jax.lax.population_count(res).astype(jnp.int32))

    def k_run_cov_and(x, y, cx, cy, rx, ry):
        res = jnp.bitwise_and(coverage_by_scatter(x, rx),
                              coverage_by_scatter(y, ry))
        return res, jnp.sum(jax.lax.population_count(res).astype(jnp.int32))

    return {
        "gallop": k_gallop,
        "probe": k_probe,
        "word_and": k_word_and,
        "run_gallop": k_run_gallop,
        "run_mask": k_run_mask,
        "run_cov_and": k_run_cov_and,
    }


def bind_args(cls: PairClass, da, db, ca, cb, ra, rb):
    """Operand roles for one grid cell (apply ``swap``)."""
    if cls.swap:
        return db, da, cb, ca, rb, ra
    return da, db, ca, cb, ra, rb


META_FIELDS = 6  # (kind_a, kind_b, card_a, card_b, nruns_a, nruns_b)


def unpack_meta(meta, i=None):
    """Interleaved i32[6C] meta -> per-row fields (scalar at ``i`` or
    batched slices)."""
    if i is None:
        return tuple(meta[j::META_FIELDS] for j in range(META_FIELDS))
    return tuple(meta[META_FIELDS * i + j] for j in range(META_FIELDS))
