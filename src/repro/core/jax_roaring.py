"""TPU-native Roaring bitmap: the static-shape container slab.

The paper's dynamic two-level structure is re-thought for accelerator
execution (static shapes, no pointer chasing):

  * a ``RoaringSlab`` holds up to ``C`` containers. Row ``i`` of ``data``
    (u16[4096], 8 kB) is a packed sorted u16 array (first ``card[i]``
    entries), a 2^16-bit bitmap stored as 4096 16-bit words, *or* a packed
    run list — sorted ``(start, length-1)`` u16 pairs (the 2016 follow-up
    paper's run containers), padded with ``(0xFFFF, 0xFFFF)`` which can never
    be a valid run. The paper's 4096-element threshold is exactly the
    break-even where array and bitmap forms cost 8 kB, so a uniform slab row
    wastes nothing at the boundary; runs reuse the same row.
  * ``keys`` is the sorted first-level index (padded with ``KEY_SENTINEL``),
    ``card`` the per-container cardinality counters (paper S2), ``kind`` the
    container type tag (0 empty / 1 array / 2 bitmap / 3 run).

Set algebra runs the *kind-dispatch engine*: key-aligned container pairs are
classified by ``(kind_a, kind_b)`` against the declarative registry in
``repro.kernels.roaring.dispatch`` (one ``PairClass`` per grid cell naming
the row kernel and output semantic) and routed through the matching
algorithm — vectorized galloping for array x array, bit probes for
array x bitmap (no domain lift), fused word-op + popcount for
bitmap x bitmap, gallop-in-ranges for array x run, range-mask coverage for
run x bitmap, and a run-domain merge for run x run that never materializes
bits at all. The XLA reference computes these paths cond-guarded per
class; on TPU ``repro.kernels.roaring`` lifts the rows to bitmap domain and
runs the word AND in its Pallas kernel, applying the same table's output
semantics. Output
canonicalization is *best-of-three* (``runOptimize``: array vs bitmap vs run
by serialized size) and *lazy*: only bitmap-domain rows whose canonical form
is packed (array or run) pay the O(2^16) extraction, and those passes are
``lax.cond``-guarded so array- and run-dominated workloads never touch the
2^16-element domain at runtime. Cardinality is maintained with
``lax.population_count`` (the popcnt the paper leans on) fused into the same
pass, mirroring Algorithm 1/3. See DESIGN.md for the dispatch table.

All functions are jit-/vmap-/pjit-compatible and allocation-free at trace
time; capacities are static Python ints.
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.roaring import dispatch as _D

CHUNK_BITS = 16
CHUNK_SIZE = 1 << CHUNK_BITS
ARRAY_MAX = 4096                 # paper's array/bitmap threshold
ROW_WORDS = 4096                 # 4096 x u16 words = 2^16 bits = 8 kB
MAX_RUNS = ROW_WORDS // 2        # (start, length-1) pairs per run row
KEY_SENTINEL = jnp.int32(1 << 20)

KIND_EMPTY = _D.KIND_EMPTY       # 0
KIND_ARRAY = _D.KIND_ARRAY       # 1
KIND_BITMAP = _D.KIND_BITMAP     # 2
KIND_RUN = _D.KIND_RUN           # 3

# raw row *forms* flowing into the canonicalization engine (how a computed
# row is currently represented, before best-of-three picks its final kind)
FORM_ARRAY, FORM_BITS, FORM_RUNS = 0, 1, 2

# The public slab API. Every symbol listed here is documented in docs/API.md
# (tests/test_docs.py asserts the two stay in sync).
__all__ = [
    # layout constants
    "CHUNK_BITS", "CHUNK_SIZE", "ARRAY_MAX", "ROW_WORDS", "MAX_RUNS",
    "KEY_SENTINEL", "KIND_EMPTY", "KIND_ARRAY", "KIND_BITMAP", "KIND_RUN",
    # container slab + constructors / exporters
    "RoaringSlab", "empty", "from_indices", "from_dense_array",
    "from_roaring", "from_ranges", "to_roaring", "to_indices", "extract_row",
    "slab_run_optimize",
    # membership / rank / select
    "contains", "rank", "slab_select",
    # pairwise set algebra (kind-dispatch engine)
    "slab_and", "slab_or", "slab_xor", "slab_andnot",
    "slab_and_card", "slab_or_card", "slab_jaccard",
    # batched / wide ops
    "stack_slabs", "slab_and_many", "slab_and_card_many",
    "union_many_slabs",
    # legacy bitmap-domain A/B baselines
    "slab_and_bitmap_domain", "slab_or_bitmap_domain",
]


class RoaringSlab(NamedTuple):
    """Static-capacity Roaring bitmap. ``C = keys.shape[0]`` containers."""

    keys: jax.Array   # i32[C], sorted, inactive rows = KEY_SENTINEL
    card: jax.Array   # i32[C]
    kind: jax.Array   # i32[C] in {0,1,2}
    data: jax.Array   # u16[C, 4096]

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    @property
    def n_containers(self) -> jax.Array:
        return jnp.sum(self.kind != KIND_EMPTY)

    @property
    def cardinality(self) -> jax.Array:
        """Sum of per-container counters (paper S2)."""
        return jnp.sum(self.card)

    def size_in_bytes(self) -> jax.Array:
        """Compressed serialized size (the paper's bits/item metric): 8-byte
        index header + 4 bytes/container header + per-kind payload — 2*card
        (array), 8192 (bitmap), 4*n_runs (run). Matches the oracle's
        ``RoaringBitmap.size_in_bytes`` accounting row for row."""
        nr = _rows_nruns(self.data, self.kind)
        payload = jnp.where(self.kind == KIND_ARRAY, 2 * self.card,
                            jnp.where(self.kind == KIND_BITMAP, 2 * ROW_WORDS,
                                      jnp.where(self.kind == KIND_RUN, 4 * nr,
                                                0)))
        live = (self.kind != KIND_EMPTY).astype(jnp.int32)
        return 8 + jnp.sum(live * (4 + payload))


def empty(capacity: int) -> RoaringSlab:
    """All-empty slab of static container capacity ``capacity``.

    Every row has ``kind == KIND_EMPTY``, ``card == 0``, key
    ``KEY_SENTINEL`` and zeroed payload — the identity element of
    ``slab_or`` / ``union_many_slabs``.
    """
    return RoaringSlab(
        keys=jnp.full((capacity,), KEY_SENTINEL, dtype=jnp.int32),
        card=jnp.zeros((capacity,), dtype=jnp.int32),
        kind=jnp.zeros((capacity,), dtype=jnp.int32),
        data=jnp.zeros((capacity, ROW_WORDS), dtype=jnp.uint16),
    )


# =============================================================================
# row-level helpers (one container)
# =============================================================================

def row_array_to_bits(row: jax.Array, card: jax.Array) -> jax.Array:
    """Packed sorted u16 array row -> 4096-word bitmap row (scatter-add:
    distinct elements set distinct bits, so the add is an exact OR)."""
    return _D.array_coverage_by_scatter(row.reshape(_D.ROW_SHAPE),
                                        card).reshape(ROW_WORDS)


def row_run_to_bits(row: jax.Array) -> jax.Array:
    """Packed run-pair row -> 4096-word coverage bitmap (the range-mask lift:
    difference-array scatter, O(n_runs + 4096) — never the 2^16 domain)."""
    return _D.coverage_by_scatter(row.reshape(_D.ROW_SHAPE),
                                  jnp.int32(MAX_RUNS)).reshape(ROW_WORDS)


def _row_run_parts(row: jax.Array):
    """(starts, length-1, valid) i32 views of a run row's 2048 pair slots.
    The ``(0xFFFF, 0xFFFF)`` padding fails ``start + length-1 < 2^16``, which
    every real run satisfies (a full-chunk run is ``(0, 0xFFFF)``)."""
    p = row.reshape(MAX_RUNS, 2).astype(jnp.int32)
    s, l = p[:, 0], p[:, 1]
    return s, l, (s + l) < CHUNK_SIZE


def row_nruns(row: jax.Array, kind: jax.Array) -> jax.Array:
    """Run count of a run row (0 for other kinds)."""
    _, _, valid = _row_run_parts(row)
    return jnp.where(kind == KIND_RUN, jnp.sum(valid.astype(jnp.int32)), 0)


def row_to_bits(row: jax.Array, card: jax.Array, kind: jax.Array) -> jax.Array:
    """Uniform bitmap-domain view of ONE container row (empty -> zeros), by
    the scatter lifts of ``dispatch.lift_rows``: arrays scatter their packed
    values, runs their coverage (both O(4096)), bitmaps pass through. Rows
    in bulk go through ``_lift_rows``."""
    as_bits = row_array_to_bits(row, card)
    lifted = jnp.where(kind == KIND_RUN, row_run_to_bits(row), as_bits)
    return jnp.where(kind == KIND_BITMAP, row, lifted) * (kind != KIND_EMPTY).astype(jnp.uint16)


def row_popcount(bits: jax.Array) -> jax.Array:
    """Container cardinality via popcnt (paper Alg. 1 line 7)."""
    return jnp.sum(lax_popcount(bits).astype(jnp.int32))


def lax_popcount(x: jax.Array) -> jax.Array:
    return jax.lax.population_count(x)


def row_bits_to_array(bits: jax.Array) -> jax.Array:
    """Vectorized Algorithm 2: bitmap row -> packed sorted u16 array row.

    Per-word popcounts -> exclusive cumsum gives each word's write offset;
    bit positions are scattered to offset + rank-within-word. O(2^16) fully
    data-parallel (the TPU replacement for the serial ``w & -w`` loop).
    """
    # bits: u16[4096] -> per-bit boolean [4096, 16]
    shifts = jnp.arange(16, dtype=jnp.uint16)
    bitmat = ((bits[:, None] >> shifts[None, :]) & jnp.uint16(1)).astype(jnp.int32)
    flat = bitmat.reshape(-1)                               # [65536] in value order
    pos = jnp.arange(CHUNK_SIZE, dtype=jnp.int32)
    rank = jnp.cumsum(flat) - flat                          # exclusive cumsum
    idx = jnp.where(flat == 1, rank, CHUNK_SIZE)            # OOB dropped
    out = jnp.zeros((ROW_WORDS,), jnp.uint16).at[idx].add(
        pos.astype(jnp.uint16), mode="drop")
    return out


def _row_canonicalize_2kind(bits: jax.Array):
    """PR 1's array/bitmap-only canonicalization — retained verbatim for the
    ``slab_*_bitmap_domain`` A/B baseline (the pre-run architecture)."""
    card = row_popcount(bits)
    as_array = row_bits_to_array(bits)
    as_array = jnp.where(jnp.arange(ROW_WORDS) < card, as_array,
                         jnp.uint16(0xFFFF))
    is_bitmap = card > ARRAY_MAX
    data = jnp.where(is_bitmap, bits, as_array)
    kind = jnp.where(card == 0, KIND_EMPTY,
                     jnp.where(is_bitmap, KIND_BITMAP, KIND_ARRAY))
    return data, card, kind


def _row_edges(bits: jax.Array):
    """(rising, falling') edge bitmaps of a bitmap row: rising marks run
    starts (set bit, previous clear), falling' the position *after* each run
    end (clear bit, previous set). Word-carry chained, O(4096)."""
    prev = jnp.concatenate([jnp.zeros((1,), jnp.uint16), bits[:-1]])
    shifted = (bits << 1) | (prev >> 15)
    rising = bits & ~shifted
    falling = ~bits & shifted
    return rising, falling


def row_nruns_bits(bits: jax.Array) -> jax.Array:
    """# maximal runs of a bitmap row = popcount of its rising edges."""
    rising, _ = _row_edges(bits)
    return row_popcount(rising)


def _row_runs_from_bits(bits: jax.Array) -> jax.Array:
    """Bitmap row -> packed run-pair row.

    One Algorithm-2 extraction over ``rising | falling'`` yields the sorted
    interleaved sequence ``s0, e0+1, s1, e1+1, ...`` directly (the two edge
    sets are disjoint); a run ending at 65535 has no falling' bit, so its
    implicit end is 2^16. O(2^16) — callers guard with ``lax.cond``.
    """
    rising, falling = _row_edges(bits)
    edges = rising | falling
    pos = row_bits_to_array(edges)
    n_edges = row_popcount(edges)
    nr = row_popcount(rising)
    k = jnp.arange(MAX_RUNS, dtype=jnp.int32)
    s = jnp.take(pos, 2 * k).astype(jnp.int32)
    e1 = jnp.where(2 * k + 1 < n_edges,
                   jnp.take(pos, jnp.minimum(2 * k + 1, ROW_WORDS - 1)).astype(jnp.int32),
                   CHUNK_SIZE)
    lm1 = e1 - 1 - s
    live = k < nr
    return jnp.stack(
        [jnp.where(live, s, 0xFFFF), jnp.where(live, lm1, 0xFFFF)],
        axis=1).reshape(ROW_WORDS).astype(jnp.uint16)


def row_canonicalize(bits: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """bitmap-domain row -> canonical (data, card, kind), best-of-three.

    The 2016 paper's ``runOptimize`` rule, applied per row: pick the kind
    whose serialized size is smallest — 2*card (array, card <= 4096), 8192
    (bitmap), 4*n_runs (run; strictly smaller only). Array rows are padded
    with 0xFFFF past ``card`` so the packed prefix plus padding stays
    globally sorted (binary-search friendly); run rows pad with the
    impossible pair (0xFFFF, 0xFFFF).
    """
    card = row_popcount(bits)
    nr = row_nruns_bits(bits)
    kind = _pick_kind(card, nr)
    as_array = row_bits_to_array(bits)
    as_array = jnp.where(jnp.arange(ROW_WORDS) < card, as_array,
                         jnp.uint16(0xFFFF))
    data = jnp.where(kind == KIND_BITMAP, bits,
                     jnp.where(kind == KIND_RUN, _row_runs_from_bits(bits),
                               as_array))
    return data, card, kind


def _pick_kind(card: jax.Array, nruns: jax.Array) -> jax.Array:
    """Strict best-of-three serialized-size rule (must match the oracle's
    ``py_roaring._canonical`` bit-for-bit): run iff 4*n_runs is strictly
    smaller than every alternative; array preferred at the 4096 tie."""
    other = jnp.where(card <= ARRAY_MAX,
                      jnp.minimum(2 * card, 2 * ARRAY_MAX), 2 * ARRAY_MAX)
    run_best = (4 * nruns < other) & (card > 0)
    return jnp.where(card == 0, KIND_EMPTY,
                     jnp.where(run_best, KIND_RUN,
                               jnp.where(card <= ARRAY_MAX, KIND_ARRAY,
                                         KIND_BITMAP)))


# =============================================================================
# construction / export
# =============================================================================

def from_indices(idx: jax.Array, valid: jax.Array, capacity: int) -> RoaringSlab:
    """Build a slab from (padded) *sorted unique* int32/int64 indices.

    ``idx``: i64/i32[M] sorted ascending with invalid entries at the end
    (``valid`` false). Elements sharing high 16 bits land in one container.
    Works with or without x64 (int32 universes cover every in-framework use:
    per-leaf gradient coordinates, block ids, page ids).
    """
    idt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    idx = idx.astype(idt)
    M = idx.shape[0]
    sentinel = jnp.asarray(int(KEY_SENTINEL), idt)
    hi = jnp.where(valid, idx >> CHUNK_BITS, sentinel)
    lo = (idx & (CHUNK_SIZE - 1)).astype(jnp.int32)

    first = jnp.concatenate([jnp.array([True]), hi[1:] != hi[:-1]]) & valid
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1           # container id per elem
    seg = jnp.where(valid, seg, capacity)                   # drop invalid
    counts = jnp.zeros((capacity,), jnp.int32).at[seg].add(1, mode="drop")

    # container keys: first element of each segment
    keys = jnp.full((capacity,), sentinel, dtype=idt)
    keys = keys.at[jnp.where(first, seg, capacity)].min(
        jnp.where(first, hi, sentinel), mode="drop")
    keys = jnp.where(counts > 0, keys, sentinel).astype(jnp.int32)

    # array representation: rank within segment
    seg_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    rank = jnp.arange(M, dtype=jnp.int32) - seg_start[jnp.minimum(seg, capacity - 1)]
    arr_data = jnp.zeros((capacity, ROW_WORDS), jnp.uint16)
    arr_data = arr_data.at[seg, jnp.where(valid, rank, ROW_WORDS)].add(
        lo.astype(jnp.uint16), mode="drop")

    # bitmap representation (scatter-add of distinct power-of-two bits)
    bit_data = jnp.zeros((capacity, ROW_WORDS), jnp.uint16)
    bit_data = bit_data.at[seg, jnp.where(valid, lo >> 4, ROW_WORDS)].add(
        jnp.where(valid, jnp.uint16(1) << (lo & 15).astype(jnp.uint16),
                  jnp.uint16(0)), mode="drop")

    is_bitmap = counts > ARRAY_MAX
    # pad array rows with 0xFFFF past card so binary search stays valid
    arr_data = jnp.where(jnp.arange(ROW_WORDS)[None, :] < counts[:, None],
                         arr_data, jnp.uint16(0xFFFF))
    data = jnp.where(is_bitmap[:, None], bit_data, arr_data)
    kind = jnp.where(counts == 0, KIND_EMPTY,
                     jnp.where(is_bitmap, KIND_BITMAP, KIND_ARRAY))
    return RoaringSlab(keys=keys, card=counts, kind=kind, data=data)


def from_dense_array(values: np.ndarray, capacity: int, max_elems: int) -> RoaringSlab:
    """Host-side convenience: numpy values -> slab (pads to max_elems)."""
    v = np.unique(np.asarray(values, dtype=np.int64))
    assert v.size <= max_elems, (v.size, max_elems)
    idx = np.full((max_elems,), 0, dtype=np.int64)
    idx[: v.size] = v
    valid = np.zeros((max_elems,), dtype=bool)
    valid[: v.size] = True
    # keep padded tail sorted-after-valid by setting it to the max value
    if v.size:
        idx[v.size:] = v[-1]
    return from_indices(jnp.asarray(idx), jnp.asarray(valid), capacity)


def from_roaring(rb, capacity: int) -> RoaringSlab:
    """Host-side bridge: a ``py_roaring.RoaringBitmap`` -> RoaringSlab with
    the container kinds preserved exactly — run containers land as run rows
    with no per-element or bitmap materialization (the run-shaped consumers'
    constructor: KV free/used pools, window/causal/doc mask rows)."""
    from repro.core import py_roaring as pr

    assert len(rb.keys) <= capacity, (len(rb.keys), capacity)
    keys = np.full((capacity,), int(KEY_SENTINEL), np.int32)
    card = np.zeros((capacity,), np.int32)
    kind = np.zeros((capacity,), np.int32)
    data = np.zeros((capacity, ROW_WORDS), np.uint16)
    for i, (k, c) in enumerate(zip(rb.keys, rb.containers)):
        keys[i] = k
        card[i] = c.cardinality
        if isinstance(c, pr.RunContainer):
            kind[i] = KIND_RUN
            row = np.full((ROW_WORDS,), 0xFFFF, np.uint16)
            row[0:2 * c.n_runs:2] = c.starts.astype(np.uint16)
            row[1:2 * c.n_runs:2] = c.lengths.astype(np.uint16)
            data[i] = row
        elif isinstance(c, pr.BitmapContainer):
            kind[i] = KIND_BITMAP
            data[i] = c.words.view(np.uint16)        # little-endian u64 -> u16
        else:
            kind[i] = KIND_ARRAY
            row = np.full((ROW_WORDS,), 0xFFFF, np.uint16)
            row[: c.arr.size] = c.arr
            data[i] = row
    return RoaringSlab(keys=jnp.asarray(keys), card=jnp.asarray(card),
                       kind=jnp.asarray(kind), data=jnp.asarray(data))


def from_ranges(ranges, capacity: int) -> RoaringSlab:
    """Host-side run-row constructor from half-open ``[start, end)`` integer
    ranges — builds run containers directly (no element materialization)."""
    from repro.core import py_roaring as pr

    return from_roaring(pr.RoaringBitmap.from_ranges(ranges), capacity)


def to_roaring(slab: RoaringSlab):
    """Host-side reverse bridge: RoaringSlab -> ``py_roaring.RoaringBitmap``,
    kind-preserving (the exact inverse of ``from_roaring``).

    Array rows become ``ArrayContainer`` (the packed ``card`` prefix), bitmap
    rows become ``BitmapContainer`` (u16 words reassembled to little-endian
    u64), run rows become ``RunContainer`` (the valid ``(start, len-1)``
    pairs). A canonical slab — any set-algebra or engine output — therefore
    round-trips bit-identically: same keys, same container kinds, same
    payloads.
    """
    from repro.core import py_roaring as pr

    keys = np.asarray(slab.keys)
    card = np.asarray(slab.card)
    kind = np.asarray(slab.kind)
    data = np.asarray(slab.data)
    rb = pr.RoaringBitmap()
    for i in range(keys.shape[0]):
        if kind[i] == KIND_EMPTY:
            continue
        if kind[i] == KIND_ARRAY:
            c = pr.ArrayContainer(data[i, : card[i]])
        elif kind[i] == KIND_BITMAP:
            c = pr.BitmapContainer(np.ascontiguousarray(data[i]).view(
                np.uint64), cardinality=int(card[i]))
        else:
            p = data[i].reshape(MAX_RUNS, 2).astype(np.int64)
            valid = (p[:, 0] + p[:, 1]) < CHUNK_SIZE
            c = pr.RunContainer(p[valid, 0], p[valid, 1])
        rb.keys.append(int(keys[i]))
        rb.containers.append(c)
    return rb


def slab_run_optimize(slab: RoaringSlab) -> RoaringSlab:
    """Device-side ``runOptimize``: re-canonicalize every row best-of-three
    through the engine (array rows runify via the O(4096) adjacency scatter,
    bitmap rows via the cond-guarded edge extraction)."""
    return _finalize_rows(slab.keys, slab.data, slab.card, slab.kind)


def to_indices(slab: RoaringSlab, max_out: int) -> tuple[jax.Array, jax.Array]:
    """Slab -> (sorted values int[max_out], valid bool[max_out]).

    Uniform path: every row is viewed in bitmap domain, all C*2^16 candidate
    bits are compacted by exclusive cumsum (global Algorithm 2).
    """
    idt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    bits = jax.vmap(row_to_bits)(slab.data, slab.card, slab.kind)   # u16[C,4096]
    shifts = jnp.arange(16, dtype=jnp.uint16)
    bitmat = ((bits[:, :, None] >> shifts[None, None, :]) & jnp.uint16(1))
    flat = bitmat.reshape(-1).astype(jnp.int32)             # [C*65536]
    # sentinel keys may wrap when shifted in int32 — harmless: their rows have
    # flat == 0 everywhere, so the wrapped values are multiplied away.
    base = (slab.keys.astype(idt) << CHUNK_BITS)
    vals = (base[:, None] + jnp.arange(CHUNK_SIZE, dtype=idt)[None, :]).reshape(-1)
    rank = jnp.cumsum(flat) - flat
    tgt = jnp.where(flat == 1, rank, max_out)
    out = jnp.zeros((max_out,), idt).at[tgt].add(vals * flat, mode="drop")
    total = jnp.sum(flat)
    valid = jnp.arange(max_out) < total
    return jnp.where(valid, out, 0), valid


def extract_row(slab: RoaringSlab, r, max_out: int = ARRAY_MAX):
    """Packed sorted values of container ``r`` (Alg. 2 on one row)."""
    bits = row_to_bits(slab.data[r], slab.card[r], slab.kind[r])
    arr = row_bits_to_array(bits)
    valid = jnp.arange(ROW_WORDS) < slab.card[r]
    return arr[:max_out], valid[:max_out]


# =============================================================================
# membership / rank
# =============================================================================

def contains(slab: RoaringSlab, queries: jax.Array) -> jax.Array:
    """Batched membership test (paper S3): first-level binary search, then
    array binary search or bitmap bit probe, selected by container kind.

    Bandwidth-lean: the bitmap path gathers only the one probed 16-bit word
    and the array path gathers one element per halving step (13 for a
    4096-wide window), instead of pulling the full 8 kB row per query into
    the vmap.
    """
    q = queries.astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32)
    hi = (q >> CHUNK_BITS).astype(jnp.int32)
    lo = (q & (CHUNK_SIZE - 1)).astype(jnp.int32)
    row = jnp.searchsorted(slab.keys, hi)
    row_c = jnp.minimum(row, slab.capacity - 1)
    key_hit = slab.keys[row_c] == hi

    def one(row_i, lo_i):
        card = slab.card[row_i]
        kind = slab.kind[row_i]
        # bitmap path: probe a single word
        word = slab.data[row_i, lo_i >> 4].astype(jnp.int32)
        bit_hit = ((word >> (lo_i & 15)) & 1) == 1
        # array path: binary search over the packed prefix, one gathered
        # element per step (log-bounded traffic; 0xFFFF padding keeps the
        # row globally sorted so the [0, card) window is safe). 13 steps:
        # lower_bound must shrink a window of up to 4096 to size 0, which
        # takes ceil(log2(4096)) + 1 halvings.
        def body(_, lh):
            l, h = lh
            mid = (l + h) // 2
            v = slab.data[row_i, jnp.clip(mid, 0, ROW_WORDS - 1)].astype(
                jnp.int32)
            go_right = v < lo_i
            return (jnp.where(go_right, mid + 1, l),
                    jnp.where(go_right, h, mid))

        # carries derived from the probe and the slab, so they vary with
        # both under shard_map
        zero = jnp.zeros_like(lo_i + card)
        l, _ = jax.lax.fori_loop(0, 13, body, (zero, zero + card))
        probe = slab.data[row_i, jnp.clip(l, 0, ROW_WORDS - 1)].astype(
            jnp.int32)
        arr_hit = (l < card) & (probe == lo_i)
        # run path: binary search the <=2048 pair slots, two gathered u16s
        # per step. The comparator maps the (0xFFFF, 0xFFFF) padding past
        # the probe, so no run count is needed. 12 steps cover 2048 runs.
        # Deliberately not dispatch._run_covered — that searches a row tile
        # already resident (a full 8 kB gather here); this gathers only two
        # probed u16s per step, keeping membership log-bounded traffic.
        # Keep the window-guard/padding semantics in sync with
        # dispatch._run_upper_bound.
        def rbody(_, lh):
            l, h = lh
            open_ = l < h
            mid = (l + h) // 2
            mid_c = jnp.clip(2 * mid, 0, ROW_WORDS - 2)
            s = slab.data[row_i, mid_c].astype(jnp.int32)
            ln = slab.data[row_i, mid_c + 1].astype(jnp.int32)
            key = jnp.where(s + ln < CHUNK_SIZE, s, CHUNK_SIZE)
            go_right = open_ & (key <= lo_i)
            return (jnp.where(go_right, mid + 1, l),
                    jnp.where(open_ & ~go_right, mid, h))

        rl, _ = jax.lax.fori_loop(0, 12, rbody, (zero, zero + MAX_RUNS))
        ri = jnp.clip(rl - 1, 0, MAX_RUNS - 1)
        rs = slab.data[row_i, 2 * ri].astype(jnp.int32)
        rln = slab.data[row_i, 2 * ri + 1].astype(jnp.int32)
        run_hit = (rl > 0) & (rs + rln < CHUNK_SIZE) & (lo_i <= rs + rln)
        return jnp.where(kind == KIND_BITMAP, bit_hit,
                         jnp.where(kind == KIND_ARRAY, arr_hit,
                                   jnp.where(kind == KIND_RUN, run_hit,
                                             False)))

    hits = jax.vmap(one)(row_c, lo)
    return hits & key_hit


def rank(slab: RoaringSlab, x: jax.Array) -> jax.Array:
    """# elements <= x: whole-container counters + one partial container."""
    x = x.astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32)
    hi = (x >> CHUNK_BITS).astype(jnp.int32)
    lo = (x & (CHUNK_SIZE - 1)).astype(jnp.int32)
    full = jnp.sum(jnp.where(slab.keys < hi, slab.card, 0))
    row = jnp.searchsorted(slab.keys, hi)
    row_c = jnp.minimum(row, slab.capacity - 1)
    hit = slab.keys[row_c] == hi
    bits = row_to_bits(slab.data[row_c], slab.card[row_c], slab.kind[row_c])
    word_idx = lo >> 4
    mask_full = (jnp.arange(ROW_WORDS) < word_idx)
    partial_words = jnp.sum(lax_popcount(jnp.where(mask_full, bits, 0)).astype(jnp.int32))
    last = bits[word_idx] & ((jnp.uint16(2) << (lo & 15).astype(jnp.uint16)) - 1).astype(jnp.uint16)
    in_row = partial_words + lax_popcount(last).astype(jnp.int32)
    return full + jnp.where(hit, in_row, 0)


def slab_select(slab: RoaringSlab, j: jax.Array) -> jax.Array:
    """Value of the j-th (0-based) smallest element — the slab counterpart
    of the oracle's ``select`` (paper S2 access operation, rank's inverse).

    First-level: binary search the per-container cardinality prefix sums;
    within the container, dispatch by kind — direct gather for arrays, a
    run-length prefix-sum search for run rows (log-bounded traffic, like
    ``contains``), and a one-row bit-rank cumsum for bitmaps. Returns -1 for
    out-of-range ``j``.
    """
    idt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    j = jnp.asarray(j, jnp.int32)
    csum = jnp.cumsum(slab.card)
    total = csum[-1] if slab.capacity else jnp.int32(0)
    row = jnp.searchsorted(csum, j, side="right")
    row_c = jnp.minimum(row, slab.capacity - 1)
    j_in = j - jnp.where(row_c > 0, csum[row_c - 1], 0)
    kind = slab.kind[row_c]
    drow = slab.data[row_c]

    # array: direct gather
    arr_val = drow[jnp.clip(j_in, 0, ROW_WORDS - 1)].astype(jnp.int32)
    # run: search the run-length prefix sums
    s, l, valid = _row_run_parts(drow)
    lens = jnp.where(valid, l + 1, 0)
    lcum = jnp.cumsum(lens)
    r = jnp.searchsorted(lcum, j_in, side="right")
    r_c = jnp.minimum(r, MAX_RUNS - 1)
    run_val = s[r_c] + j_in - (lcum[r_c] - lens[r_c])
    # bitmap: j_in-th set bit via bit-rank cumsum — the one O(2^16) pass,
    # cond-guarded so run/array selects keep their log bound
    def bit_rank(args):
        bits, j_in = args
        shifts = jnp.arange(16, dtype=jnp.uint16)
        flat = ((bits[:, None] >> shifts[None, :]) & jnp.uint16(1)).astype(
            jnp.int32).reshape(-1)
        return jnp.searchsorted(jnp.cumsum(flat), j_in + 1,
                                side="left").astype(jnp.int32)

    bit_pos = jax.lax.cond(kind == KIND_BITMAP, bit_rank,
                           lambda args: jnp.int32(0), (drow, j_in))
    lo_val = jnp.where(kind == KIND_ARRAY, arr_val,
                       jnp.where(kind == KIND_RUN, run_val,
                                 bit_pos.astype(jnp.int32)))
    val = (slab.keys[row_c].astype(idt) << CHUNK_BITS) + lo_val.astype(idt)
    ok = (j >= 0) & (j < total)
    return jnp.where(ok, val, -1)


# =============================================================================
# set algebra: hybrid per-kind dispatch (paper S4)
#
# Key-aligned container pairs are classified by (kind_a, kind_b) and routed
# through the matching algorithm via repro.kernels.roaring (Pallas word-op
# kernel on TPU, XLA reference elsewhere). Canonicalization is lazy: only
# bitmap-domain output rows that land back under the 4096 threshold pay the
# O(2^16) extraction, and the pass is lax.cond-guarded so it is skipped at
# runtime when no row needs it. The pre-dispatch bitmap-domain formulation is
# kept below as slab_*_bitmap_domain for A/B benchmarking and cross-checks.
# =============================================================================

def _pad_keys(keys: jax.Array, capacity: int) -> jax.Array:
    n = keys.shape[0]
    if capacity <= n:
        return keys[:capacity]
    return jnp.concatenate(
        [keys, jnp.full((capacity - n,), KEY_SENTINEL, jnp.int32)])


def _merge_keys_many(key_cols: list[jax.Array], capacity: int) -> jax.Array:
    """Union of N sorted key columns, deduplicated (duplicates demoted to
    ``KEY_SENTINEL`` and re-sorted), padded/truncated to ``capacity`` — the
    single key-alignment idiom shared by the pairwise ops, the tree union,
    and ``index.stack_from_slabs``."""
    srt = jnp.sort(jnp.concatenate(key_cols))
    dup = jnp.concatenate([jnp.array([False]), srt[1:] == srt[:-1]])
    return _pad_keys(jnp.sort(jnp.where(dup, KEY_SENTINEL, srt)), capacity)


def _merge_keys(a: RoaringSlab, b: RoaringSlab, capacity: int) -> jax.Array:
    """Union of the two sorted key sets, deduplicated, padded with sentinel."""
    return _merge_keys_many([a.keys, b.keys], capacity)


def _intersect_keys(a: RoaringSlab, b: RoaringSlab, capacity: int) -> jax.Array:
    """Keys present in *both* slabs (the only rows an AND can populate), so
    the dispatch grid is |A.keys ∩ B.keys| rows instead of the union."""
    pos = jnp.searchsorted(b.keys, a.keys)
    pos_c = jnp.minimum(pos, b.capacity - 1)
    hit = (b.keys[pos_c] == a.keys) & (a.keys != KEY_SENTINEL)
    vals = jnp.sort(jnp.where(hit, a.keys, KEY_SENTINEL))
    return _pad_keys(vals, capacity)


def _gather_raw(s: RoaringSlab, keys: jax.Array):
    """Raw rows of ``s`` aligned to ``keys`` — native container form, no
    bitmap-domain lift. Absent keys get (card=0, kind=EMPTY)."""
    pos = jnp.searchsorted(s.keys, keys)
    pos_c = jnp.minimum(pos, s.capacity - 1)
    present = (s.keys[pos_c] == keys) & (keys != KEY_SENTINEL)
    data = s.data[pos_c]
    card = jnp.where(present, s.card[pos_c], 0)
    kind = jnp.where(present, s.kind[pos_c], KIND_EMPTY)
    return data, card, kind


def _compact_row(vals: jax.Array, hit: jax.Array) -> jax.Array:
    """Scatter the hit subset of a packed row into a fresh packed sorted row
    (0xFFFF padded). O(4096), never touches the 2^16-element domain."""
    h = hit.astype(jnp.int32)
    rank = jnp.cumsum(h) - h
    idx = jnp.where(hit, rank, ROW_WORDS)
    return jnp.full((ROW_WORDS,), 0xFFFF, jnp.uint16).at[idx].set(
        vals, mode="drop")


def _rows_bits_to_array_lazy(bits: jax.Array, need: jax.Array,
                             card: jax.Array) -> jax.Array:
    """Lazy Algorithm 2 over rows: the O(2^16) extraction runs only when at
    least one row actually crosses back under the 4096 threshold; otherwise
    lax.cond skips the whole pass at runtime."""
    masked = jnp.where(need[:, None], bits, jnp.uint16(0))
    arrs = jax.lax.cond(
        jnp.any(need),
        lambda m: jax.vmap(row_bits_to_array)(m),
        lambda m: jnp.zeros_like(m),
        masked)
    return jnp.where(jnp.arange(ROW_WORDS)[None, :] < card[:, None],
                     arrs, jnp.uint16(0xFFFF))


def _rows_nruns(data: jax.Array, kind: jax.Array) -> jax.Array:
    """Batched ``row_nruns``: per-row run counts (0 for non-run rows)."""
    p = data.reshape(data.shape[0], MAX_RUNS, 2).astype(jnp.int32)
    valid = (p[..., 0] + p[..., 1]) < CHUNK_SIZE
    return jnp.where(kind == KIND_RUN, jnp.sum(valid.astype(jnp.int32), -1), 0)


def _runs_from_array_rows(vals: jax.Array, card: jax.Array):
    """Packed sorted array rows -> packed run-pair rows + run counts.

    Adjacency-difference run detection + two O(4096) scatters per row —
    never the 2^16 domain.
    """
    C = vals.shape[0]
    v = vals.astype(jnp.int32)
    slot = jnp.arange(ROW_WORDS, dtype=jnp.int32)[None, :]
    valid = slot < card[:, None]
    prev = jnp.concatenate([jnp.full((C, 1), -2, jnp.int32), v[:, :-1]], 1)
    nxt = jnp.concatenate([v[:, 1:], jnp.full((C, 1), -2, jnp.int32)], 1)
    isstart = valid & (v != prev + 1)
    isend = valid & ((slot + 1 >= card[:, None]) | (nxt != v + 1))
    rid = jnp.cumsum(isstart.astype(jnp.int32), axis=1) - 1
    rows = jnp.arange(C)[:, None]
    starts = jnp.zeros((C, MAX_RUNS), jnp.int32).at[
        rows, jnp.where(isstart, rid, MAX_RUNS)].add(v, mode="drop")
    pairs = jnp.full((C, ROW_WORDS), 0xFFFF, jnp.uint16)
    pairs = pairs.at[rows, jnp.where(isstart, 2 * rid, ROW_WORDS)].set(
        v.astype(jnp.uint16), mode="drop")
    lm1 = v - jnp.take_along_axis(starts, jnp.clip(rid, 0, MAX_RUNS - 1),
                                  axis=1)
    pairs = pairs.at[rows, jnp.where(isend, 2 * rid + 1, ROW_WORDS)].set(
        lm1.astype(jnp.uint16), mode="drop")
    return pairs, jnp.sum(isstart.astype(jnp.int32), axis=1)


def _arrays_from_runs_rows(pairs: jax.Array, card: jax.Array) -> jax.Array:
    """Packed run-pair rows -> packed sorted array rows (gather-only:
    per-slot binary search of the run-length prefix sums)."""
    C = pairs.shape[0]
    p = pairs.reshape(C, MAX_RUNS, 2).astype(jnp.int32)
    s, l = p[..., 0], p[..., 1]
    valid = (s + l) < CHUNK_SIZE
    lens = jnp.where(valid, l + 1, 0)
    cum = jnp.cumsum(lens, axis=1)
    k = jnp.arange(ROW_WORDS, dtype=jnp.int32)

    def one(cum_r, s_r, lens_r, card_r):
        r = jnp.searchsorted(cum_r, k, side="right")
        r_c = jnp.minimum(r, MAX_RUNS - 1)
        base = cum_r[r_c] - lens_r[r_c]
        val = s_r[r_c] + k - base
        return jnp.where(k < card_r, val, 0xFFFF).astype(jnp.uint16)

    return jax.vmap(one)(cum, s, lens, card)


def _runs_from_bits_rows_lazy(bits: jax.Array, need: jax.Array) -> jax.Array:
    """Lazy batched run extraction from bitmap rows: the O(2^16) edge pass
    runs only when some row's canonical kind is actually run."""
    masked = jnp.where(need[:, None], bits, jnp.uint16(0))
    return jax.lax.cond(
        jnp.any(need),
        lambda m: jax.vmap(_row_runs_from_bits)(m),
        lambda m: jnp.full_like(m, 0xFFFF),
        masked)


def _run_merge_row(da: jax.Array, db: jax.Array):
    """run x run intersection *in run domain* (the run-merge row kernel).

    Every output run closes at an input run end covered by the other side,
    so the <= na+nb output runs are enumerated by two lane-parallel searches
    (one per input end), deduped by a strict tie-break, and compacted with a
    single argsort — O(4096 log 4096), never the 2^16 domain. Returns
    (pairs_row, card, n_out); if ``n_out`` exceeds the 2048-pair row
    capacity (pathological alternating micro-runs) the caller falls back to
    the coverage-bits form.
    """
    sa, la, va = _row_run_parts(da)
    ea = sa + la
    sb, lb, vb = _row_run_parts(db)
    eb = sb + lb
    BIG = jnp.int32(1 << 17)
    sa_p = jnp.where(va, sa, BIG)
    sb_p = jnp.where(vb, sb, BIG)

    # candidates closing at a-ends: the b-run containing ea (ties included)
    j = jnp.searchsorted(sb_p, ea, side="right") - 1
    jc = jnp.clip(j, 0, MAX_RUNS - 1)
    av = va & (j >= 0) & (eb[jc] >= ea)
    a_start = jnp.maximum(sa, sb[jc])
    # candidates closing strictly inside a-runs at b-ends (tie-deduped)
    i = jnp.searchsorted(sa_p, eb, side="right") - 1
    ic = jnp.clip(i, 0, MAX_RUNS - 1)
    bv = vb & (i >= 0) & (ea[ic] > eb)
    b_start = jnp.maximum(sb, sa[ic])

    starts = jnp.concatenate([jnp.where(av, a_start, BIG),
                              jnp.where(bv, b_start, BIG)])
    ends = jnp.concatenate([jnp.where(av, ea, 0), jnp.where(bv, eb, 0)])
    card = (jnp.sum(jnp.where(av, ea - a_start + 1, 0))
            + jnp.sum(jnp.where(bv, eb - b_start + 1, 0)))
    n_out = jnp.sum(av.astype(jnp.int32)) + jnp.sum(bv.astype(jnp.int32))
    order = jnp.argsort(starts)
    ss = starts[order][:MAX_RUNS]
    ee = ends[order][:MAX_RUNS]
    live = jnp.arange(MAX_RUNS) < n_out
    pairs = jnp.stack([jnp.where(live, ss, 0xFFFF),
                       jnp.where(live, ee - ss, 0xFFFF)],
                      axis=1).reshape(ROW_WORDS).astype(jnp.uint16)
    return pairs, card, n_out


def _run_merge_rows_lazy(da, db, rr):
    """Cond-guarded batched run-merge over the rows classified run x run.
    Returns (pairs, card, n_out, bits_fallback) — the coverage-bits fallback
    is itself guarded and only materializes for overflowing rows."""

    def merge(args):
        da, db = args
        m = rr[:, None]
        pairs, card, n_out = jax.vmap(_run_merge_row)(
            jnp.where(m, da, jnp.uint16(0xFFFF)),
            jnp.where(m, db, jnp.uint16(0xFFFF)))
        overflow = rr & (n_out > MAX_RUNS)

        def cov(args):
            da, db = args
            o = overflow[:, None]
            return jax.vmap(lambda x, y: row_run_to_bits(x) & row_run_to_bits(y))(
                jnp.where(o, da, jnp.uint16(0xFFFF)),
                jnp.where(o, db, jnp.uint16(0xFFFF)))

        bits = jax.lax.cond(jnp.any(overflow), cov,
                            lambda args: jnp.zeros_like(args[0]), (da, db))
        return pairs, card, n_out, bits

    def skip(args):
        da, _ = args
        zero = jnp.zeros_like(da[:, 0], dtype=jnp.int32)
        return (jnp.full_like(da, 0xFFFF), zero, zero, jnp.zeros_like(da))

    return jax.lax.cond(jnp.any(rr), merge, skip, (da, db))


def _finalize(keys, card, form, arr_rows, bits_rows, runs_rows, runs_nr):
    """The engine's canonicalization + assembly stage.

    Each computed row arrives in one of three *forms* (packed array /
    bitmap-domain words / packed run pairs); best-of-three picks the
    canonical kind per row and the required conversions run vectorized —
    cheap O(4096) passes unguarded, the two O(2^16) extractions
    (bits -> array, bits -> runs) ``lax.cond``-guarded. Dead rows are keyed
    out and rows re-sorted so live keys lead.
    """
    is_af = form == FORM_ARRAY
    is_bf = form == FORM_BITS
    is_rf = form == FORM_RUNS
    live_af = is_af & (card > 0)
    pairs_from_arr, nr_arr = jax.lax.cond(
        jnp.any(live_af),
        lambda a: _runs_from_array_rows(a, jnp.where(live_af, card, 0)),
        lambda a: (jnp.full_like(a, 0xFFFF), jnp.zeros_like(card)),
        arr_rows)
    bits_m = jnp.where(is_bf[:, None], bits_rows, jnp.uint16(0))
    nr_bits = jax.lax.cond(
        jnp.any(is_bf),
        lambda b: jax.vmap(row_nruns_bits)(b),
        lambda b: jnp.zeros_like(card), bits_m)
    nr = jnp.where(is_af, nr_arr, jnp.where(is_bf, nr_bits, runs_nr))
    kind = _pick_kind(card, nr)

    need_arr_bits = is_bf & (kind == KIND_ARRAY)
    arr_from_bits = _rows_bits_to_array_lazy(bits_rows, need_arr_bits, card)
    need_run_bits = is_bf & (kind == KIND_RUN)
    runs_from_bits = _runs_from_bits_rows_lazy(bits_rows, need_run_bits)
    need_arr_runs = is_rf & (kind == KIND_ARRAY)
    arr_from_runs = jax.lax.cond(
        jnp.any(need_arr_runs),
        lambda r: _arrays_from_runs_rows(r, jnp.where(need_arr_runs, card, 0)),
        lambda r: jnp.full_like(r, 0xFFFF), runs_rows)
    # a run-form row canonicalizes to bitmap only at the 4*nr == 8192 tie
    # (nr == 2048 with card > 4096), but the coverage lift must exist or the
    # bitmap branch below would read the caller's placeholder bits
    need_bits_runs = is_rf & (kind == KIND_BITMAP)
    bits_from_runs = jax.lax.cond(
        jnp.any(need_bits_runs),
        lambda r: jax.vmap(row_run_to_bits)(
            jnp.where(need_bits_runs[:, None], r, jnp.uint16(0xFFFF))),
        lambda r: jnp.zeros_like(r), runs_rows)

    arr_final = jnp.where(is_bf[:, None], arr_from_bits,
                          jnp.where(is_rf[:, None], arr_from_runs, arr_rows))
    run_final = jnp.where(is_af[:, None], pairs_from_arr,
                          jnp.where(is_bf[:, None], runs_from_bits, runs_rows))
    bits_final = jnp.where(is_rf[:, None], bits_from_runs, bits_rows)
    data = jnp.where((kind == KIND_BITMAP)[:, None], bits_final,
                     jnp.where((kind == KIND_RUN)[:, None], run_final,
                               arr_final))
    live = kind != KIND_EMPTY
    out_keys = jnp.where(live, keys, KEY_SENTINEL)
    order = jnp.argsort(out_keys)
    return RoaringSlab(keys=out_keys[order],
                       card=jnp.where(live, card, 0)[order],
                       kind=kind[order], data=data[order])


def _dispatch_meta(ka, kb, ca, cb, ra=None, rb=None) -> jax.Array:
    """Interleave (kind_a, kind_b, card_a, card_b, nruns_a, nruns_b) per row
    -> i32[6C] (the registry's dispatch meta contract)."""
    if ra is None:
        ra = jnp.zeros_like(ka)
    if rb is None:
        rb = jnp.zeros_like(kb)
    return jnp.stack([ka, kb, ca, cb, ra, rb], axis=1).reshape(-1).astype(
        jnp.int32)


# =============================================================================
# row-state algebra: deferred-canonicalization combines shared by the pairwise
# slab ops, the log-depth tree reduction, and the repro.index query engine.
#
# A *row state* is the triple (data u16[M, 4096], card i32[M], kind i32[M]) of
# key-aligned container rows — a RoaringSlab minus its keys, and minus the
# canonical-kind guarantee: intermediate states defer best-of-three
# (runOptimize) until a single `_finalize_rows` at the root of a combine tree,
# so an N-way reduction pays one canonicalization pass, not N-1.
# =============================================================================

def _finalize_rows(keys, data, card, kind) -> RoaringSlab:
    """Row state -> canonical RoaringSlab (single deferred best-of-three).

    Maps each row's kind tag to the engine form it is stored in (array rows
    are packed prefixes, bitmap rows word rows, run rows packed pairs) and
    runs ``_finalize``: cheap O(4096) conversions unguarded, the O(2^16)
    bits->array / bits->runs extractions ``lax.cond``-guarded, dead rows
    keyed out and re-sorted.
    """
    form = jnp.where(kind == KIND_BITMAP, FORM_BITS,
                     jnp.where(kind == KIND_RUN, FORM_RUNS, FORM_ARRAY))
    nr = _rows_nruns(data, kind)
    return _finalize(keys, card, form, data, data, data, nr)


def _or_rows(da, ca, ka, db, cb, kb, *, word_op=jnp.bitwise_or,
             xor: bool = False, defer_card: bool = False):
    """One OR/XOR combine step over key-aligned row pairs -> row state.

    Routed by the registry's union policy (``dispatch.union_route``): array
    pairs whose merged size provably stays under the 4096 threshold merge in
    array domain (sorted merge, O(8192 log)); every other live pair goes
    through the bitmap domain with the kind-dispatching lift — array rows
    scatter, run rows range-mask coverage, both O(4096) — and a fused
    popcount. Both passes are ``lax.cond``-guarded. Output kinds are the
    *deferred* {EMPTY, ARRAY, BITMAP}; no canonicalization happens here.

    ``defer_card=True`` is Algorithm 4's deferred-cardinality discipline for
    OR reduction trees: bitmap-path rows get the ``CHUNK_SIZE`` upper bound
    instead of a popcount. Sound mid-tree because no consumer reads a
    BITMAP row's card before the root — the union routing policy only
    inspects cards of array-ish rows (whose merge cards stay exact) — and
    the root recounts via ``_recount_bitmap_rows`` before finalization.
    """

    def merge_pass(args):
        da, ca, db, cb = args
        return jax.vmap(
            functools.partial(_row_merge_sparse, xor=xor))(da, ca, db, cb)

    def merge_skip(args):
        da, ca, _, _ = args
        return jnp.full_like(da, 0xFFFF), jnp.zeros_like(ca)

    def bitmap_pass(args):
        da, ca, ka, db, cb, kb = args
        out = word_op(_lift_rows(da, ca, ka), _lift_rows(db, cb, kb))
        if defer_card:
            return out, jnp.full_like(ca, CHUNK_SIZE)
        return out, jax.vmap(row_popcount)(out)

    def bitmap_skip(args):
        da, ca = args[0], args[1]
        return jnp.zeros_like(da), jnp.zeros_like(ca)

    small, use_bitmap = _D.union_route(ka, kb, ca, cb, ARRAY_MAX)
    merge_rows, merge_card = jax.lax.cond(jnp.any(small), merge_pass,
                                          merge_skip, (da, ca, db, cb))
    bits, bcard = jax.lax.cond(jnp.any(use_bitmap), bitmap_pass, bitmap_skip,
                               (da, ca, ka, db, cb, kb))
    card = jnp.where(use_bitmap, bcard, merge_card)
    data = jnp.where(use_bitmap[:, None], bits, merge_rows)
    kind = jnp.where(card == 0, KIND_EMPTY,
                     jnp.where(use_bitmap, KIND_BITMAP, KIND_ARRAY))
    return data, card, kind


_or_rows_deferred = functools.partial(_or_rows, defer_card=True)


def _recount_bitmap_rows(data, card, kind):
    """Exact cards for word rows at the root of a deferred-cardinality OR
    tree: ONE cond-guarded popcount pass (Algorithm 4 line 16's 'recount
    once at the end'), replacing the ``CHUNK_SIZE`` placeholders that
    ``_or_rows(defer_card=True)`` leaves on bitmap-path rows."""
    is_b = kind == KIND_BITMAP
    masked = jnp.where(is_b[:, None], data, jnp.uint16(0))
    cnt = jax.lax.cond(
        jnp.any(is_b),
        lambda m: jax.vmap(row_popcount)(m),
        lambda m: jnp.zeros_like(card), masked)
    return jnp.where(is_b, cnt, card)


def _and_rows(da, ca, ka, db, cb, kb):
    """One AND combine step over key-aligned row pairs -> row state.

    The full 4x4 kind-dispatch grid through ``intersect_dispatch`` (Pallas on
    TPU, XLA reference elsewhere): mask-semantic cells compact the hit mask
    against the array side (output provably <= min(card) <= 4096, stays
    packed); bits-semantic cells — including run x run, which the kernel
    computes as the coverage AND — stay word rows with the fused-popcount
    cardinality. Deferred kinds {EMPTY, ARRAY, BITMAP}; the run-domain
    run-merge is a ``slab_and``-only specialization.
    """
    from repro.kernels.roaring import ops as _kops
    ra = _rows_nruns(da, ka)
    rb = _rows_nruns(db, kb)
    meta = _dispatch_meta(ka, kb, ca, cb, ra, rb)
    hits, card = _kops.intersect_dispatch(da, db, meta)
    bits_m = _D.out_mask("bits", ka, kb) | _D.route_mask("run_merge", ka, kb)
    src = jnp.where(_D.out_mask("mask_b", ka, kb)[:, None], db, da)
    arr_rows = jax.vmap(_compact_row)(src, (hits == 1) & ~bits_m[:, None])
    data = jnp.where(bits_m[:, None], hits, arr_rows)
    kind = jnp.where(card == 0, KIND_EMPTY,
                     jnp.where(bits_m, KIND_BITMAP, KIND_ARRAY))
    return data, card, kind


def _andnot_rows(da, ca, ka, db, cb, kb):
    """One ANDNOT combine step (A \\ B per row pair) -> row state.

    Registry ``andnot_route``: array-A rows probe B in place whatever B's
    kind (binary search / bit probe / gallop-in-ranges — result provably
    <= card_a <= 4096, stays packed); bitmap- and run-A rows take the
    ``lax.cond``-guarded bitmap-domain pass with the cheap run lift.
    """
    slot = jnp.arange(ROW_WORDS, dtype=jnp.int32)
    probe_a, lift_a = _D.andnot_route(ka, kb)

    def probe_row(dav, cav, dbv, cbv, kbv, rbv):
        pos = jnp.searchsorted(dbv, dav)
        pos_c = jnp.clip(pos, 0, ROW_WORDS - 1)
        arr_in = (dbv[pos_c] == dav) & (pos < cbv)
        v = dav.astype(jnp.int32)
        word = dbv[v >> 4].astype(jnp.int32)
        bit_in = ((word >> (v & 15)) & 1) == 1
        run_in = _D._run_covered(dbv.reshape(_D.ROW_SHAPE), rbv,
                                 v.reshape(_D.ROW_SHAPE)).reshape(ROW_WORDS)
        in_b = jnp.where(kbv == KIND_BITMAP, bit_in,
                         jnp.where(kbv == KIND_ARRAY, arr_in,
                                   jnp.where(kbv == KIND_RUN, run_in, False)))
        return (slot < cav) & ~in_b

    def bitmap_pass(args):
        da, ca, ka, db, cb, kb = args
        out = jnp.bitwise_and(_lift_rows(da, ca, ka),
                              ~_lift_rows(db, cb, kb))
        return out, jax.vmap(row_popcount)(out)

    def bitmap_skip(args):
        da, ca = args[0], args[1]
        return jnp.zeros_like(da), jnp.zeros_like(ca)

    rb = _rows_nruns(db, kb)
    keep = jax.vmap(probe_row)(da, ca, db, cb, kb, rb) & probe_a[:, None]
    arr_rows = jax.vmap(_compact_row)(da, keep)
    acard = jnp.sum(keep.astype(jnp.int32), axis=1)
    bits, bcard = jax.lax.cond(jnp.any(lift_a), bitmap_pass, bitmap_skip,
                               (da, ca, ka, db, cb, kb))
    card = jnp.where(lift_a, bcard, acard)
    data = jnp.where(lift_a[:, None], bits, arr_rows)
    kind = jnp.where(card == 0, KIND_EMPTY,
                     jnp.where(lift_a, KIND_BITMAP, KIND_ARRAY))
    return data, card, kind


def _tree_reduce_rows(data, card, kind, combine=_or_rows):
    """Log-depth segmented reduction over the leading (slab) axis.

    ``data/card/kind``: stacked key-aligned row states ``[N, C, ...]``. Each
    level pairs adjacent slabs and runs one flattened ``combine`` over
    ``(N/2) * C`` rows — kind-dispatching at every level — carrying the odd
    tail unchanged; ceil(log2 N) levels total, no canonicalization inside
    (callers finish with ``_finalize_rows``).
    """
    C, W = data.shape[1], data.shape[2]
    while data.shape[0] > 1:
        n = data.shape[0]
        half = n // 2
        ev = slice(0, 2 * half, 2)
        od = slice(1, 2 * half, 2)
        d, c, k = combine(
            data[ev].reshape(half * C, W), card[ev].reshape(half * C),
            kind[ev].reshape(half * C),
            data[od].reshape(half * C, W), card[od].reshape(half * C),
            kind[od].reshape(half * C))
        d = d.reshape(half, C, W)
        c = c.reshape(half, C)
        k = k.reshape(half, C)
        if n % 2:
            d = jnp.concatenate([d, data[2 * half:]], axis=0)
            c = jnp.concatenate([c, card[2 * half:]], axis=0)
            k = jnp.concatenate([k, kind[2 * half:]], axis=0)
        data, card, kind = d, c, k
    return data[0], card[0], kind[0]


def slab_and(a: RoaringSlab, b: RoaringSlab,
             capacity: int | None = None) -> RoaringSlab:
    """Kind-dispatch intersection over the registry's 4x4 AND grid.

    array x array -> vectorized galloping; array x bitmap -> bit probes;
    bitmap x bitmap -> fused word-AND + popcount (Alg. 3); array x run ->
    gallop-in-ranges; run x bitmap -> range-mask coverage AND; run x run ->
    the run-domain merge (never touches bits at all). Mask-semantic outputs
    are provably <= min(card_a, card_b) <= 4096, so they compact straight to
    packed arrays; only bits-semantic rows whose canonical form is packed
    pay the (cond-guarded) extraction.
    """
    from repro.kernels.roaring import ops as _kops
    capacity = capacity or min(a.capacity, b.capacity)
    keys = _intersect_keys(a, b, capacity)
    da, ca, ka = _gather_raw(a, keys)
    db, cb, kb = _gather_raw(b, keys)
    ra = _rows_nruns(da, ka)
    rb = _rows_nruns(db, kb)
    rr = _D.route_mask("run_merge", ka, kb)
    # run x run rows are routed around the kernel (masked empty -> skipped)
    meta = _dispatch_meta(jnp.where(rr, KIND_EMPTY, ka),
                          jnp.where(rr, KIND_EMPTY, kb), ca, cb, ra, rb)
    hits, kcard = _kops.intersect_dispatch(da, db, meta)
    pairs_rr, card_rr, nr_rr, bits_rr = _run_merge_rows_lazy(da, db, rr)

    mask_m = _D.out_mask("mask_a", ka, kb) | _D.out_mask("mask_b", ka, kb)
    src = jnp.where(_D.out_mask("mask_b", ka, kb)[:, None], db, da)
    arr_rows = jax.vmap(_compact_row)(src, (hits == 1) & mask_m[:, None])
    card = jnp.where(rr, card_rr, kcard)
    overflow = rr & (nr_rr > MAX_RUNS)
    form = jnp.where(rr & ~overflow, FORM_RUNS,
                     jnp.where(_D.out_mask("bits", ka, kb) | overflow,
                               FORM_BITS, FORM_ARRAY))
    bits_rows = jnp.where(rr[:, None], bits_rr, hits)
    return _finalize(keys, card, form, arr_rows, bits_rows, pairs_rr, nr_rr)


def slab_and_card(a: RoaringSlab, b: RoaringSlab) -> jax.Array:
    """|A ∩ B| without materializing a result slab (Alg. 3 line 5 for free:
    the dispatch kernel's fused popcount/hit-count is the entire answer —
    run x run rows use the in-kernel coverage-AND form, no merge pass)."""
    from repro.kernels.roaring import ops as _kops
    keys = _intersect_keys(a, b, min(a.capacity, b.capacity))
    da, ca, ka = _gather_raw(a, keys)
    db, cb, kb = _gather_raw(b, keys)
    meta = _dispatch_meta(ka, kb, ca, cb, _rows_nruns(da, ka),
                          _rows_nruns(db, kb))
    _, card = _kops.intersect_dispatch(da, db, meta)
    return jnp.sum(card)


def slab_or_card(a: RoaringSlab, b: RoaringSlab) -> jax.Array:
    """|A ∪ B| via inclusion-exclusion on the per-container counters."""
    return a.cardinality + b.cardinality - _slab_and_card(a, b)


def slab_jaccard(a: RoaringSlab, b: RoaringSlab) -> jax.Array:
    """|A ∩ B| / |A ∪ B| in one dispatch pass (0 when both empty)."""
    inter = _slab_and_card(a, b)
    union = a.cardinality + b.cardinality - inter
    return jnp.where(union > 0, inter / jnp.maximum(union, 1), 0.0)


def stack_slabs(slabs: list[RoaringSlab]) -> RoaringSlab:
    """Stack same-capacity slabs into one batched (leading-axis) slab."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *slabs)


def slab_and_many(query: RoaringSlab, slabs: list[RoaringSlab],
                  unroll: bool = False) -> RoaringSlab:
    """Batched ``query ∩ slab_i`` over a fleet of same-capacity slabs.

    Default is one vmapped dispatch (single fused launch) — note that vmap
    lowers the lax.cond laziness guards to select, so the down-conversion
    pass runs for every batch element. ``unroll=True`` traces each pair
    separately (compile time grows with the fleet) but keeps the runtime
    laziness per slab — prefer it for large fleets of array-dominated slabs.
    """
    if unroll:
        return stack_slabs([_slab_and(query, s) for s in slabs])
    return jax.vmap(lambda s: _slab_and(query, s))(stack_slabs(slabs))


def slab_and_card_many(query: RoaringSlab,
                       slabs: list[RoaringSlab]) -> jax.Array:
    """Batched intersection cardinalities — the query-engine primitive
    (score many posting lists against one query without materializing).
    Cond-free, so vmap costs nothing extra."""
    stacked = stack_slabs(slabs)
    return jax.vmap(lambda s: _slab_and_card(query, s))(stacked)


def _lift_rows(data, card, kind):
    """Batched bitmap-domain view of raw rows u16[..., 4096]: the kind lift
    of ``dispatch.lift_rows`` with the default device's array lift. The
    array and run lifts each run only when some row needs them (tree
    levels past the first hold bitmap rows only)."""
    # the run lift reads the rows' pair padding, not a run count
    words = _D.lift_rows(data, kind, card, jnp.zeros_like(kind),
                         _D.device_array_lift())
    return _D.from_words(words)


def _row_merge_sparse(da, ca, db, cb, *, xor: bool):
    """Array x array union/xor by sorted merge of the two packed prefixes —
    O(8192 log), stays entirely in array domain. Only meaningful when
    card_a + card_b <= 4096 (caller guarantees via the pair class)."""
    INVALID = jnp.int32(1) << 17
    slot = jnp.arange(ROW_WORDS, dtype=jnp.int32)
    ia = jnp.where(slot < ca, da.astype(jnp.int32), INVALID)
    ib = jnp.where(slot < cb, db.astype(jnp.int32), INVALID)
    cat = jnp.sort(jnp.concatenate([ia, ib]))
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), cat[:-1]])
    nxt = jnp.concatenate([cat[1:], jnp.full((1,), -2, jnp.int32)])
    first = cat != prev
    keep = first & (cat < INVALID)
    if xor:
        keep = keep & (cat != nxt)
    h = keep.astype(jnp.int32)
    rank = jnp.cumsum(h) - h
    idx = jnp.where(keep, rank, 2 * ROW_WORDS)
    row = jnp.full((ROW_WORDS,), 0xFFFF, jnp.uint16).at[idx].set(
        cat.astype(jnp.uint16), mode="drop")
    return row, jnp.sum(h)


def _union_like(a: RoaringSlab, b: RoaringSlab, capacity: int,
                word_op, xor: bool) -> RoaringSlab:
    """Shared OR/XOR pipeline: merge the key sets, run one ``_or_rows``
    combine step (registry union policy — see its docstring), and finalize
    best-of-three so run-shaped outputs come back out as run rows."""
    keys = _merge_keys(a, b, capacity)
    da, ca, ka = _gather_raw(a, keys)
    db, cb, kb = _gather_raw(b, keys)
    data, card, kind = _or_rows(da, ca, ka, db, cb, kb, word_op=word_op,
                                xor=xor)
    return _finalize_rows(keys, data, card, kind)


def slab_or(a: RoaringSlab, b: RoaringSlab,
            capacity: int | None = None) -> RoaringSlab:
    """A ∪ B through the kind-dispatch engine (canonical output).

    Capacity defaults to ``a.capacity + b.capacity`` (the key sets may be
    disjoint); pass a tighter static ``capacity`` when the union's key count
    is known to fit.
    """
    return _union_like(a, b, capacity or (a.capacity + b.capacity),
                       jnp.bitwise_or, xor=False)


def slab_xor(a: RoaringSlab, b: RoaringSlab,
             capacity: int | None = None) -> RoaringSlab:
    """A ⊕ B (symmetric difference), same routing/canonical discipline as
    ``slab_or`` with the sorted-merge dropping equal pairs."""
    return _union_like(a, b, capacity or (a.capacity + b.capacity),
                       jnp.bitwise_xor, xor=True)


def slab_andnot(a: RoaringSlab, b: RoaringSlab,
                capacity: int | None = None) -> RoaringSlab:
    """A \\ B, routed by the registry's andnot policy: array-A rows probe B
    in place whatever B's kind — binary search for array B, bit probe for
    bitmap B, gallop-in-ranges for run B (result provably <= card_a <= 4096,
    stays packed); bitmap- and run-A rows go through the (cond-guarded)
    bitmap domain with the cheap run lift."""
    capacity = capacity or a.capacity
    keys = _pad_keys(a.keys, capacity)
    da, ca, ka = _gather_raw(a, keys)
    db, cb, kb = _gather_raw(b, keys)
    data, card, kind = _andnot_rows(da, ca, ka, db, cb, kb)
    return _finalize_rows(keys, data, card, kind)


# =============================================================================
# legacy bitmap-domain path (pre-dispatch) — A/B baseline + cross-check
# =============================================================================

def _gather_rows(s: RoaringSlab, keys: jax.Array):
    """Bitmap-domain rows of ``s`` aligned to ``keys`` (zeros when absent)."""
    pos = jnp.searchsorted(s.keys, keys)
    pos_c = jnp.minimum(pos, s.capacity - 1)
    present = (s.keys[pos_c] == keys) & (keys != KEY_SENTINEL)
    bits = jax.vmap(row_to_bits)(s.data[pos_c], s.card[pos_c], s.kind[pos_c])
    return bits * present[:, None].astype(jnp.uint16), present


def _binary_bits_op(a: RoaringSlab, b: RoaringSlab, word_op, capacity: int,
                    intersection: bool) -> RoaringSlab:
    """Pre-dispatch formulation: lift every row to the 2^16-bit domain,
    apply the word op, re-canonicalize every output row. Pays the full
    bitmap-domain tax regardless of container kinds — kept only so the
    benchmarks can measure what the dispatch path saves."""
    if capacity is None:
        capacity = a.capacity + b.capacity
    keys = _merge_keys(a, b, capacity)
    bits_a, pa = _gather_rows(a, keys)
    bits_b, pb = _gather_rows(b, keys)
    out_bits = word_op(bits_a, bits_b)
    data, card, kind = jax.vmap(_row_canonicalize_2kind)(out_bits)
    live = card > 0
    if intersection:
        live = live & pa & pb
        card = jnp.where(live, card, 0)
        kind = jnp.where(live, kind, KIND_EMPTY)
    keys = jnp.where(live, keys, KEY_SENTINEL)
    # compact: sort rows so live keys are sorted first (sentinel rows sink)
    order = jnp.argsort(keys)
    return RoaringSlab(keys=keys[order], card=card[order], kind=kind[order],
                       data=data[order])


def slab_and_bitmap_domain(a: RoaringSlab, b: RoaringSlab,
                           capacity: int | None = None) -> RoaringSlab:
    """A ∩ B through the pre-dispatch bitmap-domain path (A/B baseline).

    Same values/card as ``slab_and`` but 2-kind canonicalization only (no
    run outputs) and the full per-row O(2^16) tax — benchmark baseline, not
    a production path.
    """
    return _binary_bits_op(a, b, jnp.bitwise_and,
                           capacity or min(a.capacity, b.capacity) * 2,
                           intersection=True)


def slab_or_bitmap_domain(a: RoaringSlab, b: RoaringSlab,
                          capacity: int | None = None) -> RoaringSlab:
    """A ∪ B through the pre-dispatch bitmap-domain path (A/B baseline);
    see ``slab_and_bitmap_domain``."""
    return _binary_bits_op(a, b, jnp.bitwise_or,
                           capacity or (a.capacity + b.capacity),
                           intersection=False)


def union_many_slabs(slabs: list[RoaringSlab], capacity: int) -> RoaringSlab:
    """Algorithm 4, TPU form: log-depth tree reduction through the engine.

    The merged key set is computed once; every slab's rows are gathered
    key-aligned in *native* container form, and ``_tree_reduce_rows`` runs
    ceil(log2 N) ``_or_rows`` levels — kind-dispatching at every level:
    sparse array pairs merge in array domain, everything else goes through
    the bitmap domain with the O(4096) kind-aware lift (run rows range-mask,
    never the 2^16 element domain). Canonicalization is a *single* deferred
    best-of-three pass at the root, so run-shaped unions (e.g. the KV free
    pool) come back out as run rows. Replaces the PR 2 static unroll of N
    sequential bitmap-domain ORs; see ``benchmarks/kernel_bench.wide_ab``
    for the tree-vs-fold speedup gate.
    """
    if not slabs:
        return empty(capacity)
    keys = _merge_keys_many([s.keys for s in slabs], capacity)
    gathered = [_gather_raw(s, keys) for s in slabs]
    data = jnp.stack([g[0] for g in gathered])
    card = jnp.stack([g[1] for g in gathered])
    kind = jnp.stack([g[2] for g in gathered])
    data, card, kind = _tree_reduce_rows(data, card, kind, _or_rows_deferred)
    card = _recount_bitmap_rows(data, card, kind)   # Alg. 4: recount once
    return _finalize_rows(keys, data, card, kind)


# =============================================================================
# deprecation shims: the tuple-threading slab_* free functions are superseded
# by the repro.roaring object API. Each public slab_* name below is rebound to
# a shim that warns (DeprecationWarning, caller-attributed) and delegates; the
# original implementation stays reachable as _slab_<name> — the internal layer
# repro.roaring and this module's own helpers call. Warning cost is trace-time
# only: jitted callers never re-enter the shim.
# =============================================================================

_DEPRECATED = {
    "slab_and": "a & b (repro.roaring.RoaringSlab)",
    "slab_or": "a | b (repro.roaring.RoaringSlab)",
    "slab_xor": "a ^ b (repro.roaring.RoaringSlab)",
    "slab_andnot": "a - b (repro.roaring.RoaringSlab)",
    "slab_and_card": "a.and_card(b) (repro.roaring.RoaringSlab)",
    "slab_or_card": "a.or_card(b) (repro.roaring.RoaringSlab)",
    "slab_jaccard": "a.jaccard(b) (repro.roaring.RoaringSlab)",
    "slab_select": "a.select(j) (repro.roaring.RoaringSlab)",
    "slab_run_optimize": "a.run_optimize() (repro.roaring.RoaringSlab)",
    "slab_and_many": "stacked & query (repro.roaring, batched broadcast)",
    "slab_and_card_many": "stacked.and_card(query) (repro.roaring)",
    "slab_and_bitmap_domain":
        "repro.roaring set algebra (this A/B baseline stays for benchmarks)",
    "slab_or_bitmap_domain":
        "repro.roaring set algebra (this A/B baseline stays for benchmarks)",
}


def _install_deprecation_shims() -> None:
    g = globals()
    for name, repl in _DEPRECATED.items():
        impl = g[name]
        g["_" + name] = impl

        def _make(impl=impl, name=name, repl=repl):
            @functools.wraps(impl)
            def shim(*args, **kwargs):
                warnings.warn(
                    f"repro.core.jax_roaring.{name} is deprecated; "
                    f"use {repl}", DeprecationWarning, stacklevel=2)
                return impl(*args, **kwargs)

            shim.__wrapped__ = impl
            return shim

        g[name] = _make()


_install_deprecation_shims()
