"""Plain reference of the search deployments: Zipf postings made from the
seed, term queries drawn from the traffic, and their exact answers on
packed numpy bitsets. Imports nothing of the program under test.

A request is a plain tuple ``(shape, ranks, mode, k)``: ``shape`` is
``term`` / ``and`` / ``or`` / ``andnot``, ``ranks`` the 0-based Zipf ranks
of its distinct terms (rank 0 the most frequent). ``andnot`` of two terms
is ``a AND NOT b``, of three ``(a AND b) AND NOT c``.

A traffic file names term-frequency ``bands``, each ``[lo, hi)`` as a
share of all documents, and each template one band per term: a request
draws its terms uniformly from the ranks whose posting lies in the band.
"""

from __future__ import annotations

import numpy as np

from bench import roofline

SHAPES = ("term", "and", "or", "andnot")


class Data:
    """Postings of ``n_terms`` Zipf-sized terms over ``n_docs`` documents:
    the term of rank ``r`` holds ``max(1, 0.5 * n_docs / (r + 1) ** s)``
    uniform draws, deduplicated (the sizes are the same for every seed).
    """

    def __init__(self, config: dict, seed: int):
        self.n_docs = int(config["n_docs"])
        self.n_terms = int(config["n_terms"])
        s = float(config["posting_zipf_s"])
        rng = np.random.default_rng([seed % (1 << 64), 7])
        ranks = np.arange(1, self.n_terms + 1, dtype=np.float64)
        # each term's nominal share of the docs, the same for every seed
        self.share = 0.5 * ranks ** -s
        sizes = np.maximum(1, self.n_docs * self.share).astype(np.int64)
        self.postings = [np.unique(rng.integers(0, self.n_docs, int(n)))
                         for n in sizes]

    def band(self, lo: float, hi: float) -> np.ndarray:
        """Ranks whose nominal share of the docs lies in ``[lo, hi)``."""
        ranks = np.flatnonzero((self.share >= lo) & (self.share < hi))
        if ranks.size == 0:
            raise ValueError(f"no term holds a share in [{lo}, {hi})")
        return ranks


def request(config: dict, data: Data, template: dict, rng) -> tuple:
    """The request a traffic template describes: one distinct term per
    entry of its ``bands``, drawn uniformly from that band."""
    shape, bands = template["shape"], template["bands"]
    n = len(bands)
    if (shape not in SHAPES or (shape == "term") != (n == 1)
            or (shape == "andnot" and n > 3)):
        raise ValueError(f"unsupported query template {template}")
    ranks: list = []
    for name in bands:
        lo, hi = template["term_bands"][name]
        pool = np.setdiff1d(data.band(float(lo), float(hi)), ranks)
        ranks.append(int(rng.choice(pool)))
    return (shape, tuple(ranks), template["mode"], int(template.get("k", 0)))


class Reference:
    """Exact answers on packed little-endian bitsets."""

    def __init__(self, config: dict, data: Data):
        self.data = data
        self._bits: dict = {}
        self._bytes: dict = {}
        self._all_ids = None

    def bits(self, rank: int) -> np.ndarray:
        if rank not in self._bits:
            dense = np.zeros(self.data.n_docs, bool)
            dense[self.data.postings[rank]] = True
            self._bits[rank] = np.packbits(dense, bitorder="little")
        return self._bits[rank]

    def evaluate(self, request) -> np.ndarray:
        shape, ranks, _, _ = request
        b = [self.bits(r) for r in ranks]
        if shape == "term":
            return b[0]
        if shape == "and":
            return np.bitwise_and.reduce(b)
        if shape == "or":
            return np.bitwise_or.reduce(b)
        return np.bitwise_and.reduce(b[:-1]) & ~b[-1]

    def scores(self, bits: np.ndarray) -> np.ndarray:
        """``|posting ∩ result|`` of every term, in rank order."""
        if self._all_ids is None:
            self._all_ids = np.concatenate(self.data.postings)
            self._starts = np.cumsum(
                [0] + [p.size for p in self.data.postings[:-1]])
        dense = np.unpackbits(bits, bitorder="little")[: self.data.n_docs]
        return np.add.reduceat(dense[self._all_ids].astype(np.int64),
                               self._starts)

    def answer(self, request):
        """What the served answer is compared with: the exact count, or for
        ``topk`` the k best scores and every term's score."""
        bits = self.evaluate(request)
        _, _, mode, k = request
        if mode == "count":
            return int(np.bitwise_count(bits).sum())
        if mode != "topk":
            raise ValueError(f"mode {mode!r} has no reference here")
        scores = self.scores(bits)
        # the index ranks its reserved empty row too: score 0, rank -1
        return sorted(scores.tolist() + [0], reverse=True)[:k], scores

    def serve(self, request, precision: str):
        """The control: the reference in the program's place, answering in
        the served form with every count and score rounded to
        ``precision`` (``bfloat16``)."""
        bits = self.evaluate(request)
        _, _, mode, k = request
        if mode == "count":
            return _round(int(np.bitwise_count(bits).sum()), precision)
        scores = [_round(int(s), precision) for s in self.scores(bits)]
        ranked = sorted(enumerate(scores + [0]), key=lambda rs: -rs[1])[:k]
        return [(r if r < len(scores) else -1, s) for r, s in ranked]

    def same(self, request, got, want) -> bool:
        """Whether the served answer equals the reference's. A top-k list
        must hold the reference's k best scores, and each of its terms the
        reference's score for that term (ties may come in any order)."""
        if request[2] == "count":
            return got == want
        top, scores = want
        if [s for _, s in got] != top:
            return False
        return all((s == 0) if r < 0 else (int(scores[r]) == s)
                   for r, s in got)

    def least_bytes(self, request) -> int:
        """Least bytes the request moves: each operand posting once, and for
        ``topk`` every posting of the vocabulary, which it scores."""
        shape, ranks, mode, _ = request
        terms = range(self.data.n_terms) if mode == "topk" else ranks
        return sum(self._posting_bytes(r) for r in set(terms) | set(ranks))

    def _posting_bytes(self, rank: int) -> int:
        if rank not in self._bytes:
            self._bytes[rank] = roofline.sorted_ids_bytes(
                self.data.postings[rank])
        return self._bytes[rank]


def _round(x: int, precision: str) -> int:
    if precision != "bfloat16":
        raise ValueError(f"no control in {precision!r}")
    import ml_dtypes
    return int(np.float32(np.array(x, np.float32).astype(
        ml_dtypes.bfloat16)))
