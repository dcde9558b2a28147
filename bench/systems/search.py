"""Drives ``repro.search.SearchService`` over a ``PostingIndex``: the
program under test for the search deployments.

The window calls ``SearchService.submit`` for each request, ``step`` while
any is queued, and ``take`` for each one a step completed; nothing else
touches the program in the window.
"""

from __future__ import annotations

ENTRY = "SearchService.submit/step/take"


def _term(rank: int) -> str:
    return f"t{rank:05d}"          # rank order is sorted order


class Server:
    """The service behind the loop's ``submit`` / ``step`` / ``pending``."""

    def __init__(self, config: dict, data):
        import jax

        from repro.search.index import PostingIndex
        from repro.search.service import SearchService

        postings = {_term(r): p for r, p in enumerate(data.postings)}
        self.index = PostingIndex.from_postings(postings, data.n_docs)
        jax.block_until_ready(self.index.stack.payload)
        self.service = SearchService(
            self.index, max_batch=int(config["max_batch"]),
            cache_slots=int(config["cache_slots"]))
        self._pending = 0

    def resident_bytes(self) -> int:
        st = self.index.stack
        return sum(int(x.nbytes) for x in
                   (st.payload, st.keys, st.kinds, st.cards, st.nruns))

    @staticmethod
    def query(request):
        from repro.search import service as S

        shape, ranks, _, _ = request
        leaves = [S.term(_term(r)) for r in ranks]
        if shape == "term":
            return leaves[0]
        if shape == "and":
            return S.and_(*leaves)
        if shape == "or":
            return S.or_(*leaves)
        return S.andnot(S.and_(*leaves[:-1]), leaves[-1])

    def submit(self, request) -> int:
        _, _, mode, k = request
        self._pending += 1
        if mode == "topk":
            return self.service.submit(self.query(request), mode, k=k)
        return self.service.submit(self.query(request), mode)

    def step(self):
        done = self.service.step()
        self._pending -= len(done)
        return [(rid, self._take(rid)) for rid in done]

    def _take(self, rid: int):
        try:
            return self._served(self.service.take(rid))
        except KeyError as e:       # completed without a result
            return e

    @staticmethod
    def _served(value):
        """Counts as ints; top-k terms as ranks (-1: the empty row); doc ids
        (the warm-up's) as they come."""
        if isinstance(value, list):
            return [(-1 if t is None else int(t[1:]), int(s))
                    for t, s in value]
        return value if hasattr(value, "shape") and value.ndim else int(value)

    def pending(self) -> int:
        return self._pending

    def warm(self, requests_of_template, n_templates: int, log) -> None:
        """Compile every shape the traffic uses: each template at every
        batch size from 1 to ``max_batch``, as one step each. A top-k
        template steps in ``docs`` mode, which runs the same batch program
        without scoring the whole vocabulary. The scoring, and the slicing
        of each batch's rows for it, compile per ``k`` and batch size: one
        top-k template per ``k`` steps once more at every batch size."""
        first_of_k = {}
        for t in range(n_templates):
            for b in range(1, self.service.max_batch + 1):
                for shape, ranks, mode, k in requests_of_template(t, b):
                    if mode == "topk":
                        first_of_k.setdefault(k, t)
                        mode = "docs"
                    self.submit((shape, ranks, mode, k))
                self._warm_step(t, b)
        for t in first_of_k.values():
            for b in range(1, self.service.max_batch + 1):
                for req in requests_of_template(t, b):
                    self.submit(req)
                self._warm_step(t, b)
        stats = self.service.cache_stats()
        log(f"warm-up: {n_templates} templates x batch 1-"
            f"{self.service.max_batch}, {len(first_of_k)} scored; cache "
            f"hits {stats['hits']} misses {stats['misses']}")

    def _warm_step(self, t: int, b: int) -> None:
        done = self.step()
        if len(done) != b or self.pending():
            raise RuntimeError(f"warm-up of template {t} at batch {b} ran a "
                               f"batch of {len(done)}")

    def counters(self) -> dict:
        s = self.service.cache_stats()
        return {"search.cache.hits": s["hits"],
                "search.cache.misses": s["misses"],
                "search.cache.evictions": s["evictions"],
                "search.steps": self.service.steps_run}

    def close(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.service = None
        self.index = None
