"""Drives ``repro.store.BitmapStore`` over the cell's columns: the program
under test for the store deployments.

The window calls ``submit`` for each request and ``step`` while any is
queued; a step answers every queued request in turn, each by one
``BitmapStore.group_sum`` call (the store has no batcher). A request
``(name, (value, by), filters)`` is answered as ``sum(value * by)``: the
store's sum of ``value`` grouped by ``by``, weighted by each group's value.
"""

from __future__ import annotations

import collections

ENTRY = "BitmapStore.group_sum"


def predicate(filters):
    """The store predicate of a request's ``(column, lo, hi)`` filters."""
    from repro.store import predicate as P

    return P.and_(*[P.eq(col, lo) if lo is not None and lo == hi
                    else P.range_(col, lo, hi) for col, lo, hi in filters])


class Server:
    """The store behind the loop's ``submit`` / ``step`` / ``pending``."""

    def __init__(self, config: dict, data):
        import jax

        from repro.store.store import BitmapStore

        # looked up before the build, which takes most of set-up, so that a
        # program without the entry fails at once
        self._group_sum = BitmapStore.group_sum
        self.store = BitmapStore.build(data.columns, bsi=config["bsi"])
        jax.block_until_ready(self.store._stack.payload)
        self.queue = collections.deque()
        self._next = 0

    def resident_bytes(self) -> int:
        st = self.store._stack
        return sum(int(x.nbytes) for x in
                   (st.payload, st.keys, st.kinds, st.cards, st.nruns))

    def answer(self, request) -> int:
        _, (value, by), filters = request
        sums = self._group_sum(self.store, value, by, predicate(filters))
        return sum(v * s for v, s in sums.items())

    def submit(self, request) -> int:
        self._next += 1
        self.queue.append((self._next, request))
        return self._next

    def step(self):
        done = []
        while self.queue:
            handle, request = self.queue.popleft()
            done.append((handle, self.answer(request)))
        return done

    def pending(self) -> int:
        return len(self.queue)

    def warm(self, requests_of_template, n_templates: int, log) -> None:
        """Compile every program the traffic uses: each template once."""
        for t in range(n_templates):
            for request in requests_of_template(t, 1):
                self.submit(request)
            self.step()
        log(f"warm-up: {n_templates} templates; store cache "
            f"{self.store.cache_stats()}")

    def counters(self) -> dict:
        s = self.store.cache_stats()
        return {"store.cache.hits": s["hits"],
                "store.cache.misses": s["misses"],
                "store.cache.fallbacks": s["fallbacks"]}

    def close(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.store = None
