"""The one traffic generator, and the loops that offer its load.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

* ``arrivals``: ``{"kind": "open", "rate_per_s": r}`` — independent users,
  a Poisson open loop at a fixed rate; or ``{"kind": "closed",
  "clients": n}`` — n callers that each send the next request when the
  last one is answered.
* ``mix``: request templates with a ``weight`` each. What a template holds
  beyond its weight (a tree shape, a mode, literals) is read by the
  configuration's system adapter.
* anything else the adapter reads (``term_bands``).

Every seed gets the same work in another order: an open loop of ``n``
requests takes its gaps from the ``n`` midpoint quantiles of the
exponential distribution and its templates in exact proportion, both
shuffled by the seed; a closed loop repeats shuffled blocks of the mix.
What varies per request (which terms) comes from a generator keyed by
``(seed, request index)``.

The loops time each request from when it was **due**: in the open loop its
scheduled arrival, in the closed loop the moment its client was answered.
A request still unanswered at the close is waited for, up to ``drain_s``.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, List, Optional

import numpy as np


def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *key])


def template_counts(weights: List[float], n: int) -> List[int]:
    """``n`` split over ``weights`` by largest remainder (exact shares)."""
    total = float(sum(weights))
    exact = [w * n / total for w in weights]
    counts = [int(math.floor(x)) for x in exact]
    order = sorted(range(len(weights)), key=lambda i: counts[i] - exact[i])
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def open_schedule(rate: float, seconds: float, weights: List[float],
                  seed: int):
    """``(due_s[], template[])`` of an open loop at ``rate`` over
    ``seconds``: ``round(rate * seconds)`` arrivals."""
    n = max(1, int(round(rate * seconds)))
    rng = _rng(seed, 0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    # the midpoint quantiles sum to a little under n / rate: the last
    # arrival falls inside the window
    due = np.minimum(np.cumsum(rng.permutation(gaps)), seconds * (1 - 1e-9))
    counts = template_counts(weights, n)
    tmpl = rng.permutation(np.repeat(np.arange(len(counts)), counts))
    return due, tmpl


def closed_templates(weights: List[float], seed: int):
    """Endless template indices for a closed loop: shuffled blocks that
    hold each template in proportion to its integer weight."""
    block = np.repeat(np.arange(len(weights)),
                      [int(round(w)) for w in weights])
    rng = _rng(seed, 1)
    while True:
        yield from rng.permutation(block).tolist()


@dataclasses.dataclass
class Record:
    """One request's life in the window, on the host clock (seconds from
    the window's start)."""

    index: int
    template: int
    request: object
    due: float
    submitted: float = math.nan
    done: float = math.nan
    result: object = None
    error: Optional[str] = None

    @property
    def latency(self) -> float:
        return self.done - self.due

    @property
    def answered(self) -> bool:
        return self.done == self.done and self.error is None


class Loop:
    """Drives one server through one window.

    ``server`` has ``submit(request) -> handle``, ``step() -> [(handle,
    result)]`` and ``pending() -> int``; ``make(index, template)`` builds
    the request a template describes. ``annotate(name)`` returns a context
    manager around each call into the server (the profiler's host spans).
    """

    def __init__(self, server, make: Callable, annotate: Callable):
        self.server = server
        self.make = make
        self.annotate = annotate
        self.records: List[Record] = []
        self.step_s: List[float] = []       # each step's duration
        self._by_handle = {}
        self.t0 = 0.0

    def _now(self) -> float:
        return time.perf_counter() - self.t0

    def _submit(self, rec: Record) -> None:
        with self.annotate("submit"):
            handle = self.server.submit(rec.request)
        rec.submitted = self._now()
        self._by_handle[handle] = rec
        self.records.append(rec)

    def _step(self) -> List[Record]:
        t0 = time.perf_counter()
        with self.annotate("step"):
            out = self.server.step()
        self.step_s.append(time.perf_counter() - t0)
        now = self._now()
        done = []
        for handle, result in out:
            rec = self._by_handle.pop(handle)
            rec.done = now
            if isinstance(result, BaseException):
                rec.error = f"{type(result).__name__}: {result}"
            else:
                rec.result = result
            done.append(rec)
        return done

    def open_loop(self, due, templates, seconds: float,
                  drain_s: float) -> None:
        """Submit each request at its due time; step while work is queued;
        after ``seconds`` submit nothing more and drain."""
        n = len(due)
        i = 0
        self.t0 = time.perf_counter()
        while True:
            now = self._now()
            while i < n and due[i] <= now:
                self._submit(Record(i, int(templates[i]),
                                    self.make(i, int(templates[i])),
                                    float(due[i])))
                i += 1
            if self.server.pending():
                if now > seconds + drain_s:
                    return
                self._step()
            elif i < n:
                with self.annotate("wait"):
                    time.sleep(max(0.0, due[i] - self._now()))
            else:
                return

    def closed_loop(self, clients: int, templates, seconds: float,
                    drain_s: float) -> None:
        """``clients`` callers, each sending its next request the moment
        its last one is answered, until ``seconds``; then drain."""
        self.t0 = time.perf_counter()
        index = 0
        for _ in range(clients):
            t = next(templates)
            self._submit(Record(index, t, self.make(index, t), 0.0))
            index += 1
        while self.server.pending():
            if self._now() > seconds + drain_s:
                return
            for rec in self._step():
                if rec.done < seconds:
                    t = next(templates)
                    self._submit(Record(index, t, self.make(index, t),
                                        rec.done))
                    index += 1

    def lateness(self) -> List[float]:
        """How late each request was handed to the server (seconds)."""
        return [r.submitted - r.due for r in self.records]
