"""Plain reference of the store deployments: the Star Schema Benchmark's
``lineorder`` flattened with its ``date`` dimension, made from the seed by
dbgen's rules, and the exact answers of query flight 1 on numpy columns.
Imports nothing of the program under test.

A request is a plain tuple ``(name, aggregate, filters)``: ``aggregate`` is
``(value column, by column)`` — the answer is ``sum(value * by)`` over the
rows that pass every filter, SSB's ``sum(lo_extendedprice * lo_discount)``
— and each filter is ``(column, lo, hi)``, the closed range ``lo <= column
<= hi`` with ``None`` for an open side.
"""

from __future__ import annotations

import numpy as np

from bench import roofline

FIRST_DAY = np.datetime64("1992-01-01")
N_DAYS = 2406                   # 1992-01-01 to 1998-08-02, dbgen's dates


def n_parts(scale_factor: float) -> int:
    """dbgen's part count: 200,000 * (1 + floor(log2 SF)) from SF 1 on."""
    sf = max(1.0, float(scale_factor))
    return 200_000 * (1 + int(np.floor(np.log2(sf))))


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """dbgen's ``p_retailprice`` in cents."""
    pk = partkey.astype(np.int64)
    return 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1_000)


def date_columns(day: np.ndarray) -> dict:
    """``d_year``, ``d_yearmonthnum`` and ``d_weeknuminyear`` of day
    indices counted from 1992-01-01 (a table of the 2,406 dates, then one
    take per column)."""
    dates = FIRST_DAY + np.arange(N_DAYS)
    year = dates.astype("datetime64[Y]").astype(np.int64) + 1970
    month = dates.astype("datetime64[M]").astype(np.int64) % 12 + 1
    doy = (dates - dates.astype("datetime64[Y]")).astype(np.int64) + 1
    table = {"d_year": year.astype(np.int16),
             "d_yearmonthnum": (100 * year + month).astype(np.int32),
             "d_weeknuminyear": ((doy - 1) // 7 + 1).astype(np.int8)}
    return {k: v[day] for k, v in table.items()}


class Data:
    """The flattened columns, one row per ``lineorder`` row: order date
    uniform over the 2,406 days, quantity over 1-50, discount over 0-10,
    part key over the scale factor's parts; extended price = quantity x
    the part's retail price."""

    def __init__(self, config: dict, seed: int):
        self.n_rows = int(config["n_rows"])
        n = self.n_rows
        rng = np.random.default_rng([seed % (1 << 64), 11])
        day = rng.integers(0, N_DAYS, n, dtype=np.int16)
        quantity = rng.integers(1, 51, n, dtype=np.int8)
        partkey = rng.integers(1, n_parts(config["scale_factor"]) + 1, n,
                               dtype=np.int32)
        self.columns = date_columns(day)
        self.columns["lo_discount"] = rng.integers(0, 11, n, dtype=np.int8)
        self.columns["lo_quantity"] = quantity
        self.columns["lo_extendedprice"] = (
            quantity.astype(np.int32) * retail_price(partkey).astype(
                np.int32))
        self.bsi = tuple(config["bsi"])


def request(config: dict, data: Data, template: dict, rng) -> tuple:
    """The request a traffic template names: its literals as they stand."""
    filters = tuple((col, None if lo is None else int(lo),
                     None if hi is None else int(hi))
                    for col, lo, hi in template["filters"])
    return (template["name"], tuple(template["aggregate"]), filters)


class Reference:
    """Exact answers on the numpy columns, cached per request."""

    def __init__(self, config: dict, data: Data):
        self.data = data
        self._answers: dict = {}
        self._bytes: dict = {}

    def mask(self, filters) -> np.ndarray:
        m = np.ones(self.data.n_rows, bool)
        for col, lo, hi in filters:
            c = self.data.columns[col]
            if lo is not None:
                m &= c >= lo
            if hi is not None:
                m &= c <= hi
        return m

    def answer(self, request) -> int:
        if request not in self._answers:
            _, (value, by), filters = request
            m = self.mask(filters)
            price, disc = (self.data.columns[value][m],
                           self.data.columns[by][m])
            self._answers[request] = int(
                (price.astype(np.int64) * disc).sum())
        return self._answers[request]

    def serve(self, request, precision: str) -> int:
        """The control: the exact answer rounded to ``precision``."""
        if precision != "bfloat16":
            raise ValueError(f"no control in {precision!r}")
        import ml_dtypes
        return int(np.float32(np.array(self.answer(request), np.float32)
                              .astype(ml_dtypes.bfloat16)))

    def same(self, request, got, want) -> bool:
        return got == want

    def least_bytes(self, request) -> int:
        """Least bytes the request moves: each distinct operand slab once at
        its smallest encoding. The slabs are the filters' (one per value of
        an equality column in range, every bit slice of a bit-sliced one),
        the slab of each value of the ``by`` column the filters leave, and
        every bit slice of the summed column."""
        if request not in self._bytes:
            self._bytes[request] = self._least_bytes(request)
        return self._bytes[request]

    def _least_bytes(self, request) -> int:
        _, (value, by), filters = request
        slabs = set(self._column_slabs(value, None, None))
        for col, lo, hi in filters:
            if col != by:
                slabs.update(self._column_slabs(col, lo, hi))
        by_range = [(lo, hi) for col, lo, hi in filters if col == by]
        slabs.update(s for s in self._column_slabs(by, None, None)
                     if all((lo is None or s[2] >= lo)
                            and (hi is None or s[2] <= hi)
                            for lo, hi in by_range))
        return sum(self._slab_bytes(s) for s in slabs)

    def _column_slabs(self, col, lo, hi):
        c = self.data.columns[col]
        if col in self.data.bsi:
            bits = max(1, int(c.max()).bit_length())
            return [(col, "bit", j) for j in range(bits)]
        return [(col, "eq", int(v)) for v in np.unique(c)
                if (lo is None or v >= lo) and (hi is None or v <= hi)]

    def _slab_bytes(self, slab) -> int:
        if slab not in self._bytes:
            col, kind, x = slab
            c = self.data.columns[col]
            mask = ((c >> x) & 1).astype(bool) if kind == "bit" else c == x
            self._bytes[slab] = roofline.mask_bytes(mask)
        return self._bytes[slab]
