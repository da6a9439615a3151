"""Seeded TPC-H-shaped tables: the yardstick's own generator.

A copy in spirit of ``benchmarks/tpch.py``'s generator (same schema, same
distributions, not dbgen-exact), kept here so that a later PR can change the
program's copy and not the benchmark's. Two differences, both for set-up
time, which every run of every later check pays:

- every column draws from a stream of its own, ``default_rng([seed, id])``,
  so a cell generates only the columns its queries read and still gets the
  values any other cell would get for them;
- strings are made as dictionary arrays and cast to ``string`` by Arrow, not
  as 60M numpy unicode objects, and numbers are drawn in the narrowest type
  and widened once, in place where numpy allows: on a fresh machine the
  first touch of a page of host memory is what generation costs.

``generate(scale, seed, columns)`` takes ``{"table": ["column", ...]}`` and
returns ``{"table": pyarrow.Table}`` with exactly those columns, in the
order asked for.
"""

from __future__ import annotations

import datetime
import numpy as np
import pyarrow as pa

ROWS_PER_SF = {"lineitem": 6_000_000, "orders": 1_500_000,
               "customer": 150_000}
MIN_ROWS = {"lineitem": 100, "orders": 25, "customer": 10}

_EPOCH = datetime.date(1970, 1, 1)
_START = (datetime.date(1992, 1, 1) - _EPOCH).days
_END = (datetime.date(1998, 12, 1) - _EPOCH).days

MKT_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD",
                "MACHINERY"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
NATIONS = {
    "ALGERIA": "AFRICA", "ARGENTINA": "AMERICA", "BRAZIL": "AMERICA",
    "CANADA": "AMERICA", "EGYPT": "MIDDLE EAST", "ETHIOPIA": "AFRICA",
    "FRANCE": "EUROPE", "GERMANY": "EUROPE", "INDIA": "ASIA",
    "INDONESIA": "ASIA", "IRAN": "MIDDLE EAST", "IRAQ": "MIDDLE EAST",
    "JAPAN": "ASIA", "JORDAN": "MIDDLE EAST", "KENYA": "AFRICA",
    "MOROCCO": "AFRICA", "MOZAMBIQUE": "AFRICA", "PERU": "AMERICA",
    "CHINA": "ASIA", "ROMANIA": "EUROPE", "SAUDI ARABIA": "MIDDLE EAST",
    "VIETNAM": "ASIA", "RUSSIA": "EUROPE", "UNITED KINGDOM": "EUROPE",
    "UNITED STATES": "AMERICA",
}

# stream id of every column: fixed for good, a new column takes a new id
_STREAM = {name: i for i, name in enumerate((
    "c_mktsegment", "c_nationkey", "c_acctbal",
    "o_custkey", "o_orderdate", "o_totalprice", "o_orderstatus",
    "l_orderkey", "l_shipdate", "l_quantity", "l_extendedprice",
    "l_discount", "l_tax", "l_returnflag", "l_linestatus", "l_shipmode",
    "l_partkey", "l_suppkey", "l_linenumber"))}


def row_counts(scale: float) -> dict:
    """Rows of each table at ``scale`` (nation is always 25)."""
    n = {t: max(int(per * scale), MIN_ROWS[t])
         for t, per in ROWS_PER_SF.items()}
    n["nation"] = len(NATIONS)
    return n


def _strings(codes: np.ndarray, values: list) -> pa.Array:
    return pa.DictionaryArray.from_arrays(
        pa.array(codes, pa.int8()), pa.array(values, pa.string())
    ).cast(pa.string())


def _dates(days: np.ndarray) -> pa.Array:
    return pa.array(days, pa.int32()).cast(pa.date32())


class _Maker:
    """Columns of one (scale, seed), each made once, on demand."""

    def __init__(self, scale: float, seed: int):
        self.n = row_counts(scale)
        self.seed = int(seed) % (2 ** 32)
        self._raw: dict = {}

    def rng(self, column: str) -> np.random.Generator:
        return np.random.default_rng([self.seed, _STREAM[column]])

    def raw(self, column: str) -> np.ndarray:
        """The numpy values other columns derive from (dates as day
        numbers, keys as int64)."""
        if column not in self._raw:
            n_li, n_ord = self.n["lineitem"], self.n["orders"]
            if column == "o_orderdate":
                v = self.rng(column).integers(_START, _END - 151, n_ord,
                                              dtype=np.int32)
            elif column == "l_orderkey":
                v = self.rng(column).integers(1, n_ord + 1, n_li,
                                              dtype=np.int64)
            else:
                raise KeyError(column)
            self._raw[column] = v
        return self._raw[column]

    def column(self, table: str, name: str) -> pa.Array:
        n = self.n[table]
        n_cust, n_li = self.n["customer"], self.n["lineitem"]
        if table == "nation":
            names = list(NATIONS)
            return {
                "n_nationkey": lambda: pa.array(np.arange(n), pa.int64()),
                "n_name": lambda: pa.array(names, pa.string()),
                "n_regionname": lambda: pa.array(
                    [NATIONS[x] for x in names], pa.string()),
            }[name]()
        if name in ("c_custkey", "o_orderkey"):
            return pa.array(np.arange(1, n + 1, dtype=np.int64))
        if name in ("o_orderdate",):
            return _dates(self.raw(name))
        if name == "l_orderkey":
            return pa.array(self.raw(name))
        if name == "o_shippriority":
            return pa.array(np.zeros(n, dtype=np.int64))
        rng = self.rng(name)
        if name == "c_mktsegment":
            return _strings(rng.integers(0, 5, n, dtype=np.int8),
                            MKT_SEGMENTS)
        if name == "c_nationkey":
            return pa.array(rng.integers(0, len(NATIONS), n, dtype=np.int64))
        if name == "c_acctbal":
            return pa.array(np.round(rng.uniform(-999.99, 9999.99, n), 2))
        if name == "o_custkey":
            return pa.array(rng.integers(1, n_cust + 1, n, dtype=np.int64))
        if name == "o_totalprice":
            return pa.array(np.round(rng.uniform(850.0, 560000.0, n), 2))
        if name == "o_orderstatus":
            return _strings(rng.integers(0, 3, n, dtype=np.int8),
                            ["F", "O", "P"])
        if name == "l_shipdate":
            days = self.raw("o_orderdate")[self.raw("l_orderkey") - 1]
            days += rng.integers(1, 122, n, dtype=np.int8)
            return _dates(days)
        if name == "l_quantity":
            return pa.array(rng.integers(1, 51, n, dtype=np.int8)
                            .astype(np.float64))
        if name == "l_extendedprice":
            price = rng.uniform(900.0, 105000.0, n)
            return pa.array(np.round(price, 2, out=price))
        if name == "l_discount":
            return pa.array(rng.integers(0, 11, n, dtype=np.int8) / 100.0)
        if name == "l_tax":
            return pa.array(rng.integers(0, 9, n, dtype=np.int8) / 100.0)
        if name == "l_returnflag":
            return _strings(rng.integers(0, 3, n, dtype=np.int8),
                            ["A", "N", "R"])
        if name == "l_linestatus":
            return _strings(rng.integers(0, 2, n, dtype=np.int8),
                            ["F", "O"])
        if name == "l_shipmode":
            return _strings(rng.integers(0, 7, n, dtype=np.int8), SHIPMODES)
        if name == "l_partkey":
            return pa.array(rng.integers(1, max(n_li // 30, 2), n,
                                         dtype=np.int64))
        if name == "l_suppkey":
            return pa.array(rng.integers(1, max(n_cust // 15, 2), n,
                                         dtype=np.int64))
        if name == "l_linenumber":
            return pa.array(rng.integers(1, 8, n, dtype=np.int64))
        raise KeyError(f"{table}.{name}")


def generate(scale: float, seed: int, columns: dict) -> dict:
    """``{"table": pyarrow.Table}`` holding exactly ``columns``."""
    maker = _Maker(scale, seed)
    return {t: pa.table({c: maker.column(t, c) for c in cols})
            for t, cols in columns.items()}
