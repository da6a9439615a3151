"""Seeded TPC-H-shaped tables for the queries that come as SQL text.

Every column ``chipbench/datasets/tpch.py`` makes is taken from it (same
seed, same values; its file is loaded from beside this one, nothing of the
program is imported). This file adds, each from a stream of its own:

- ``o_orderpriority`` (the specification's five values),
  ``l_commitdate`` (order date + 30..90 days), ``l_receiptdate`` (ship date
  + 1..30 days), ``c_name`` (``Customer#%09d``), ``c_phone``
  (``CC-ddd-ddd-dddd`` with CC = nation key + 10, as clause 4.2.2.9 has it);
- PART at 200,000 x scale rows: ``p_partkey``, ``p_brand`` (``Brand#MN``,
  25 values), ``p_container`` (the specification's 40);
- an ``o_custkey`` of its own: never a multiple of 3 (clause 4.2.3: a third
  of the customers have no order), so that Q22's ``NOT EXISTS`` keeps a
  third of the customers as in TPC-H and not seven of 150,000.

``generate(scale, seed, columns)`` as in ``tpch.py``.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np
import pyarrow as pa

_spec = importlib.util.spec_from_file_location(
    "chipbench_datasets_tpch",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpch)

PART_ROWS_PER_SF = 200_000
PART_MIN_ROWS = 10

PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
BRANDS = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]

# stream ids of this file's columns: apart from tpch.py's (0..18), fixed for
# good, a new column takes a new id
_STREAM = {"o_orderpriority": 100, "l_commitdate": 101, "l_receiptdate": 102,
           "c_phone": 103, "p_brand": 104, "p_container": 105,
           "o_custkey": 106}


def row_counts(scale: float) -> dict:
    n = tpch.row_counts(scale)
    n["part"] = max(int(PART_ROWS_PER_SF * scale), PART_MIN_ROWS)
    return n


class _Maker:
    def __init__(self, scale: float, seed: int):
        self.base = tpch._Maker(scale, seed)
        self.n = row_counts(scale)

    def rng(self, column: str) -> np.random.Generator:
        return np.random.default_rng([self.base.seed, _STREAM[column]])

    def column(self, table: str, name: str) -> pa.Array:
        n = self.n[table]
        if name == "p_partkey":
            return pa.array(np.arange(1, n + 1, dtype=np.int64))
        if name == "c_name":
            keys = np.arange(1, n + 1)
            return pa.array(np.char.add("Customer#",
                                        np.char.zfill(keys.astype(str), 9)))
        if name not in _STREAM:
            return self.base.column(table, name)
        rng = self.rng(name)
        if name == "o_orderpriority":
            return tpch._strings(rng.integers(0, 5, n, dtype=np.int8),
                                 PRIORITIES)
        if name == "p_brand":
            return tpch._strings(rng.integers(0, 25, n, dtype=np.int8),
                                 BRANDS)
        if name == "p_container":
            return tpch._strings(rng.integers(0, 40, n, dtype=np.int8),
                                 CONTAINERS)
        if name == "o_custkey":
            # the k-th customer key that 3 does not divide: 1, 2, 4, 5, 7 ...
            n_cust = self.n["customer"]
            k = rng.integers(0, n_cust - n_cust // 3, n, dtype=np.int64)
            return pa.array(3 * (k // 2) + k % 2 + 1)
        if name == "l_commitdate":
            days = self.base.raw("o_orderdate")[
                self.base.raw("l_orderkey") - 1]
            days += rng.integers(30, 91, n, dtype=np.int8)
            return tpch._dates(days)
        if name == "l_receiptdate":
            days = self.base.column("lineitem", "l_shipdate").cast(
                pa.int32()).to_numpy(zero_copy_only=False)
            return tpch._dates(days + rng.integers(1, 31, n, dtype=np.int8))
        if name == "c_phone":
            nation = self.base.column("customer", "c_nationkey").to_numpy()
            local = rng.integers(0, 10, (n, 10), dtype=np.int8)
            digits = (local + ord("0")).astype(np.uint8)
            out = np.empty((n, 15), dtype=np.uint8)
            cc = nation + 10
            out[:, 0] = cc // 10 + ord("0")
            out[:, 1] = cc % 10 + ord("0")
            out[:, (2, 6, 10)] = ord("-")
            out[:, 3:6] = digits[:, 0:3]
            out[:, 7:10] = digits[:, 3:6]
            out[:, 11:15] = digits[:, 6:10]
            return pa.array(out.view("S15").ravel().astype(str))
        raise KeyError(f"{table}.{name}")


def generate(scale: float, seed: int, columns: dict) -> dict:
    """``{"table": pyarrow.Table}`` holding exactly ``columns``."""
    maker = _Maker(scale, seed)
    return {t: pa.table({c: maker.column(t, c) for c in cols})
            for t, cols in columns.items()}
