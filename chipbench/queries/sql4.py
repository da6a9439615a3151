"""TPC-H Q4, order priority checking (clause 2.4.4), from its SQL text:
``EXISTS`` over LINEITEM becomes a semi join of 57k orders of a quarter
with the 6.0M-row fact table's late lines."""

import datetime

import pyarrow as pa
import pyarrow.compute as pc

COLUMNS = {
    "orders": ["o_orderkey", "o_orderdate", "o_orderpriority"],
    "lineitem": ["l_orderkey", "l_commitdate", "l_receiptdate"],
}

# validation parameters: DATE = 1993-07-01, three months
TEXT = """
SELECT o_orderpriority, COUNT(*) AS order_count
FROM orders
WHERE o_orderdate >= DATE '1993-07-01'
  AND o_orderdate < DATE '1993-10-01'
  AND EXISTS (
    SELECT * FROM lineitem
    WHERE l_orderkey = o_orderkey AND l_commitdate < l_receiptdate)
GROUP BY o_orderpriority
ORDER BY o_orderpriority
"""

floors = {"device_join_probes": 1, "device_aggregations": 1,
          "sql_subquery_joins_device": 1}


def build(frames):
    import daft_tpu as dt

    return dt.sql(TEXT, **frames)


def reference(tables) -> dict:
    orders, li = tables["orders"], tables["lineitem"]
    late = li.filter(pc.less(li["l_commitdate"], li["l_receiptdate"]))
    when = orders["o_orderdate"]
    o = orders.filter(pc.and_(
        pc.greater_equal(when, pa.scalar(datetime.date(1993, 7, 1))),
        pc.less(when, pa.scalar(datetime.date(1993, 10, 1)))))
    o = o.filter(pc.is_in(o["o_orderkey"],
                          value_set=pc.unique(late["l_orderkey"])))
    g = o.group_by("o_orderpriority").aggregate([([], "count_all")])
    g = g.sort_by("o_orderpriority")
    return {"o_orderpriority": g["o_orderpriority"].to_pylist(),
            "order_count": g["count_all"].to_pylist()}


def min_bytes(row_counts) -> int:
    return sum(4 * row_counts[t] * len(cols) for t, cols in COLUMNS.items())
