"""TPC-H Q18, large volume customer (clause 2.4.18), from its SQL text:
``IN (SELECT ... GROUP BY ... HAVING ...)`` becomes sums over 1.5M order
keys and a semi join; then three tables joined, grouped, the top 100."""

import pyarrow as pa
import pyarrow.compute as pc

COLUMNS = {
    "customer": ["c_custkey", "c_name"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
    "lineitem": ["l_orderkey", "l_quantity"],
}

# validation parameter: QUANTITY = 300
TEXT = """
SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
       SUM(l_quantity) AS sum_qty
FROM customer, orders, lineitem
WHERE o_orderkey IN (
    SELECT l_orderkey FROM lineitem
    GROUP BY l_orderkey HAVING SUM(l_quantity) > 300)
  AND c_custkey = o_custkey
  AND o_orderkey = l_orderkey
GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
ORDER BY o_totalprice DESC, o_orderdate
LIMIT 100
"""

floors = {"device_join_probes": 1, "device_aggregations": 1,
          "sql_subquery_joins_device": 1}


def build(frames):
    import daft_tpu as dt

    return dt.sql(TEXT, **frames)


def reference(tables) -> dict:
    customer, orders = tables["customer"], tables["orders"]
    li = tables["lineitem"]
    qty = li.group_by("l_orderkey").aggregate([("l_quantity", "sum")])
    big = qty.filter(pc.greater(qty["l_quantity_sum"], pa.scalar(300.0)))
    o = orders.join(big, keys="o_orderkey", right_keys="l_orderkey",
                    join_type="inner")
    j = o.join(customer, keys="o_custkey", right_keys="c_custkey",
               join_type="inner")
    # one row an order (o_orderkey is the key of ORDERS): the group's sum
    # is the order's, already there
    j = j.sort_by([("o_totalprice", "descending"),
                   ("o_orderdate", "ascending")]).slice(0, 100)
    return {"c_name": j["c_name"].to_pylist(),
            "c_custkey": j["o_custkey"].to_pylist(),
            "o_orderkey": j["o_orderkey"].to_pylist(),
            "o_orderdate": j["o_orderdate"].to_pylist(),
            "o_totalprice": j["o_totalprice"].to_pylist(),
            "sum_qty": j["l_quantity_sum"].to_pylist()}


def min_bytes(row_counts) -> int:
    # LINEITEM is read twice: by the sums and by the join
    return sum(4 * row_counts[t] * len(cols) for t, cols in COLUMNS.items()
               ) + 4 * row_counts["lineitem"] * 2
