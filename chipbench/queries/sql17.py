"""TPC-H Q17, small-quantity-order revenue (clause 2.4.17), from its SQL
text: the correlated ``(SELECT 0.2 * AVG(l_quantity) ...)`` becomes an
average over 200k part keys, joined back and compared."""

import pyarrow as pa
import pyarrow.compute as pc

COLUMNS = {
    "lineitem": ["l_partkey", "l_quantity", "l_extendedprice"],
    "part": ["p_partkey", "p_brand", "p_container"],
}

# validation parameters: BRAND = Brand#23, CONTAINER = MED BOX
TEXT = """
SELECT SUM(l_extendedprice) / 7.0 AS avg_yearly
FROM lineitem, part
WHERE p_partkey = l_partkey
  AND p_brand = 'Brand#23'
  AND p_container = 'MED BOX'
  AND l_quantity < (
    SELECT 0.2 * AVG(l_quantity) FROM lineitem
    WHERE l_partkey = p_partkey)
"""

floors = {"device_join_probes": 1, "device_aggregations": 1,
          "sql_subquery_joins_device": 1, "sql_scalar_subqueries": 1}


def build(frames):
    import daft_tpu as dt

    return dt.sql(TEXT, **frames)


def reference(tables) -> dict:
    li, part = tables["lineitem"], tables["part"]
    p = part.filter(pc.and_(pc.equal(part["p_brand"], "Brand#23"),
                            pc.equal(part["p_container"], "MED BOX")))
    j = li.filter(pc.is_in(li["l_partkey"], value_set=p["p_partkey"]))
    # each of those parts' average quantity, over all of its lines
    avg = j.group_by("l_partkey").aggregate([("l_quantity", "mean")])
    j = j.join(avg, keys="l_partkey", join_type="inner")
    small = j.filter(pc.less(
        j["l_quantity"], pc.multiply(pa.scalar(0.2), j["l_quantity_mean"])))
    total = pc.sum(small["l_extendedprice"]).as_py()
    return {"avg_yearly": [None if total is None else total / 7.0]}


def min_bytes(row_counts) -> int:
    # LINEITEM is read twice: by the outer query and by the average
    return sum(4 * row_counts[t] * len(cols) for t, cols in COLUMNS.items()
               ) + 4 * row_counts["lineitem"] * 2
