"""TPC-H Q22, global sales opportunity (clause 2.4.22), from its SQL text:
an uncorrelated ``(SELECT AVG(c_acctbal) ...)`` becomes a one-row frame
cross-joined inside the plan, ``NOT EXISTS`` over ORDERS an anti join; the
country code is a substring of ``c_phone``, 150k distinct strings."""

import pyarrow as pa
import pyarrow.compute as pc

COLUMNS = {
    "customer": ["c_custkey", "c_phone", "c_acctbal"],
    "orders": ["o_custkey"],
}

# validation parameters: I1..I7 = 13, 31, 23, 29, 30, 18, 17
TEXT = """
SELECT cntrycode, COUNT(*) AS numcust, SUM(c_acctbal) AS totacctbal
FROM (
  SELECT SUBSTRING(c_phone, 1, 2) AS cntrycode, c_acctbal
  FROM customer
  WHERE SUBSTRING(c_phone, 1, 2) IN ('13', '31', '23', '29', '30', '18', '17')
    AND c_acctbal > (
      SELECT AVG(c_acctbal) FROM customer
      WHERE c_acctbal > 0.00
        AND SUBSTRING(c_phone, 1, 2) IN
            ('13', '31', '23', '29', '30', '18', '17'))
    AND NOT EXISTS (
      SELECT * FROM orders WHERE o_custkey = c_custkey)
) AS custsale
GROUP BY cntrycode
ORDER BY cntrycode
"""

floors = {"device_join_probes": 1, "device_aggregations": 1,
          "sql_subquery_joins_device": 1, "sql_scalar_subqueries": 1}

_CODES = ["13", "31", "23", "29", "30", "18", "17"]


def build(frames):
    import daft_tpu as dt

    return dt.sql(TEXT, **frames)


def reference(tables) -> dict:
    customer, orders = tables["customer"], tables["orders"]
    code = pc.utf8_slice_codeunits(customer["c_phone"], 0, 2)
    c = customer.append_column("cntrycode", code)
    c = c.filter(pc.is_in(c["cntrycode"], value_set=pa.array(_CODES)))
    rich = c.filter(pc.greater(c["c_acctbal"], pa.scalar(0.0)))
    average = pc.mean(rich["c_acctbal"])
    if average.as_py() is None:  # no such customer: the comparison is NULL
        c = c.slice(0, 0)
    else:
        c = c.filter(pc.greater(c["c_acctbal"], average))
    c = c.filter(pc.invert(pc.is_in(
        c["c_custkey"], value_set=pc.unique(orders["o_custkey"]))))
    g = c.group_by("cntrycode").aggregate(
        [([], "count_all"), ("c_acctbal", "sum")]).sort_by("cntrycode")
    return {"cntrycode": g["cntrycode"].to_pylist(),
            "numcust": g["count_all"].to_pylist(),
            "totacctbal": g["c_acctbal_sum"].to_pylist()}


def min_bytes(row_counts) -> int:
    # CUSTOMER's phone and balance are read twice: by the outer query and
    # by the average
    return sum(4 * row_counts[t] * len(cols) for t, cols in COLUMNS.items()
               ) + 4 * row_counts["customer"] * 2
