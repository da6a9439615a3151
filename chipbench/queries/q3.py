"""TPC-H Q3, shipping priority: customer x orders x lineitem, a three-key
grouped sum, top 10 by revenue."""

import datetime

import pyarrow as pa
import pyarrow.compute as pc

COLUMNS = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_shipdate", "l_extendedprice",
                 "l_discount"],
}

floors = {"device_aggregations": 1, "device_resident_segments": 1,
          "device_join_probes": 1, "device_sorts": 1}

_CUTOFF = datetime.date(1995, 3, 15)


def build(frames):
    from daft_tpu import col

    c = frames["customer"].where(col("c_mktsegment") == "BUILDING")
    o = frames["orders"].where(col("o_orderdate") < _CUTOFF)
    li = frames["lineitem"].where(col("l_shipdate") > _CUTOFF)
    return (
        c.join(o, left_on="c_custkey", right_on="o_custkey")
        .join(li, left_on="o_orderkey", right_on="l_orderkey")
        .with_column("revenue",
                     col("l_extendedprice") * (1 - col("l_discount")))
        .groupby("o_orderkey", "o_orderdate", "o_shippriority")
        .agg(col("revenue").sum().alias("revenue"))
        .select("o_orderkey", "revenue", "o_orderdate", "o_shippriority")
        .sort(["revenue", "o_orderdate"], desc=[True, False])
        .limit(10)
    )


def reference(tables) -> dict:
    customer, orders = tables["customer"], tables["orders"]
    li = tables["lineitem"]
    cutoff = pa.scalar(_CUTOFF)
    c = customer.filter(pc.equal(customer["c_mktsegment"], "BUILDING"))
    o = orders.filter(pc.less(orders["o_orderdate"], cutoff))
    li = li.filter(pc.greater(li["l_shipdate"], cutoff))
    co = c.join(o, keys="c_custkey", right_keys="o_custkey",
                join_type="inner")
    j = co.join(li, keys="o_orderkey", right_keys="l_orderkey",
                join_type="inner")
    revenue = pc.multiply(j["l_extendedprice"],
                          pc.subtract(pa.scalar(1.0), j["l_discount"]))
    j = j.append_column("revenue", revenue)
    g = j.group_by(["o_orderkey", "o_orderdate", "o_shippriority"]
                   ).aggregate([("revenue", "sum")])
    g = g.sort_by([("revenue_sum", "descending"),
                   ("o_orderdate", "ascending")]).slice(0, 10)
    return {
        "o_orderkey": g["o_orderkey"].to_pylist(),
        "revenue": g["revenue_sum"].to_pylist(),
        "o_orderdate": g["o_orderdate"].to_pylist(),
        "o_shippriority": g["o_shippriority"].to_pylist(),
    }


def min_bytes(row_counts) -> int:
    return sum(4 * row_counts[t] * len(cols) for t, cols in COLUMNS.items())
