"""TPC-H Q5 in the repo's form: nation x customer x orders x lineitem,
revenue by nation for ASIA in 1994 (customer.nation drives locality; the
generated schema has no supplier)."""

import datetime

import pyarrow as pa
import pyarrow.compute as pc

COLUMNS = {
    "nation": ["n_nationkey", "n_name", "n_regionname"],
    "customer": ["c_custkey", "c_nationkey"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "lineitem": ["l_orderkey", "l_extendedprice", "l_discount"],
}

# the final sort orders five nation rows on the host under any
# device_min_rows, so no device sort is asked for
floors = {"device_aggregations": 1, "device_resident_segments": 1,
          "device_join_probes": 1}

_LO = datetime.date(1994, 1, 1)
_HI = datetime.date(1995, 1, 1)


def build(frames):
    from daft_tpu import col

    n = frames["nation"].where(col("n_regionname") == "ASIA")
    o = frames["orders"].where((col("o_orderdate") >= _LO)
                               & (col("o_orderdate") < _HI))
    return (
        n.join(frames["customer"], left_on="n_nationkey",
               right_on="c_nationkey")
        .join(o, left_on="c_custkey", right_on="o_custkey")
        .join(frames["lineitem"], left_on="o_orderkey",
              right_on="l_orderkey")
        .with_column("revenue",
                     col("l_extendedprice") * (1 - col("l_discount")))
        .groupby("n_name")
        .agg(col("revenue").sum().alias("revenue"))
        .sort("revenue", desc=True)
    )


def reference(tables) -> dict:
    nation, customer = tables["nation"], tables["customer"]
    orders, li = tables["orders"], tables["lineitem"]
    lo, hi = pa.scalar(_LO), pa.scalar(_HI)
    n = nation.filter(pc.equal(nation["n_regionname"], "ASIA"))
    o = orders.filter(pc.and_(pc.greater_equal(orders["o_orderdate"], lo),
                              pc.less(orders["o_orderdate"], hi)))
    nc = n.join(customer, keys="n_nationkey", right_keys="c_nationkey",
                join_type="inner")
    nco = nc.join(o, keys="c_custkey", right_keys="o_custkey",
                  join_type="inner")
    j = nco.join(li.select(["l_orderkey", "l_extendedprice", "l_discount"]),
                 keys="o_orderkey", right_keys="l_orderkey",
                 join_type="inner")
    revenue = pc.multiply(j["l_extendedprice"],
                          pc.subtract(pa.scalar(1.0), j["l_discount"]))
    j = j.append_column("revenue", revenue)
    g = j.group_by(["n_name"]).aggregate([("revenue", "sum")])
    g = g.sort_by([("revenue_sum", "descending")])
    return {"n_name": g["n_name"].to_pylist(),
            "revenue": g["revenue_sum"].to_pylist()}


def min_bytes(row_counts) -> int:
    return sum(4 * row_counts[t] * len(cols) for t, cols in COLUMNS.items())
