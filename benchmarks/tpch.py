"""TPC-H data generator + query definitions (daft_tpu + pyarrow oracle).

Role-equivalent to the reference's benchmarking/tpch/__main__.py +
tests/benchmarks/test_local_tpch.py: deterministic synthetic TPC-H-shaped
tables at a row-count scale, the daft_tpu implementations of Q1/Q3/Q5/Q6, and
pyarrow/numpy oracle implementations for result parity checks.

Not dbgen-exact data (no egress to fetch dbgen); distributions follow the spec
shapes so the queries exercise the same plan structure (filters, multi-key
groupby, 3-way join, decimal-ish arithmetic).
"""

from __future__ import annotations

import datetime
from typing import Dict

import numpy as np
import pyarrow as pa

LINEITEM_ROWS_PER_SF = 6_000_000
ORDERS_ROWS_PER_SF = 1_500_000
CUSTOMER_ROWS_PER_SF = 150_000

_EPOCH = datetime.date(1970, 1, 1)
_START = (datetime.date(1992, 1, 1) - _EPOCH).days
_END = (datetime.date(1998, 12, 1) - _EPOCH).days

MKT_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
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


def generate_tables(scale: float = 0.01, seed: int = 42) -> Dict[str, pa.Table]:
    """Generate lineitem/orders/customer/nation at `scale` of SF1 row counts."""
    rng = np.random.RandomState(seed)
    n_li = max(int(LINEITEM_ROWS_PER_SF * scale), 100)
    n_ord = max(int(ORDERS_ROWS_PER_SF * scale), 25)
    n_cust = max(int(CUSTOMER_ROWS_PER_SF * scale), 10)

    nation_names = list(NATIONS)
    nation = pa.table({
        "n_nationkey": pa.array(np.arange(len(nation_names)), pa.int64()),
        "n_name": pa.array(nation_names),
        "n_regionname": pa.array([NATIONS[n] for n in nation_names]),
    })

    cust_nation = rng.randint(0, len(nation_names), n_cust)
    customer = pa.table({
        "c_custkey": pa.array(np.arange(1, n_cust + 1), pa.int64()),
        "c_mktsegment": pa.array([MKT_SEGMENTS[i] for i in rng.randint(0, 5, n_cust)]),
        "c_nationkey": pa.array(cust_nation, pa.int64()),
        "c_acctbal": pa.array(np.round(rng.uniform(-999.99, 9999.99, n_cust), 2)),
    })

    o_orderdate = rng.randint(_START, _END - 151, n_ord)
    orders = pa.table({
        "o_orderkey": pa.array(np.arange(1, n_ord + 1), pa.int64()),
        "o_custkey": pa.array(rng.randint(1, n_cust + 1, n_ord), pa.int64()),
        "o_orderdate": pa.array(o_orderdate.astype("datetime64[D]")),
        "o_shippriority": pa.array(np.zeros(n_ord, dtype=np.int64)),
        "o_totalprice": pa.array(np.round(rng.uniform(850.0, 560000.0, n_ord), 2)),
        "o_orderstatus": pa.array([("F", "O", "P")[i] for i in rng.randint(0, 3, n_ord)]),
    })

    l_orderkey = rng.randint(1, n_ord + 1, n_li)
    order_date_of_line = o_orderdate[l_orderkey - 1]
    l_shipdate = order_date_of_line + rng.randint(1, 122, n_li)
    l_quantity = rng.randint(1, 51, n_li).astype(np.float64)
    l_extendedprice = np.round(rng.uniform(900.0, 105000.0, n_li), 2)
    l_discount = rng.randint(0, 11, n_li) / 100.0
    l_tax = rng.randint(0, 9, n_li) / 100.0
    flags = np.array(["A", "N", "R"])
    status = np.array(["F", "O"])
    lineitem = pa.table({
        "l_orderkey": pa.array(l_orderkey, pa.int64()),
        "l_partkey": pa.array(rng.randint(1, max(n_li // 30, 2), n_li), pa.int64()),
        "l_suppkey": pa.array(rng.randint(1, max(n_cust // 15, 2), n_li), pa.int64()),
        "l_linenumber": pa.array(rng.randint(1, 8, n_li), pa.int64()),
        "l_quantity": pa.array(l_quantity),
        "l_extendedprice": pa.array(l_extendedprice),
        "l_discount": pa.array(l_discount),
        "l_tax": pa.array(l_tax),
        "l_returnflag": pa.array(flags[rng.randint(0, 3, n_li)]),
        "l_linestatus": pa.array(status[rng.randint(0, 2, n_li)]),
        "l_shipdate": pa.array(l_shipdate.astype("datetime64[D]")),
    })
    # l_shipmode draws AFTER the table above so every earlier column keeps
    # its exact values (the rng stream is consumed in order; recorded
    # baselines must not shift)
    shipmodes = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL",
                          "FOB"])
    lineitem = lineitem.append_column(
        "l_shipmode", pa.array(shipmodes[rng.randint(0, 7, n_li)]))
    return {"lineitem": lineitem, "orders": orders, "customer": customer, "nation": nation}


def generate_lineitem_only(scale: float, seed: int = 42) -> pa.Table:
    """Just the lineitem columns Q1/Q6 touch — lets bench.py run the SF10
    no-shuffle rung without materializing the full star schema."""
    rng = np.random.RandomState(seed)
    n_li = max(int(LINEITEM_ROWS_PER_SF * scale), 100)
    l_shipdate = rng.randint(_START, _END, n_li)
    flags = np.array(["A", "N", "R"])
    status = np.array(["F", "O"])
    return pa.table({
        "l_quantity": pa.array(rng.randint(1, 51, n_li).astype(np.float64)),
        "l_extendedprice": pa.array(np.round(rng.uniform(900.0, 105000.0, n_li), 2)),
        "l_discount": pa.array(rng.randint(0, 11, n_li) / 100.0),
        "l_tax": pa.array(rng.randint(0, 9, n_li) / 100.0),
        "l_returnflag": pa.array(flags[rng.randint(0, 3, n_li)]),
        "l_linestatus": pa.array(status[rng.randint(0, 2, n_li)]),
        "l_shipdate": pa.array(l_shipdate.astype("datetime64[D]")),
    })


# ---------------------------------------------------------------------------
# daft_tpu query implementations
# ---------------------------------------------------------------------------

def q1(lineitem) -> "object":
    """TPC-H Q1: pricing summary report."""
    from daft_tpu import col

    disc_price = col("l_extendedprice") * (1 - col("l_discount"))
    charge = disc_price * (1 + col("l_tax"))
    return (
        lineitem
        .where(col("l_shipdate") <= datetime.date(1998, 9, 2))
        .groupby("l_returnflag", "l_linestatus")
        .agg(
            col("l_quantity").sum().alias("sum_qty"),
            col("l_extendedprice").sum().alias("sum_base_price"),
            disc_price.sum().alias("sum_disc_price"),
            charge.sum().alias("sum_charge"),
            col("l_quantity").mean().alias("avg_qty"),
            col("l_extendedprice").mean().alias("avg_price"),
            col("l_discount").mean().alias("avg_disc"),
            col("l_quantity").count().alias("count_order"),
        )
        .sort(["l_returnflag", "l_linestatus"])
    )


def q3(customer, orders, lineitem) -> "object":
    """TPC-H Q3: shipping priority (3-way join + agg + top-k)."""
    from daft_tpu import col

    cutoff = datetime.date(1995, 3, 15)
    c = customer.where(col("c_mktsegment") == "BUILDING")
    o = orders.where(col("o_orderdate") < cutoff)
    l = lineitem.where(col("l_shipdate") > cutoff)
    return (
        c.join(o, left_on="c_custkey", right_on="o_custkey")
        .join(l, left_on="o_orderkey", right_on="l_orderkey")
        .with_column("revenue", col("l_extendedprice") * (1 - col("l_discount")))
        .groupby("o_orderkey", "o_orderdate", "o_shippriority")
        .agg(col("revenue").sum().alias("revenue"))
        .select("o_orderkey", "revenue", "o_orderdate", "o_shippriority")
        .sort(["revenue", "o_orderdate"], desc=[True, False])
        .limit(10)
    )


def q5(customer, orders, lineitem, nation) -> "object":
    """TPC-H-shaped Q5 variant: revenue by nation for ASIA region in 1994
    (adapted to the generated star schema: customer.nation drives locality)."""
    from daft_tpu import col

    lo = datetime.date(1994, 1, 1)
    hi = datetime.date(1995, 1, 1)
    n = nation.where(col("n_regionname") == "ASIA")
    o = orders.where((col("o_orderdate") >= lo) & (col("o_orderdate") < hi))
    return (
        n.join(customer, left_on="n_nationkey", right_on="c_nationkey")
        .join(o, left_on="c_custkey", right_on="o_custkey")
        .join(lineitem, left_on="o_orderkey", right_on="l_orderkey")
        .with_column("revenue", col("l_extendedprice") * (1 - col("l_discount")))
        .groupby("n_name")
        .agg(col("revenue").sum().alias("revenue"))
        .sort("revenue", desc=True)
    )


def q12(lineitem) -> "object":
    """TPC-H Q12-shaped rung (adapted to the generated schema): string
    is_in + date-range filters feeding a string-keyed grouped aggregation —
    the device dictionary-code surface end to end (LUT filter, device group
    codes, fused segment aggs)."""
    from daft_tpu import col

    lo = datetime.date(1994, 1, 1)
    hi = datetime.date(1995, 1, 1)
    return (
        lineitem
        .where(col("l_shipmode").is_in(["MAIL", "SHIP"])
               & (col("l_shipdate") >= lo) & (col("l_shipdate") < hi))
        .groupby("l_shipmode")
        .agg(col("l_extendedprice").sum().alias("revenue"),
             col("l_quantity").count().alias("line_count"))
        .sort("l_shipmode")
    )


def oracle_q12(lineitem: pa.Table) -> dict:
    import pyarrow.compute as pc

    lo = datetime.date(1994, 1, 1)
    hi = datetime.date(1995, 1, 1)
    mask = pc.and_(
        pc.and_(pc.is_in(lineitem["l_shipmode"],
                         value_set=pa.array(["MAIL", "SHIP"])),
                pc.greater_equal(lineitem["l_shipdate"], pa.scalar(lo))),
        pc.less(lineitem["l_shipdate"], pa.scalar(hi)))
    t = lineitem.filter(mask)
    out = pa.TableGroupBy(t.select(["l_shipmode", "l_extendedprice",
                                    "l_quantity"]), "l_shipmode").aggregate(
        [("l_extendedprice", "sum"), ("l_quantity", "count")])
    order = pc.sort_indices(out["l_shipmode"])
    out = out.take(order)
    return {"l_shipmode": out["l_shipmode"].to_pylist(),
            "revenue": out["l_extendedprice_sum"].to_pylist(),
            "line_count": out["l_quantity_count"].to_pylist()}


def q6(lineitem) -> "object":
    """TPC-H Q6: forecasting revenue change (pure filter + reduce)."""
    from daft_tpu import col

    return (
        lineitem
        .where(
            (col("l_shipdate") >= datetime.date(1994, 1, 1))
            & (col("l_shipdate") < datetime.date(1995, 1, 1))
            & (col("l_discount") >= 0.05)
            & (col("l_discount") <= 0.07)
            & (col("l_quantity") < 24)
        )
        .agg((col("l_extendedprice") * col("l_discount")).sum().alias("revenue"))
    )


# ---------------------------------------------------------------------------
# pyarrow/numpy oracle implementations
# ---------------------------------------------------------------------------

def parity(got: dict, want: dict, rtol: float) -> bool:
    """Column-dict equality as bench.py and chip_smoke.py gate it: same
    columns and lengths, floats within ``rtol`` (absolute floor 1e-6),
    everything else exact."""
    if set(got) != set(want):
        return False
    for k in want:
        if len(got[k]) != len(want[k]):
            return False
        for a, b in zip(got[k], want[k]):
            if isinstance(b, float):
                if abs(a - b) > max(rtol * abs(b), 1e-6):
                    return False
            elif a != b:
                return False
    return True


def oracle_q1(lineitem: pa.Table) -> dict:
    import pyarrow.compute as pc

    cutoff = datetime.date(1998, 9, 2)
    t = lineitem.filter(pc.less_equal(lineitem["l_shipdate"], pa.scalar(cutoff)))
    price = t["l_extendedprice"]
    disc = t["l_discount"]
    disc_price = pc.multiply(price, pc.subtract(pa.scalar(1.0), disc))
    charge = pc.multiply(disc_price, pc.add(pa.scalar(1.0), t["l_tax"]))
    t = t.append_column("disc_price", disc_price).append_column("charge", charge)
    g = t.group_by(["l_returnflag", "l_linestatus"]).aggregate([
        ("l_quantity", "sum"), ("l_extendedprice", "sum"), ("disc_price", "sum"),
        ("charge", "sum"), ("l_quantity", "mean"), ("l_extendedprice", "mean"),
        ("l_discount", "mean"), ("l_quantity", "count"),
    ])
    g = g.sort_by([("l_returnflag", "ascending"), ("l_linestatus", "ascending")])
    return {
        "l_returnflag": g["l_returnflag"].to_pylist(),
        "l_linestatus": g["l_linestatus"].to_pylist(),
        "sum_qty": g["l_quantity_sum"].to_pylist(),
        "sum_base_price": g["l_extendedprice_sum"].to_pylist(),
        "sum_disc_price": g["disc_price_sum"].to_pylist(),
        "sum_charge": g["charge_sum"].to_pylist(),
        "avg_qty": g["l_quantity_mean"].to_pylist(),
        "avg_price": g["l_extendedprice_mean"].to_pylist(),
        "avg_disc": g["l_discount_mean"].to_pylist(),
        "count_order": g["l_quantity_count"].to_pylist(),
    }


def oracle_q3(customer: pa.Table, orders: pa.Table, lineitem: pa.Table) -> dict:
    import pyarrow.compute as pc

    cutoff = pa.scalar(datetime.date(1995, 3, 15))
    c = customer.filter(pc.equal(customer["c_mktsegment"], "BUILDING"))
    o = orders.filter(pc.less(orders["o_orderdate"], cutoff))
    l = lineitem.filter(pc.greater(lineitem["l_shipdate"], cutoff))
    co = c.join(o, keys="c_custkey", right_keys="o_custkey", join_type="inner")
    col_ = co.join(l, keys="o_orderkey", right_keys="l_orderkey", join_type="inner")
    revenue = pc.multiply(col_["l_extendedprice"],
                          pc.subtract(pa.scalar(1.0), col_["l_discount"]))
    col_ = col_.append_column("revenue", revenue)
    g = col_.group_by(["o_orderkey", "o_orderdate", "o_shippriority"]).aggregate(
        [("revenue", "sum")])
    g = g.sort_by([("revenue_sum", "descending"), ("o_orderdate", "ascending")])
    g = g.slice(0, 10)
    return {
        "o_orderkey": g["o_orderkey"].to_pylist(),
        "revenue": g["revenue_sum"].to_pylist(),
        "o_orderdate": g["o_orderdate"].to_pylist(),
        "o_shippriority": g["o_shippriority"].to_pylist(),
    }


def oracle_q5(customer, orders, lineitem, nation) -> dict:
    import pyarrow.compute as pc

    lo = pa.scalar(datetime.date(1994, 1, 1))
    hi = pa.scalar(datetime.date(1995, 1, 1))
    n = nation.filter(pc.equal(nation["n_regionname"], "ASIA"))
    o = orders.filter(pc.and_(pc.greater_equal(orders["o_orderdate"], lo),
                              pc.less(orders["o_orderdate"], hi)))
    nc = n.join(customer, keys="n_nationkey", right_keys="c_nationkey", join_type="inner")
    nco = nc.join(o, keys="c_custkey", right_keys="o_custkey", join_type="inner")
    ncol = nco.join(lineitem, keys="o_orderkey", right_keys="l_orderkey", join_type="inner")
    revenue = pc.multiply(ncol["l_extendedprice"],
                          pc.subtract(pa.scalar(1.0), ncol["l_discount"]))
    ncol = ncol.append_column("revenue", revenue)
    g = ncol.group_by(["n_name"]).aggregate([("revenue", "sum")])
    g = g.sort_by([("revenue_sum", "descending")])
    return {"n_name": g["n_name"].to_pylist(), "revenue": g["revenue_sum"].to_pylist()}


def oracle_q6(lineitem: pa.Table) -> float:
    import pyarrow.compute as pc

    lo = pa.scalar(datetime.date(1994, 1, 1))
    hi = pa.scalar(datetime.date(1995, 1, 1))
    m = pc.and_(
        pc.and_(
            pc.and_(pc.greater_equal(lineitem["l_shipdate"], lo),
                    pc.less(lineitem["l_shipdate"], hi)),
            pc.and_(pc.greater_equal(lineitem["l_discount"], 0.05),
                    pc.less_equal(lineitem["l_discount"], 0.07)),
        ),
        pc.less(lineitem["l_quantity"], 24),
    )
    t = lineitem.filter(m)
    return pc.sum(pc.multiply(t["l_extendedprice"], t["l_discount"])).as_py()
