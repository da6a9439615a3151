"""TPC-H Q1, pricing summary report: one filter and a two-key grouped
aggregate over seven lineitem columns."""

import datetime

import pyarrow as pa
import pyarrow.compute as pc

COLUMNS = {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                        "l_quantity", "l_extendedprice", "l_discount",
                        "l_tax"]}

# device counters a run on the device path cannot read less than
floors = {"device_aggregations": 1}

_CUTOFF = datetime.date(1998, 9, 2)


def build(frames):
    from daft_tpu import col

    disc_price = col("l_extendedprice") * (1 - col("l_discount"))
    charge = disc_price * (1 + col("l_tax"))
    return (
        frames["lineitem"]
        .where(col("l_shipdate") <= _CUTOFF)
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


def reference(tables) -> dict:
    li = tables["lineitem"]
    t = li.filter(pc.less_equal(li["l_shipdate"], pa.scalar(_CUTOFF)))
    disc_price = pc.multiply(t["l_extendedprice"],
                             pc.subtract(pa.scalar(1.0), t["l_discount"]))
    charge = pc.multiply(disc_price, pc.add(pa.scalar(1.0), t["l_tax"]))
    t = (t.append_column("disc_price", disc_price)
         .append_column("charge", charge))
    g = t.group_by(["l_returnflag", "l_linestatus"]).aggregate([
        ("l_quantity", "sum"), ("l_extendedprice", "sum"),
        ("disc_price", "sum"), ("charge", "sum"), ("l_quantity", "mean"),
        ("l_extendedprice", "mean"), ("l_discount", "mean"),
        ("l_quantity", "count"),
    ])
    g = g.sort_by([("l_returnflag", "ascending"),
                   ("l_linestatus", "ascending")])
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


def min_bytes(row_counts) -> int:
    """The least the query must read: each column once, at 32-bit width."""
    return sum(4 * row_counts[t] * len(cols) for t, cols in COLUMNS.items())
