"""TPC-H Q12 in the repo's lineitem-only form: a string ``is_in`` and a date
range feeding a string-keyed grouped aggregate (dictionary codes on the
device, end to end)."""

import datetime

import pyarrow as pa
import pyarrow.compute as pc

COLUMNS = {"lineitem": ["l_shipmode", "l_shipdate", "l_extendedprice",
                        "l_quantity"]}

floors = {"device_aggregations": 1}

_LO = datetime.date(1994, 1, 1)
_HI = datetime.date(1995, 1, 1)


def build(frames):
    from daft_tpu import col

    return (
        frames["lineitem"]
        .where(col("l_shipmode").is_in(["MAIL", "SHIP"])
               & (col("l_shipdate") >= _LO) & (col("l_shipdate") < _HI))
        .groupby("l_shipmode")
        .agg(col("l_extendedprice").sum().alias("revenue"),
             col("l_quantity").count().alias("line_count"))
        .sort("l_shipmode")
    )


def reference(tables) -> dict:
    li = tables["lineitem"]
    mask = pc.and_(
        pc.and_(pc.is_in(li["l_shipmode"],
                         value_set=pa.array(["MAIL", "SHIP"])),
                pc.greater_equal(li["l_shipdate"], pa.scalar(_LO))),
        pc.less(li["l_shipdate"], pa.scalar(_HI)))
    t = li.filter(mask).select(["l_shipmode", "l_extendedprice",
                                "l_quantity"])
    out = t.group_by("l_shipmode").aggregate(
        [("l_extendedprice", "sum"), ("l_quantity", "count")])
    out = out.sort_by([("l_shipmode", "ascending")])
    return {"l_shipmode": out["l_shipmode"].to_pylist(),
            "revenue": out["l_extendedprice_sum"].to_pylist(),
            "line_count": out["l_quantity_count"].to_pylist()}


def min_bytes(row_counts) -> int:
    return sum(4 * row_counts[t] * len(cols) for t, cols in COLUMNS.items())
