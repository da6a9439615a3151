"""TPC-H Q6, forecasting revenue change: a five-term filter and one global
sum over four lineitem columns."""

import datetime

import pyarrow as pa
import pyarrow.compute as pc

COLUMNS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice"]}

floors = {"device_aggregations": 1}

_LO = datetime.date(1994, 1, 1)
_HI = datetime.date(1995, 1, 1)


def build(frames):
    from daft_tpu import col

    return (
        frames["lineitem"]
        .where(
            (col("l_shipdate") >= _LO)
            & (col("l_shipdate") < _HI)
            & (col("l_discount") >= 0.05)
            & (col("l_discount") <= 0.07)
            & (col("l_quantity") < 24)
        )
        .agg((col("l_extendedprice") * col("l_discount")).sum()
             .alias("revenue"))
    )


def reference(tables) -> dict:
    li = tables["lineitem"]
    m = pc.and_(
        pc.and_(
            pc.and_(pc.greater_equal(li["l_shipdate"], pa.scalar(_LO)),
                    pc.less(li["l_shipdate"], pa.scalar(_HI))),
            pc.and_(pc.greater_equal(li["l_discount"], 0.05),
                    pc.less_equal(li["l_discount"], 0.07)),
        ),
        pc.less(li["l_quantity"], 24),
    )
    t = li.filter(m)
    return {"revenue": [pc.sum(pc.multiply(t["l_extendedprice"],
                                           t["l_discount"])).as_py()]}


def min_bytes(row_counts) -> int:
    return sum(4 * row_counts[t] * len(cols) for t, cols in COLUMNS.items())
