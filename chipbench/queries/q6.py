"""TPC-H Q6 (forecasting revenue change): one scan, a five-term filter and
a single sum; a 1-row answer.  The DataFrame is `benchmarks/tpch/bulk.py`'s
`q6`, copied; the reference is a numpy mask and sum."""
import numpy as np

D_1994, D_1995 = 8766, 9131   # 1994-01-01, 1995-01-01 as days since epoch

TABLES = {"lineitem": ["l_extendedprice", "l_discount", "l_quantity",
                       "l_shipdate"]}


def build(session, frames):
    from spark_rapids_tpu.plan.logical import col, functions as F
    df = frames["lineitem"]
    return (df.filter((col("l_shipdate") >= D_1994)
                      & (col("l_shipdate") < D_1995)
                      & (col("l_discount") >= 0.05)
                      & (col("l_discount") <= 0.07)
                      & (col("l_quantity") < 24))
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def reference(tables):
    li = {c: tables["lineitem"][c].to_numpy() for c in TABLES["lineitem"]}
    keep = ((li["l_shipdate"] >= D_1994) & (li["l_shipdate"] < D_1995)
            & (li["l_discount"] >= 0.05) & (li["l_discount"] <= 0.07)
            & (li["l_quantity"] < 24))
    revenue = li["l_extendedprice"][keep] * li["l_discount"][keep]
    return [(float(revenue.sum()) if keep.any() else None,)]


def bytes_needed(rows):
    """Bytes the query has to read: four 8-byte columns of every row."""
    return rows["lineitem"] * 4 * 8
