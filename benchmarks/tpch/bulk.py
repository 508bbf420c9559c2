"""Numpy-vectorised TPC-H lineitem/orders at SF-scale row counts, and the
three query shapes bench.py and chip_smoke.py both drive over them.

`datagen.generate` builds python lists row by row (fine at SF0.001 for the
22-query tests, hours at SF1); these generators draw whole columns at once
so 6M lineitem rows take seconds.  Column widths are the published ones
(64-bit ints/doubles, 1-char flags); value distributions are bench.py's
since round 1, so its series stays comparable.
"""
from __future__ import annotations

import numpy as np
import pyarrow as pa

# 1994-01-01 / 1995-01-01 / 1995-03-15 / 1998-09-02 as days since epoch
D_1994, D_1995, D_19950315, D_19980902 = 8766, 9131, 9204, 10471


def make_lineitem(n: int, seed: int = 42, n_orders: int = 0) -> pa.Table:
    """Q6+Q1 lineitem: the 4 Q6 columns plus Q1's returnflag/linestatus/tax.
    `n_orders` > 0 appends l_orderkey drawn from [1, n_orders] AFTER the
    other columns, so the first seven are the same with or without it."""
    rng = np.random.RandomState(seed)
    price = rng.uniform(900.0, 105000.0, n)
    discount = rng.choice(np.arange(0.0, 0.11, 0.01), n)
    quantity = rng.randint(1, 51, n).astype(np.int64)
    shipdate = rng.randint(8035, 10592, n).astype(np.int64)
    returnflag = np.array(["A", "N", "R"])[rng.randint(0, 3, n)]
    linestatus = np.array(["F", "O"])[rng.randint(0, 2, n)]
    tax = np.round(rng.uniform(0.0, 0.08, n), 2)
    cols = {
        "l_extendedprice": price,
        "l_discount": discount,
        "l_quantity": quantity.astype(np.float64),
        "l_shipdate": shipdate,
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_tax": tax,
    }
    if n_orders > 0:
        cols["l_orderkey"] = rng.randint(1, n_orders + 1, n).astype(np.int64)
    return pa.table(cols)


def make_orders(n: int, seed: int = 42) -> pa.Table:
    """orders, ints and dates only: dense unique o_orderkey in [1, n]."""
    rng = np.random.RandomState(seed + 1)
    return pa.table({
        "o_orderkey": rng.permutation(n).astype(np.int64) + 1,
        "o_custkey": rng.randint(1, max(2, n // 10) + 1, n).astype(np.int64),
        "o_orderdate": rng.randint(8035, 10441, n).astype(np.int64),
        "o_shippriority": np.zeros(n, dtype=np.int64),
    })


def q6(df):
    from spark_rapids_tpu.plan.logical import col, functions as F
    return (df.filter((col("l_shipdate") >= D_1994)
                      & (col("l_shipdate") < D_1995)
                      & (col("l_discount") >= 0.05)
                      & (col("l_discount") <= 0.07)
                      & (col("l_quantity") < 24))
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def q1(df):
    from spark_rapids_tpu.plan.logical import col, functions as F, lit
    li = df.filter(col("l_shipdate") <= D_19980902)
    disc = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (li.group_by(col("l_returnflag"), col("l_linestatus"))
            .agg(F.sum(col("l_quantity")).alias("sum_qty"),
                 F.sum(col("l_extendedprice")).alias("sum_base_price"),
                 F.sum(disc).alias("sum_disc_price"),
                 F.sum(disc * (lit(1.0) + col("l_tax"))).alias("sum_charge"),
                 F.avg(col("l_quantity")).alias("avg_qty"),
                 F.avg(col("l_extendedprice")).alias("avg_price"),
                 F.avg(col("l_discount")).alias("avg_disc"),
                 F.count(lit(1)).alias("count_order"))
            .order_by("l_returnflag", "l_linestatus"))


def q3_shape(lineitem, orders):
    """q3 without customer: lineitem JOIN orders, grouped revenue, top 10."""
    from spark_rapids_tpu.plan.logical import (SortOrder, col,
                                               functions as F, lit)
    o = orders.filter(col("o_orderdate") < D_19950315)
    li = lineitem.filter(col("l_shipdate") > D_19950315)
    return (o.join(li, on=col("o_orderkey") == col("l_orderkey"))
            .group_by(col("l_orderkey"), col("o_orderdate"),
                      col("o_shippriority"))
            .agg(F.sum(col("l_extendedprice")
                       * (lit(1.0) - col("l_discount"))).alias("revenue"))
            .order_by(SortOrder(col("revenue"), ascending=False),
                      "o_orderdate")
            .limit(10))
