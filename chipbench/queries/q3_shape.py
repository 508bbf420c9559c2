"""The TPC-H Q3 shape without CUSTOMER: ORDERS joined to LINEITEM on the
order key, revenue grouped by order, the ten largest.  The DataFrame is
`benchmarks/tpch/bulk.py`'s `q3_shape`, copied; the reference is a pyarrow
join, `group_by`, sort and head."""
import pyarrow.compute as pc

D_19950315 = 9204   # 1995-03-15 as days since epoch

TABLES = {"lineitem": ["l_extendedprice", "l_discount", "l_shipdate",
                       "l_orderkey"],
          "orders": ["o_orderkey", "o_orderdate", "o_shippriority"]}


def build(session, frames):
    from spark_rapids_tpu.plan.logical import (SortOrder, col,
                                               functions as F, lit)
    o = frames["orders"].filter(col("o_orderdate") < D_19950315)
    li = frames["lineitem"].filter(col("l_shipdate") > D_19950315)
    return (o.join(li, on=col("o_orderkey") == col("l_orderkey"))
            .group_by(col("l_orderkey"), col("o_orderdate"),
                      col("o_shippriority"))
            .agg(F.sum(col("l_extendedprice")
                       * (lit(1.0) - col("l_discount"))).alias("revenue"))
            .order_by(SortOrder(col("revenue"), ascending=False),
                      "o_orderdate")
            .limit(10))


def reference(tables):
    o = tables["orders"].select(TABLES["orders"])
    o = o.filter(pc.less(o["o_orderdate"], D_19950315))
    li = tables["lineitem"].select(TABLES["lineitem"])
    li = li.filter(pc.greater(li["l_shipdate"], D_19950315))
    li = li.append_column(
        "rev", pc.multiply(li["l_extendedprice"],
                           pc.subtract(1.0, li["l_discount"])))
    j = o.join(li.select(["l_orderkey", "rev"]), keys="o_orderkey",
               right_keys="l_orderkey", join_type="inner")
    g = j.group_by(["o_orderkey", "o_orderdate", "o_shippriority"]
                   ).aggregate([("rev", "sum")])
    g = g.sort_by([("rev_sum", "descending"), ("o_orderdate", "ascending")]
                  ).slice(0, 10)
    order = ["o_orderkey", "o_orderdate", "o_shippriority", "rev_sum"]
    return [tuple(r[c] for c in order) for r in g.to_pylist()]


def bytes_needed(rows):
    """Four 8-byte columns of lineitem and three of orders, each once."""
    return rows["lineitem"] * 4 * 8 + rows["orders"] * 3 * 8
