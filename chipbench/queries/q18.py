"""TPC-H query 18 (Large Volume Customer, clause 2.4.18), as published:

    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem
                         group by l_orderkey
                         having sum(l_quantity) > [QUANTITY])
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate
    limit 100

Departures from the template, all of them: the `IN (subquery)` is a
`left_semi` join of the orders on the subquery's keys, which is Spark's own
rewrite (RewritePredicateSubquery); the comma joins are inner joins in the
template's order, CUSTOMER then ORDERS then LINEITEM, with the semi join
placed on the orders before LINEITEM joins (the answer is the same: the
predicate reads ORDERS alone); `[QUANTITY]` is 300, the qualification
value; the last column is named `sum_qty`.  `benchmarks/tpch/queries.py`'s
`q18` is an equivalent rewrite (an inner join to the big orders, the
quantity carried from the subquery), not this form.

The reference is independent of the engine and of semi joins: a pyarrow
`group_by` and filter for the big orders, `pc.is_in` on ORDERS, two pyarrow
inner joins, a five-key `group_by`, a Python sort and the first 100 rows.
"""
import pyarrow as pa
import pyarrow.compute as pc

TABLES = {"customer": ["c_custkey", "c_name"],
          "orders_priced": ["o_orderkey", "o_custkey", "o_orderdate",
                            "o_totalprice"],
          "lineitem_clustered": ["l_orderkey", "l_quantity"]}

QUANTITY = 300
GROUP = ["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"]


def build(session, frames, quantity=QUANTITY):
    from spark_rapids_tpu.plan.logical import col, functions as F
    li = frames["lineitem_clustered"]
    big = (li.group_by(col("l_orderkey"))
           .agg(F.sum(col("l_quantity")).alias("sum_qty"))
           .filter(col("sum_qty") > quantity)
           .select(col("l_orderkey").alias("big_key")))
    return (frames["customer"]
            .join(frames["orders_priced"],
                  on=col("c_custkey") == col("o_custkey"))
            .join(big, on=col("o_orderkey") == col("big_key"),
                  how="left_semi")
            .join(li, on=col("o_orderkey") == col("l_orderkey"))
            .group_by(*(col(c) for c in GROUP))
            .agg(F.sum(col("l_quantity")).alias("sum_qty"))
            .order_by(col("o_totalprice").desc(), col("o_orderdate"))
            .limit(100))


def reference(tables, quantity=QUANTITY):
    li = tables["lineitem_clustered"].select(TABLES["lineitem_clustered"])
    sums = li.group_by("l_orderkey").aggregate([("l_quantity", "sum")])
    big = sums.filter(pc.greater(sums["l_quantity_sum"], quantity))
    o = tables["orders_priced"].select(TABLES["orders_priced"])
    o = o.filter(pc.is_in(o["o_orderkey"],
                          value_set=pa.array(big["l_orderkey"])))
    c = tables["customer"].select(TABLES["customer"])
    j = c.join(o, keys="c_custkey", right_keys="o_custkey",
               join_type="inner")
    j = j.join(li, keys="o_orderkey", right_keys="l_orderkey",
               join_type="inner")
    g = j.group_by(GROUP).aggregate([("l_quantity", "sum")])
    rows = [tuple(r[k] for k in GROUP + ["l_quantity_sum"])
            for r in g.to_pylist()]
    rows.sort(key=lambda r: (-r[4], r[3]))
    prices = [r[4] for r in rows[:101]]
    # the order of the rows is decided: no two orders tie on the price
    assert len(set(prices)) == len(prices), "o_totalprice ties"
    return rows[:100]


def bytes_needed(rows):
    """LINEITEM's two 8-byte columns read twice (the subquery's aggregate
    and the last join), ORDERS' four once, CUSTOMER's key and 18-byte name
    once."""
    return (rows["lineitem_clustered"] * 2 * 8 * 2
            + rows["orders_priced"] * 4 * 8 + rows["customer"] * (8 + 18))


def agg_sort_bytes_needed(rows):
    """What the grouped sort program over LINEITEM must move at least: its
    input read once (`l_orderkey`, `l_quantity`) and one output row a group
    written once (the key and the sum; every order has a line)."""
    return rows["lineitem_clustered"] * 16 + rows["orders_priced"] * 16
