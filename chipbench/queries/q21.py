"""TPC-H query 21 (Suppliers Who Kept Orders Waiting, clause 2.4.21), as
published:

    select s_name, count(*) as numwait
    from supplier, lineitem l1, orders, nation
    where s_suppkey = l1.l_suppkey
      and o_orderkey = l1.l_orderkey
      and o_orderstatus = 'F'
      and l1.l_receiptdate > l1.l_commitdate
      and exists (select * from lineitem l2
                  where l2.l_orderkey = l1.l_orderkey
                    and l2.l_suppkey <> l1.l_suppkey)
      and not exists (select * from lineitem l3
                      where l3.l_orderkey = l1.l_orderkey
                        and l3.l_suppkey <> l1.l_suppkey
                        and l3.l_receiptdate > l3.l_commitdate)
      and s_nationkey = n_nationkey
      and n_name = '[NATION]'
    group by s_name
    order by numwait desc, s_name
    limit 100

Departures from the template, all of them: `EXISTS` is a `left_semi` join
and `NOT EXISTS` a `left_anti` join of the late lines `l1` with LINEITEM,
each on the order key with the supplier inequality as a residual
condition, which is Spark's own rewrite (RewritePredicateSubquery); both
sit on `l1` below the other joins, where Spark 3's
PushLeftSemiLeftAntiThroughJoin places them (the answer is the same: the
subqueries read `l1` alone); `l3`'s own lateness filter is applied before
its join, as Spark pushes a filter that reads the subquery alone; the comma
joins are inner joins in the template's order, SUPPLIER, then ORDERS cut to
status 'F', then NATION cut to `[NATION]`; `l2` and `l3` carry their two
columns under names of their own (`l2_orderkey`, `l3_suppkey`, ...);
`count(*)` is `count(1)`; `[NATION]` is 'SAUDI ARABIA', the qualification
value.  `benchmarks/tpch/queries.py`'s `q21` is an equivalent rewrite (two
counts of distinct suppliers an order, no semi or anti join), not this
form.

The reference is independent of the engine and of semi and anti joins:
per order, numpy counts the distinct suppliers and the distinct LATE
suppliers; a late line qualifies iff its order has at least two distinct
suppliers and exactly one distinct late supplier (its own).  Then pyarrow
filters and joins, `group_by` counts, a Python sort orders by `numwait`
descending and `s_name` (unique, so the order is decided), and the first
100 rows are kept.  Every value is an integer or a string: the comparison
is exact.
"""
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

TABLES = {"lineitem_supplied": ["l_orderkey", "l_suppkey", "l_commitdate",
                                "l_receiptdate"],
          "orders_status": ["o_orderkey", "o_orderstatus"],
          "supplier": ["s_suppkey", "s_name", "s_nationkey"],
          "nation": ["n_nationkey", "n_name"]}

NATION = "SAUDI ARABIA"

#: share of lines received after their commit date: P(U[1,121] + U[1,30]
#: > U[30,90]) over the three uniform day offsets of clause 4.2.3
LATE_SHARE = 139_995 / 221_430
#: share of lines in orders of two or more lines (1 to 7 lines an order,
#: uniform): 27 of every 28 lines
MULTI_LINE_SHARE = 27 / 28


def build(session, frames, nation=NATION):
    from spark_rapids_tpu.plan.logical import col, functions as F, lit
    li = frames["lineitem_supplied"]
    late = col("l_receiptdate") > col("l_commitdate")
    l1 = li.filter(late)
    l2 = li.select(col("l_orderkey").alias("l2_orderkey"),
                   col("l_suppkey").alias("l2_suppkey"))
    l3 = li.filter(late).select(col("l_orderkey").alias("l3_orderkey"),
                                col("l_suppkey").alias("l3_suppkey"))
    orders = frames["orders_status"].filter(col("o_orderstatus") == "F")
    nations = frames["nation"].filter(col("n_name") == nation)
    return (l1
            .join(l2, on=(col("l_orderkey") == col("l2_orderkey"))
                  & (col("l2_suppkey") != col("l_suppkey")),
                  how="left_semi")
            .join(l3, on=(col("l_orderkey") == col("l3_orderkey"))
                  & (col("l3_suppkey") != col("l_suppkey")),
                  how="left_anti")
            .join(frames["supplier"],
                  on=col("s_suppkey") == col("l_suppkey"))
            .join(orders, on=col("o_orderkey") == col("l_orderkey"))
            .join(nations, on=col("s_nationkey") == col("n_nationkey"))
            .group_by(col("s_name"))
            .agg(F.count(lit(1)).alias("numwait"))
            .order_by(col("numwait").desc(), col("s_name"))
            .limit(100))


def _distinct_per_order(order, supp, n_orders):
    """Distinct suppliers of each order (`order` an index 0..n_orders-1)."""
    width = int(supp.max(initial=0)) + 1
    pairs = np.unique(order * width + supp)
    return np.bincount(pairs // width, minlength=n_orders)


def reference(tables, nation=NATION):
    li = tables["lineitem_supplied"]
    okey = li["l_orderkey"].to_numpy()
    supp = li["l_suppkey"].to_numpy()
    late = li["l_receiptdate"].to_numpy() > li["l_commitdate"].to_numpy()
    keys, order = np.unique(okey, return_inverse=True)
    suppliers = _distinct_per_order(order, supp, len(keys))
    late_suppliers = _distinct_per_order(order[late], supp[late], len(keys))
    blamed = late & (suppliers[order] >= 2) & (late_suppliers[order] == 1)
    l1 = pa.table({"l_orderkey": okey[blamed], "l_suppkey": supp[blamed]})
    o = tables["orders_status"].select(TABLES["orders_status"])
    o = o.filter(pc.equal(o["o_orderstatus"], "F"))
    s = tables["supplier"].select(TABLES["supplier"])
    n = tables["nation"].select(TABLES["nation"])
    n = n.filter(pc.equal(n["n_name"], nation))
    j = l1.join(s, keys="l_suppkey", right_keys="s_suppkey",
                join_type="inner")
    j = j.join(o, keys="l_orderkey", right_keys="o_orderkey",
               join_type="inner")
    j = j.join(n, keys="s_nationkey", right_keys="n_nationkey",
               join_type="inner")
    g = j.group_by("s_name").aggregate([("s_name", "count")])
    rows = [(r["s_name"], r["s_name_count"]) for r in g.to_pylist()]
    rows.sort(key=lambda r: (-r[1], r[0]))
    return rows[:100]


def bytes_needed(rows):
    """LINEITEM's four 8-byte columns read for `l1` and for `l3`, and its
    key and supplier for `l2`; ORDERS' key and 1-byte status, SUPPLIER's
    key, 18-byte name and nation, NATION's key and 25-byte name once."""
    return (rows["lineitem_supplied"] * (4 + 2 + 4) * 8
            + rows["orders_status"] * (8 + 1)
            + rows["supplier"] * (8 + 18 + 8) + rows["nation"] * (8 + 25))


def residual_bytes_needed(rows):
    """What the two conditional joins must move at least: each join's
    stream and build read once at their key and condition columns
    (`l_orderkey`, `l_suppkey`: 16 B a row) and one mask byte a stream row
    written.  The semi join streams the late lines, L x LATE_SHARE, over a
    build of all L lines; the anti join streams what the semi join kept,
    the late lines of orders with two lines or more (x MULTI_LINE_SHARE),
    over a build of the late lines:
    `17 late + 16 L + 17 late x 27/28 + 16 late`."""
    lines = rows["lineitem_supplied"]
    late = lines * LATE_SHARE
    kept = late * MULTI_LINE_SHARE
    return round(17 * late + 16 * lines + 17 * kept + 16 * late)
