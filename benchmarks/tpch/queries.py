"""All 22 TPC-H queries in the DataFrame API.

Reference counterpart: integration_tests/.../tpch/TpchLikeSpark.scala
(Q1-Q22 as DataFrame programs).  Correlated subqueries are expressed the
way Spark's optimizer would: aggregate-then-join; scalar subqueries are
evaluated driver-side (collect -> literal), mirroring Spark's scalar
subquery execution.  Distinct aggregates use two-level grouping rewrites.

Each `qN(t)` takes {table_name: DataFrame} (one session) and returns a
DataFrame.
"""
from __future__ import annotations

from spark_rapids_tpu.plan.logical import SortOrder, col, functions as F, lit

from .datagen import days


def q1(t):
    li = t["lineitem"].filter(col("l_shipdate") <= "1998-09-02")
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


def q2(t):
    part = t["part"].filter((col("p_size") == 15)
                            & col("p_type").endswith("BRASS"))
    europe = (t["region"].filter(col("r_name") == "EUROPE")
              .join(t["nation"],
                    on=col("r_regionkey") == col("n_regionkey"))
              .join(t["supplier"],
                    on=col("n_nationkey") == col("s_nationkey")))
    ps = t["partsupp"].join(europe,
                            on=col("ps_suppkey") == col("s_suppkey"))
    joined = part.join(ps, on=col("p_partkey") == col("ps_partkey"))
    mins = (joined.group_by(col("p_partkey"))
            .agg(F.min(col("ps_supplycost")).alias("min_cost"))
            .select(col("p_partkey").alias("mk"), col("min_cost")))
    return (joined.join(mins, on=(col("p_partkey") == col("mk"))
                        & (col("ps_supplycost") == col("min_cost")))
            .select(col("s_acctbal"), col("s_name"), col("n_name"),
                    col("p_partkey"), col("p_mfgr"), col("s_address"),
                    col("s_phone"), col("s_comment"))
            .order_by(SortOrder(col("s_acctbal"), ascending=False),
                      "n_name", "s_name", "p_partkey")
            .limit(100))


def q3(t):
    cust = t["customer"].filter(col("c_mktsegment") == "BUILDING")
    orders = t["orders"].filter(col("o_orderdate") < "1995-03-15")
    li = t["lineitem"].filter(col("l_shipdate") > "1995-03-15")
    return (cust.join(orders, on=col("c_custkey") == col("o_custkey"))
            .join(li, on=col("o_orderkey") == col("l_orderkey"))
            .group_by(col("l_orderkey"), col("o_orderdate"),
                      col("o_shippriority"))
            .agg(F.sum(col("l_extendedprice")
                       * (lit(1.0) - col("l_discount"))).alias("revenue"))
            .order_by(SortOrder(col("revenue"), ascending=False),
                      "o_orderdate")
            .limit(10))


def q4(t):
    orders = t["orders"].filter(
        (col("o_orderdate") >= "1993-07-01")
        & (col("o_orderdate") < "1993-10-01"))
    late = t["lineitem"].filter(col("l_commitdate") < col("l_receiptdate"))
    return (orders.join(late, on=col("o_orderkey") == col("l_orderkey"),
                        how="left_semi")
            .group_by(col("o_orderpriority"))
            .agg(F.count(lit(1)).alias("order_count"))
            .order_by("o_orderpriority"))


def q5(t):
    return (t["region"].filter(col("r_name") == "ASIA")
            .join(t["nation"], on=col("r_regionkey") == col("n_regionkey"))
            .join(t["supplier"], on=col("n_nationkey") == col("s_nationkey"))
            .join(t["lineitem"], on=col("s_suppkey") == col("l_suppkey"))
            .join(t["orders"].filter(
                (col("o_orderdate") >= "1994-01-01")
                & (col("o_orderdate") < "1995-01-01")),
                on=col("l_orderkey") == col("o_orderkey"))
            .join(t["customer"],
                  on=(col("o_custkey") == col("c_custkey"))
                  & (col("c_nationkey") == col("s_nationkey")))
            .group_by(col("n_name"))
            .agg(F.sum(col("l_extendedprice")
                       * (lit(1.0) - col("l_discount"))).alias("revenue"))
            .order_by(SortOrder(col("revenue"), ascending=False)))


def q6(t):
    return (t["lineitem"]
            .filter((col("l_shipdate") >= "1994-01-01")
                    & (col("l_shipdate") < "1995-01-01")
                    & col("l_discount").between(0.05, 0.07)
                    & (col("l_quantity") < 24))
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def q7(t):
    n1 = t["nation"].select(col("n_nationkey").alias("n1_key"),
                            col("n_name").alias("supp_nation"))
    n2 = t["nation"].select(col("n_nationkey").alias("n2_key"),
                            col("n_name").alias("cust_nation"))
    li = t["lineitem"].filter(col("l_shipdate").between("1995-01-01",
                                                        "1996-12-31"))
    joined = (li.join(t["supplier"], on=col("l_suppkey") == col("s_suppkey"))
              .join(t["orders"], on=col("l_orderkey") == col("o_orderkey"))
              .join(t["customer"], on=col("o_custkey") == col("c_custkey"))
              .join(n1, on=col("s_nationkey") == col("n1_key"))
              .join(n2, on=col("c_nationkey") == col("n2_key"))
              .filter(((col("supp_nation") == "FRANCE")
                       & (col("cust_nation") == "GERMANY"))
                      | ((col("supp_nation") == "GERMANY")
                         & (col("cust_nation") == "FRANCE"))))
    return (joined
            .with_column("l_year", F.year(col("l_shipdate")))
            .with_column("volume", col("l_extendedprice")
                         * (lit(1.0) - col("l_discount")))
            .group_by(col("supp_nation"), col("cust_nation"), col("l_year"))
            .agg(F.sum(col("volume")).alias("revenue"))
            .order_by("supp_nation", "cust_nation", "l_year"))


def q8(t):
    n1 = t["nation"].select(col("n_nationkey").alias("n1_key"),
                            col("n_regionkey").alias("n1_region"))
    n2 = t["nation"].select(col("n_nationkey").alias("n2_key"),
                            col("n_name").alias("supp_nation"))
    america = t["region"].filter(col("r_name") == "AMERICA")
    part = t["part"].filter(col("p_type") == "ECONOMY ANODIZED STEEL")
    orders = t["orders"].filter(col("o_orderdate").between("1995-01-01",
                                                           "1996-12-31"))
    joined = (part.join(t["lineitem"],
                        on=col("p_partkey") == col("l_partkey"))
              .join(t["supplier"], on=col("l_suppkey") == col("s_suppkey"))
              .join(orders, on=col("l_orderkey") == col("o_orderkey"))
              .join(t["customer"], on=col("o_custkey") == col("c_custkey"))
              .join(n1, on=col("c_nationkey") == col("n1_key"))
              .join(america, on=col("n1_region") == col("r_regionkey"))
              .join(n2, on=col("s_nationkey") == col("n2_key")))
    vol = (joined
           .with_column("o_year", F.year(col("o_orderdate")))
           .with_column("volume", col("l_extendedprice")
                        * (lit(1.0) - col("l_discount")))
           .with_column("brazil_volume",
                        F.when(col("supp_nation") == "BRAZIL",
                               col("volume")).otherwise(0.0)))
    return (vol.group_by(col("o_year"))
            .agg((F.sum(col("brazil_volume"))
                  / F.sum(col("volume"))).alias("mkt_share"))
            .order_by("o_year"))


def q9(t):
    part = t["part"].filter(col("p_name").contains("green"))
    joined = (part.join(t["lineitem"],
                        on=col("p_partkey") == col("l_partkey"))
              .join(t["supplier"], on=col("l_suppkey") == col("s_suppkey"))
              .join(t["partsupp"],
                    on=(col("ps_partkey") == col("l_partkey"))
                    & (col("ps_suppkey") == col("l_suppkey")))
              .join(t["orders"], on=col("l_orderkey") == col("o_orderkey"))
              .join(t["nation"], on=col("s_nationkey") == col("n_nationkey")))
    return (joined
            .with_column("o_year", F.year(col("o_orderdate")))
            .with_column("amount",
                         col("l_extendedprice")
                         * (lit(1.0) - col("l_discount"))
                         - col("ps_supplycost") * col("l_quantity"))
            .group_by(col("n_name"), col("o_year"))
            .agg(F.sum(col("amount")).alias("sum_profit"))
            .order_by("n_name", SortOrder(col("o_year"), ascending=False)))


def q10(t):
    orders = t["orders"].filter((col("o_orderdate") >= "1993-10-01")
                                & (col("o_orderdate") < "1994-01-01"))
    li = t["lineitem"].filter(col("l_returnflag") == "R")
    return (t["customer"]
            .join(orders, on=col("c_custkey") == col("o_custkey"))
            .join(li, on=col("o_orderkey") == col("l_orderkey"))
            .join(t["nation"], on=col("c_nationkey") == col("n_nationkey"))
            .group_by(col("c_custkey"), col("c_name"), col("c_acctbal"),
                      col("c_phone"), col("n_name"), col("c_address"),
                      col("c_comment"))
            .agg(F.sum(col("l_extendedprice")
                       * (lit(1.0) - col("l_discount"))).alias("revenue"))
            .order_by(SortOrder(col("revenue"), ascending=False))
            .limit(20))


def q11(t):
    germany = t["nation"].filter(col("n_name") == "GERMANY")
    ps = (t["partsupp"]
          .join(t["supplier"], on=col("ps_suppkey") == col("s_suppkey"))
          .join(germany, on=col("s_nationkey") == col("n_nationkey"))
          .with_column("value", col("ps_supplycost") * col("ps_availqty")))
    total = ps.agg(F.sum(col("value")).alias("tv")).collect()[0][0] or 0.0
    return (ps.group_by(col("ps_partkey"))
            .agg(F.sum(col("value")).alias("value"))
            .filter(col("value") > total * 0.0001)
            .order_by(SortOrder(col("value"), ascending=False)))


def q12(t):
    li = t["lineitem"].filter(
        col("l_shipmode").isin("MAIL", "SHIP")
        & (col("l_commitdate") < col("l_receiptdate"))
        & (col("l_shipdate") < col("l_commitdate"))
        & (col("l_receiptdate") >= "1994-01-01")
        & (col("l_receiptdate") < "1995-01-01"))
    hi = F.when(col("o_orderpriority").isin("1-URGENT", "2-HIGH"),
                1).otherwise(0)
    lo = F.when(col("o_orderpriority").isin("1-URGENT", "2-HIGH"),
                0).otherwise(1)
    return (t["orders"].join(li, on=col("o_orderkey") == col("l_orderkey"))
            .group_by(col("l_shipmode"))
            .agg(F.sum(hi).alias("high_line_count"),
                 F.sum(lo).alias("low_line_count"))
            .order_by("l_shipmode"))


def q13(t):
    orders = t["orders"].filter(
        ~(col("o_comment").contains("special")
          & col("o_comment").contains("requests")))
    per_cust = (t["customer"]
                .join(orders, on=col("c_custkey") == col("o_custkey"),
                      how="left")
                .with_column("has_order",
                             F.when(col("o_orderkey").is_null(), 0)
                             .otherwise(1))
                .group_by(col("c_custkey"))
                .agg(F.sum(col("has_order")).alias("c_count")))
    return (per_cust.group_by(col("c_count"))
            .agg(F.count(lit(1)).alias("custdist"))
            .order_by(SortOrder(col("custdist"), ascending=False),
                      SortOrder(col("c_count"), ascending=False)))


def q14(t):
    li = t["lineitem"].filter((col("l_shipdate") >= "1995-09-01")
                              & (col("l_shipdate") < "1995-10-01"))
    joined = li.join(t["part"], on=col("l_partkey") == col("p_partkey"))
    disc = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    promo = F.when(col("p_type").startswith("PROMO"), disc).otherwise(0.0)
    return joined.agg(
        ((F.sum(promo) * 100.0) / F.sum(disc)).alias("promo_revenue"))


def q15(t):
    li = t["lineitem"].filter((col("l_shipdate") >= "1996-01-01")
                              & (col("l_shipdate") < "1996-04-01"))
    revenue = (li.group_by(col("l_suppkey"))
               .agg(F.sum(col("l_extendedprice")
                          * (lit(1.0) - col("l_discount")))
                    .alias("total_revenue")))
    top = revenue.agg(F.max(col("total_revenue")).alias("m")) \
        .collect()[0][0] or 0.0
    return (t["supplier"]
            .join(revenue.filter(col("total_revenue") >= top - 1e-6),
                  on=col("s_suppkey") == col("l_suppkey"))
            .select(col("s_suppkey"), col("s_name"), col("s_address"),
                    col("s_phone"), col("total_revenue"))
            .order_by("s_suppkey"))


def q16(t):
    part = t["part"].filter(
        (col("p_brand") != "Brand#45")
        & ~col("p_type").startswith("MEDIUM POLISHED")
        & col("p_size").isin(49, 14, 23, 45, 19, 3, 36, 9))
    bad_supp = t["supplier"].filter(
        col("s_comment").contains("Customer")
        & col("s_comment").contains("Complaints"))
    ps = (t["partsupp"]
          .join(bad_supp, on=col("ps_suppkey") == col("s_suppkey"),
                how="left_anti")
          .join(part, on=col("ps_partkey") == col("p_partkey")))
    # distinct supplier count via two-level grouping (no distinct aggs)
    distinct_ps = (ps.group_by(col("p_brand"), col("p_type"), col("p_size"),
                               col("ps_suppkey"))
                   .agg(F.count(lit(1)).alias("_c")))
    return (distinct_ps.group_by(col("p_brand"), col("p_type"),
                                 col("p_size"))
            .agg(F.count(lit(1)).alias("supplier_cnt"))
            .order_by(SortOrder(col("supplier_cnt"), ascending=False),
                      "p_brand", "p_type", "p_size"))


def q17(t):
    part = t["part"].filter((col("p_brand") == "Brand#23")
                            & (col("p_container") == "MED BOX"))
    li = t["lineitem"].join(part,
                            on=col("l_partkey") == col("p_partkey"))
    avg_qty = (li.group_by(col("p_partkey"))
               .agg((F.avg(col("l_quantity")) * 0.2).alias("limit_qty"))
               .select(col("p_partkey").alias("ak"), col("limit_qty")))
    return (li.join(avg_qty, on=col("p_partkey") == col("ak"))
            .filter(col("l_quantity") < col("limit_qty"))
            .agg((F.sum(col("l_extendedprice")) / 7.0)
                 .alias("avg_yearly")))


def q18(t):
    """An equivalent rewrite of query 18, not the published form: the
    `IN (subquery)` and the join to LINEITEM become ONE inner join to the
    big orders, with the quantity carried from the subquery's aggregate
    instead of summed again.  `chipbench/queries/q18.py` is the published
    form (a `left_semi` join, LINEITEM joined again, the five-key
    group-by)."""
    big = (t["lineitem"].group_by(col("l_orderkey"))
           .agg(F.sum(col("l_quantity")).alias("sum_qty"))
           .filter(col("sum_qty") > 300)
           .select(col("l_orderkey").alias("big_key"), col("sum_qty")))
    return (t["orders"]
            .join(big, on=col("o_orderkey") == col("big_key"))
            .join(t["customer"], on=col("o_custkey") == col("c_custkey"))
            .select(col("c_name"), col("c_custkey"), col("o_orderkey"),
                    col("o_orderdate"), col("o_totalprice"), col("sum_qty"))
            .order_by(SortOrder(col("o_totalprice"), ascending=False),
                      "o_orderdate")
            .limit(100))


def q19(t):
    li = t["lineitem"].filter(
        col("l_shipmode").isin("AIR", "REG AIR")
        & (col("l_shipinstruct") == "DELIVER IN PERSON"))
    joined = li.join(t["part"], on=col("l_partkey") == col("p_partkey"))
    b1 = ((col("p_brand") == "Brand#12")
          & col("p_container").isin("SM CASE", "SM BOX", "SM PACK", "SM PKG")
          & col("l_quantity").between(1, 11) & (col("p_size").between(1, 5)))
    b2 = ((col("p_brand") == "Brand#23")
          & col("p_container").isin("MED BAG", "MED BOX", "MED PKG",
                                    "MED PACK")
          & col("l_quantity").between(10, 20)
          & (col("p_size").between(1, 10)))
    b3 = ((col("p_brand") == "Brand#34")
          & col("p_container").isin("LG CASE", "LG BOX", "LG PACK", "LG PKG")
          & col("l_quantity").between(20, 30)
          & (col("p_size").between(1, 15)))
    return (joined.filter(b1 | b2 | b3)
            .agg(F.sum(col("l_extendedprice")
                       * (lit(1.0) - col("l_discount"))).alias("revenue")))


def q20(t):
    forest_parts = t["part"].filter(col("p_name").startswith("forest")) \
        .select(col("p_partkey").alias("fp_key"))
    li94 = t["lineitem"].filter((col("l_shipdate") >= "1994-01-01")
                                & (col("l_shipdate") < "1995-01-01"))
    half_qty = (li94.group_by(col("l_partkey"), col("l_suppkey"))
                .agg((F.sum(col("l_quantity")) * 0.5).alias("half_qty")))
    ps = (t["partsupp"]
          .join(forest_parts, on=col("ps_partkey") == col("fp_key"),
                how="left_semi")
          .join(half_qty, on=(col("ps_partkey") == col("l_partkey"))
                & (col("ps_suppkey") == col("l_suppkey")))
          .filter(col("ps_availqty") > col("half_qty")))
    canada = t["nation"].filter(col("n_name") == "CANADA")
    return (t["supplier"]
            .join(ps, on=col("s_suppkey") == col("ps_suppkey"),
                  how="left_semi")
            .join(canada, on=col("s_nationkey") == col("n_nationkey"))
            .select(col("s_name"), col("s_address"))
            .order_by("s_name"))


def q21(t):
    """An equivalent rewrite of query 21, not the published form: EXISTS
    and NOT EXISTS become two counts of distinct suppliers an order (all
    of them and the late ones), joined back and filtered.
    `chipbench/queries/q21.py` is the published form (a `left_semi` and a
    `left_anti` self-join of LINEITEM, each with the supplier inequality
    as a residual condition)."""
    nation = t["nation"].filter(col("n_name") == "SAUDI ARABIA")
    f_orders = t["orders"].filter(col("o_orderstatus") == "F") \
        .select(col("o_orderkey"))
    li = t["lineitem"].join(f_orders,
                            on=col("l_orderkey") == col("o_orderkey"),
                            how="left_semi")
    # per order: number of distinct suppliers, and of distinct LATE suppliers
    supp_per_order = (li.group_by(col("l_orderkey"), col("l_suppkey"))
                      .agg(F.count(lit(1)).alias("_c"))
                      .group_by(col("l_orderkey"))
                      .agg(F.count(lit(1)).alias("nsupp"))
                      .select(col("l_orderkey").alias("all_key"),
                              col("nsupp")))
    late = li.filter(col("l_receiptdate") > col("l_commitdate"))
    late_per_order = (late.group_by(col("l_orderkey"), col("l_suppkey"))
                      .agg(F.count(lit(1)).alias("_c"))
                      .group_by(col("l_orderkey"))
                      .agg(F.count(lit(1)).alias("nlate"))
                      .select(col("l_orderkey").alias("late_key"),
                              col("nlate")))
    blamed = (late
              .join(supp_per_order, on=col("l_orderkey") == col("all_key"))
              .join(late_per_order, on=col("l_orderkey") == col("late_key"))
              .filter((col("nsupp") > 1) & (col("nlate") == 1)))
    return (blamed
            .join(t["supplier"], on=col("l_suppkey") == col("s_suppkey"))
            .join(nation, on=col("s_nationkey") == col("n_nationkey"))
            .group_by(col("s_name"))
            .agg(F.count(lit(1)).alias("numwait"))
            .order_by(SortOrder(col("numwait"), ascending=False), "s_name")
            .limit(100))


def q22(t):
    codes = ["13", "31", "23", "29", "30", "18", "17"]
    cust = t["customer"].with_column("cntrycode",
                                     col("c_phone").substr(1, 2))
    cust = cust.filter(col("cntrycode").isin(*codes))
    avg_bal = cust.filter(col("c_acctbal") > 0.0) \
        .agg(F.avg(col("c_acctbal")).alias("a")).collect()[0][0] or 0.0
    rich = cust.filter(col("c_acctbal") > avg_bal)
    no_orders = rich.join(t["orders"],
                          on=col("c_custkey") == col("o_custkey"),
                          how="left_anti")
    return (no_orders.group_by(col("cntrycode"))
            .agg(F.count(lit(1)).alias("numcust"),
                 F.sum(col("c_acctbal")).alias("totacctbal"))
            .order_by("cntrycode"))


QUERIES = {i: globals()[f"q{i}"] for i in range(1, 23)}
