"""TPC-DS query 36 (gross margin down the item hierarchy, store channel,
one year, eight states), as published in `query36.tpl`:

    select sum(ss_net_profit)/sum(ss_ext_sales_price) as gross_margin,
           i_category, i_class,
           grouping(i_category)+grouping(i_class) as lochierarchy,
           rank() over (partition by grouping(i_category)+grouping(i_class),
                        case when grouping(i_class) = 0 then i_category end
                        order by sum(ss_net_profit)/sum(ss_ext_sales_price) asc)
             as rank_within_parent
    from store_sales, date_dim d1, item, store
    where d1.d_year = [YEAR] and d1.d_date_sk = ss_sold_date_sk
      and i_item_sk = ss_item_sk and s_store_sk = ss_store_sk
      and s_state in ([STATE_A] ... [STATE_H])
    group by rollup(i_category, i_class)
    order by lochierarchy desc,
             case when lochierarchy = 0 then i_category end,
             rank_within_parent
    limit 100

Departures from the template, all of them: the comma joins are written as
three inner joins in the template's order, each dimension filtered BEFORE
its join (as `queries/q52.py` does; the answer is the same); `[YEAR]` is
2001 and the eight states are TN, SD, AL, GA, MI, OH, TX, CA (the
qualification run names TN eight times: one state would leave a tenth of
the rows); the window's order key is the output column `gross_margin`, the
same expression; Spark's default NULL order stands for the template's
(ascending keys NULLS FIRST).  `build` is `benchmarks/tpcds/queries.py`'s
`q36`, copied.

The reference is independent of the engine and of Expand: pyarrow filters
and inner joins (which drop a NULL key by themselves), THREE separate
group-bys (class level, category level, grand total) with `lochierarchy`
0, 1, 2 set by hand, the ratio, `rank()` by numpy inside each parent (ties
share the lowest rank, gaps follow), a Python sort, the first 100 rows.
"""
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from spark_rapids_tpu.plan.logical import functions as _F

# a program without grouping() cannot state this query: say so when the
# cell is loaded, before a table is drawn or a device touched
_F.grouping, _F.grouping_id

TABLES = {"store_sales_margin": ["ss_sold_date_sk", "ss_item_sk",
                                 "ss_store_sk", "ss_net_profit",
                                 "ss_ext_sales_price"],
          "date_dim": ["d_date_sk", "d_year"],
          "item_hierarchy": ["i_item_sk", "i_category", "i_class"],
          "store": ["s_store_sk", "s_state"]}

YEAR = 2001
STATES = ("TN", "SD", "AL", "GA", "MI", "OH", "TX", "CA")


def build(session, frames):
    from spark_rapids_tpu.plan.logical import Window, col, functions as F
    dd = frames["date_dim"].filter(col("d_year") == YEAR)
    st = frames["store"].filter(col("s_state").isin(*STATES))
    rolled = (frames["store_sales_margin"]
              .join(dd, on=col("ss_sold_date_sk") == col("d_date_sk"))
              .join(frames["item_hierarchy"],
                    on=col("ss_item_sk") == col("i_item_sk"))
              .join(st, on=col("ss_store_sk") == col("s_store_sk"))
              .rollup(col("i_category"), col("i_class"))
              .agg((F.sum(col("ss_net_profit"))
                    / F.sum(col("ss_ext_sales_price")))
                   .alias("gross_margin")))
    level = F.grouping("i_category") + F.grouping("i_class")
    parent = Window.partition_by(
        level, F.when(F.grouping("i_class") == 0, col("i_category"))
    ).order_by(col("gross_margin").asc())
    return (rolled
            .select(col("gross_margin"), col("i_category"), col("i_class"),
                    level.alias("lochierarchy"),
                    F.rank().over(parent).alias("rank_within_parent"))
            .order_by(col("lochierarchy").desc(),
                      F.when(col("lochierarchy") == 0, col("i_category")),
                      col("rank_within_parent"))
            .limit(100))


def _rank(values):
    """SQL rank() ascending: 1 + the number of strictly smaller values."""
    values = np.asarray(values)
    return 1 + (values[None, :] < values[:, None]).sum(axis=1)


def reference(tables):
    dd = tables["date_dim"].select(TABLES["date_dim"])
    dd = dd.filter(pc.equal(dd["d_year"], YEAR))
    st = tables["store"].select(TABLES["store"])
    st = st.filter(pc.is_in(st["s_state"], value_set=pa.array(STATES)))
    it = tables["item_hierarchy"].select(TABLES["item_hierarchy"])
    ss = tables["store_sales_margin"].select(TABLES["store_sales_margin"])
    j = ss.join(dd.select(["d_date_sk"]), keys="ss_sold_date_sk",
                right_keys="d_date_sk", join_type="inner")
    j = j.join(it, keys="ss_item_sk", right_keys="i_item_sk",
               join_type="inner")
    j = j.join(st.select(["s_store_sk"]), keys="ss_store_sk",
               right_keys="s_store_sk", join_type="inner")
    sums = [("ss_net_profit", "sum"), ("ss_ext_sales_price", "sum")]
    rows = []          # (margin, category, class, lochierarchy, parent)
    for keys, level in ((["i_category", "i_class"], 0),
                        (["i_category"], 1), ([], 2)):
        for r in j.group_by(keys).aggregate(sums).to_pylist():
            category = r.get("i_category")
            rows.append((r["ss_net_profit_sum"]
                         / r["ss_ext_sales_price_sum"],
                         category, r.get("i_class"), level,
                         category if level == 0 else None))
    ranked = []
    for part in {(r[3], r[4]) for r in rows}:
        members = [r for r in rows if (r[3], r[4]) == part]
        for r, rank in zip(members, _rank([m[0] for m in members])):
            ranked.append(r[:4] + (int(rank),))
    # lochierarchy desc; the category only on the class level, NULLs (the
    # other levels) first; the rank
    ranked.sort(key=lambda r: (-r[3], r[1] if r[3] == 0 else "", r[4]))
    return ranked[:100]


def bytes_needed(rows):
    """Five 8-byte columns of the fact table, two of date_dim, a key and
    the two char(50) of item, a key and the char(2) of store, each once."""
    return (rows["store_sales_margin"] * 5 * 8 + rows["date_dim"] * 2 * 8
            + rows["item_hierarchy"] * (8 + 50 + 50)
            + rows["store"] * (8 + 2))
