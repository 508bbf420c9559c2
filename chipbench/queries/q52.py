"""TPC-DS query 52 (brand revenue of one manager's items in November 2000):
STORE_SALES cut by two small dimensions, grouped by year, brand and brand
id, the top 100.  The DataFrame is `benchmarks/tpcds/queries.py`'s `q52`,
copied: the dimensions filtered before the joins, `dd.join(store_sales)
.join(item)`.  The reference is a pyarrow filter, two inner joins (which
drop a NULL key by themselves), `group_by`, sort and head."""
import pyarrow.compute as pc

TABLES = {"store_sales": ["ss_sold_date_sk", "ss_item_sk",
                          "ss_ext_sales_price"],
          "date_dim": ["d_date_sk", "d_year", "d_moy"],
          "item": ["i_item_sk", "i_brand_id", "i_brand", "i_manager_id"]}

MOY, YEAR, MANAGER = 11, 2000, 1


def build(session, frames):
    from spark_rapids_tpu.plan.logical import col, functions as F
    dd = frames["date_dim"].filter((col("d_moy") == MOY)
                                   & (col("d_year") == YEAR))
    it = frames["item"].filter(col("i_manager_id") == MANAGER)
    return (dd.join(frames["store_sales"],
                    on=col("d_date_sk") == col("ss_sold_date_sk"))
            .join(it, on=col("ss_item_sk") == col("i_item_sk"))
            .group_by(col("d_year"), col("i_brand"), col("i_brand_id"))
            .agg(F.sum(col("ss_ext_sales_price")).alias("ext_price"))
            .order_by(col("d_year"), col("ext_price").desc(),
                      col("i_brand_id"))
            .limit(100))


def reference(tables):
    dd = tables["date_dim"].select(TABLES["date_dim"])
    dd = dd.filter(pc.and_(pc.equal(dd["d_moy"], MOY),
                           pc.equal(dd["d_year"], YEAR)))
    it = tables["item"].select(TABLES["item"])
    it = it.filter(pc.equal(it["i_manager_id"], MANAGER))
    ss = tables["store_sales"].select(TABLES["store_sales"])
    j = ss.join(dd.select(["d_date_sk", "d_year"]), keys="ss_sold_date_sk",
                right_keys="d_date_sk", join_type="inner")
    j = j.join(it.select(["i_item_sk", "i_brand", "i_brand_id"]),
               keys="ss_item_sk", right_keys="i_item_sk", join_type="inner")
    g = j.group_by(["d_year", "i_brand", "i_brand_id"]).aggregate(
        [("ss_ext_sales_price", "sum")])
    g = g.sort_by([("d_year", "ascending"),
                   ("ss_ext_sales_price_sum", "descending"),
                   ("i_brand_id", "ascending")]).slice(0, 100)
    order = ["d_year", "i_brand", "i_brand_id", "ss_ext_sales_price_sum"]
    return [tuple(r[c] for c in order) for r in g.to_pylist()]


def bytes_needed(rows):
    """Three 8-byte columns of store_sales and of date_dim, three 8-byte
    columns and the char(50) brand of item, each once."""
    return (rows["store_sales"] * 3 * 8 + rows["item"] * (3 * 8 + 50)
            + rows["date_dim"] * 3 * 8)
