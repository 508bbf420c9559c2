"""TPC-H Q1 (pricing summary report): a filter, a grouped aggregate over
two 1-char keys (at most 6 groups) and a sort of the groups.  The DataFrame
is `benchmarks/tpch/bulk.py`'s `q1`, copied; the reference is pyarrow
compute and `Table.group_by`."""
import pyarrow as pa
import pyarrow.compute as pc

D_19980902 = 10471   # 1998-09-02 as days since epoch

TABLES = {"lineitem": ["l_extendedprice", "l_discount", "l_quantity",
                       "l_shipdate", "l_returnflag", "l_linestatus",
                       "l_tax"]}


def build(session, frames):
    from spark_rapids_tpu.plan.logical import col, functions as F, lit
    li = frames["lineitem"].filter(col("l_shipdate") <= D_19980902)
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


def reference(tables):
    li = tables["lineitem"]
    li = li.filter(pc.less_equal(li["l_shipdate"], D_19980902))
    disc = pc.multiply(li["l_extendedprice"],
                       pc.subtract(1.0, li["l_discount"]))
    charge = pc.multiply(disc, pc.add(1.0, li["l_tax"]))
    t = pa.table({"l_returnflag": li["l_returnflag"],
                  "l_linestatus": li["l_linestatus"],
                  "qty": li["l_quantity"], "price": li["l_extendedprice"],
                  "disc_price": disc, "charge": charge,
                  "disc": li["l_discount"]})
    g = t.group_by(["l_returnflag", "l_linestatus"]).aggregate([
        ("qty", "sum"), ("price", "sum"), ("disc_price", "sum"),
        ("charge", "sum"), ("qty", "mean"), ("price", "mean"),
        ("disc", "mean"), ("qty", "count")])
    g = g.sort_by([("l_returnflag", "ascending"),
                   ("l_linestatus", "ascending")])
    order = ["l_returnflag", "l_linestatus", "qty_sum", "price_sum",
             "disc_price_sum", "charge_sum", "qty_mean", "price_mean",
             "disc_mean", "qty_count"]
    return [tuple(r[c] for c in order) for r in g.to_pylist()]


def bytes_needed(rows):
    """Five 8-byte columns and two 1-byte flags of every row."""
    return rows["lineitem"] * (5 * 8 + 2 * 1)
