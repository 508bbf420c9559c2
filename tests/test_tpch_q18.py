"""TPC-H query 18 as `chipbench`'s cell `tpch_q18_resident` runs it, here at
a small size on the CPU.  Tables, query, reference and comparison are the
benchmark's own files (`chipbench/tables/customer.py`, `orders_priced.py`,
`lineitem_clustered.py`, `queries/q18.py`, `compare.py`), and the session's
conf is the configuration file's; only the reader's batch size is set IN
THE TEST where a test counts batches, so that 80,000 lines come in ten
equal batches as 6,000,000 come in six.

What no other test holds together: a grouped whole-stage SORT program over
a raw resident table (one group an order), its output through a HAVING
into the build side of a `left_semi` join (the `IN` subquery), the counter
`joinSemiBatches`, and a five-key aggregate over a string and a double.
"""
import json
import os

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.basic import TpuScanMemoryExec
from spark_rapids_tpu.exec.broadcast import (TpuBroadcastExchangeExec,
                                             TpuBroadcastHashJoinExec)
from test_agg_streaming import BENCH, _bench_module

ORDERS = 20_000
SIZES = {"customer": 2_000, "orders_priced": ORDERS,
         "lineitem_clustered": 4 * ORDERS}
#: LINEITEM in ten batches of capacity 8,192 (the tenth holds 6,272 rows),
#: ORDERS in three (8,192, 8,192 and 3,616 at capacity 4,096)
BATCH = 8_192
LINE_BATCHES, ORDER_BATCHES = 10, 3

COMPARE = _bench_module("", "compare")
Q18 = _bench_module("queries", "q18")
LINES = _bench_module("tables", "lineitem_clustered")

with open(os.path.join(BENCH, "configs", "tpch-sf1-q18-1chip.json")) as _f:
    CELL_CONF = json.load(_f)["conf"]


def q18_tables(seed, sizes=SIZES):
    """The cell's three tables from `seed`, as `cells.make_tables` makes
    them."""
    tables = {}
    for table, columns in Q18.TABLES.items():
        drawn = _bench_module("tables", table).generate(
            sizes[table], seed, sizes)
        tables[table] = pa.table({c: drawn[c] for c in columns})
    return tables


def session(batch=None):
    conf = dict(CELL_CONF)
    if batch:
        conf["spark.rapids.sql.reader.batchSizeRows"] = str(batch)
    return TpuSession(conf)


def q18(s, tables, quantity=Q18.QUANTITY):
    return Q18.build(s, {t: s.from_arrow(tb) for t, tb in tables.items()},
                     quantity)


def collect_moved(s, df):
    """(rows, the counters the query moved)."""
    before = dict(s.query_metrics_total)
    rows = df.collect()
    return rows, {k: v - before.get(k, 0)
                  for k, v in s.query_metrics_total.items()}


def walk(node):
    yield node
    for child in node.children:
        yield from walk(child)


@pytest.mark.parametrize("quantity", [300, 250])
@pytest.mark.parametrize("seed", [3_700_000_017, 5, 2**31 + 9])
def test_q18_equals_the_plain_reference(seed, quantity):
    tables = q18_tables(seed % 2**32)
    want = Q18.reference(tables, quantity)
    # 300 leaves an order or two of 20,000, 250 some ninety
    assert len(want) <= 5 if quantity == 300 else 50 <= len(want) <= 100
    s = session()
    got, moved = collect_moved(s, q18(s, tables, quantity))
    ok, worst = COMPARE.rows_match(got, want)
    assert ok, (worst, got[:3], want[:3])
    assert moved.get("numCpuFallbacks", 0) == 0


def test_the_plan_is_a_semi_join_on_the_having_over_lineitem():
    plan = q18(session(), q18_tables(5)).physical_plan()
    assert not [n for n in walk(plan) if type(n).__name__.startswith("Cpu")]
    [semi] = [n for n in walk(plan) if getattr(n, "join_type", None)
              == "left_semi"]
    assert isinstance(semi, TpuBroadcastHashJoinExec)
    # the build side: the HAVING (fused with the key's projection) over
    # the aggregate of LINEITEM by order, broadcast
    exchange = semi.children[1]
    assert isinstance(exchange, TpuBroadcastExchangeExec)
    assert exchange.schema.names == ["big_key"]
    stage = exchange.children[0]
    assert "TpuFilterExec" in stage.describe()
    [agg] = [n for n in walk(stage) if isinstance(n, TpuHashAggregateExec)]
    assert [c.describe() for c in agg.children] == [
        f"TpuScanMemoryExec[rows={SIZES['lineitem_clustered']}]"]
    # the second aggregate groups on the name, three integers and a double
    top = [n for n in walk(plan) if isinstance(n, TpuHashAggregateExec)
           and n is not agg]
    assert len(top) == 1 and "c_name:string" in top[0].describe() \
        and "o_totalprice:double" in top[0].describe()
    assert sum(isinstance(n, TpuScanMemoryExec) for n in walk(plan)) == 4


def test_the_counters_read_the_batches_the_plan_implies():
    tables = q18_tables(11)
    s = session(BATCH)
    df = q18(s, tables, 250)
    want = Q18.reference(tables, 250)
    first, _ = collect_moved(s, df)   # the bucket probe fails, the key latches
    got, moved = collect_moved(s, df)
    for rows in (first, got):
        ok, worst = COMPARE.rows_match(rows, want)
        assert ok, (worst, rows[:3], want[:3])
    # ORDERS' stream batches, each through the membership mask
    assert moved["joinSemiBatches"] == ORDER_BATCHES
    # LINEITEM's batches, all in ONE grouped whole-stage sort program
    assert moved["aggSortPathBatches"] == LINE_BATCHES
    assert moved.get("aggStreamedBatches", 0) == 0
    assert moved.get("numCpuFallbacks", 0) == 0
    # three joins: the customer, the semi and LINEITEM's; the semi join
    # places no pairs, the others place them or pass the stream through
    assert moved["joinMergedWindowBatches"] == (
        moved.get("joinOutputSpaceBatches", 0)
        + moved.get("joinPassThroughBatches", 0) + moved["joinSemiBatches"])


def test_the_q3_shape_answers_through_no_semi_join():
    q3 = _bench_module("queries", "q3_shape")
    sizes = {"lineitem": 40_000, "orders": 10_000}
    tables = {}
    for table, columns in q3.TABLES.items():
        drawn = _bench_module("tables", table).generate(sizes[table], 3,
                                                        sizes)
        tables[table] = pa.table({c: drawn[c] for c in columns})
    s = session()
    df = q3.build(s, {t: s.from_arrow(tb) for t, tb in tables.items()})
    got, moved = collect_moved(s, df)
    ok, worst = COMPARE.rows_match(got, q3.reference(tables))
    assert ok, (worst, got[:3])
    assert moved["joinMergedWindowBatches"] >= 1
    assert moved.get("joinSemiBatches", 0) == 0


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_the_generators_keep_the_specifications_rules(seed):
    tables = q18_tables(seed % 2**32)
    c, o, li = (tables[t] for t in ("customer", "orders_priced",
                                    "lineitem_clustered"))
    assert li.num_rows == SIZES["lineitem_clustered"]
    keys = o["o_orderkey"].to_numpy()
    # sparse keys, the first 8 of every 32, in key order
    assert np.array_equal(keys, LINES.order_keys(ORDERS))
    assert set(np.unique((keys - 1) % 32)) == set(range(8))
    assert np.all(np.diff(keys) > 0)
    # LINEITEM clustered by order, in the orders' order, 1 to 7 lines each
    lkeys = li["l_orderkey"].to_numpy()
    assert np.all(np.diff(lkeys) >= 0)
    owners, counts = np.unique(lkeys, return_counts=True)
    assert np.array_equal(owners, keys)
    assert counts.min() == 1 and counts.max() == 7
    assert abs(counts.mean() - 4) < 1e-9
    assert sorted(set(np.bincount(counts, minlength=8)[1:] > 0)) == [True]
    qty = li["l_quantity"].to_numpy()
    assert qty.min() == 1 and qty.max() == 50
    assert np.array_equal(qty, np.round(qty))
    cust = o["o_custkey"].to_numpy()
    assert cust.min() >= 1 and cust.max() <= SIZES["customer"]
    assert not np.any(cust % 3 == 0)
    assert len(np.unique(cust)) > SIZES["customer"] * 0.6
    names = c["c_name"].to_pylist()
    assert names[0] == "Customer#000000001"
    assert all(len(n) == 18 for n in names)
    assert names[-1] == f"Customer#{SIZES['customer']:09d}"
    dates = o["o_orderdate"].to_numpy()
    assert dates.min() >= 8035 and dates.max() <= 10440
    # o_totalprice from the order's OWN lines: dearer with more quantity,
    # and the same draws give the same prices and lines
    price = o["o_totalprice"].to_numpy()
    assert np.array_equal(price, np.round(price, 2))
    per_order = np.bincount(np.repeat(np.arange(ORDERS), counts),
                            weights=qty)
    lo, hi = per_order * 900 * 0.9 * 1.0, per_order * 2100 * 1.08
    assert np.all(price >= np.floor(lo) - 0.01)
    assert np.all(price <= np.ceil(hi) + 0.01)
    assert np.corrcoef(per_order, price)[0, 1] > 0.9
    again = q18_tables(seed % 2**32)
    assert all(again[t].equals(tables[t]) for t in tables)


def test_the_line_count_is_met_exactly_within_one_to_seven():
    rng = np.random.RandomState(0)
    for orders, lines in ((1_000, 4_000), (1_000, 3_800), (1_000, 4_300),
                          (10, 70), (10, 10)):
        counts = LINES.line_counts(rng, orders, lines)
        assert counts.sum() == lines
        assert counts.min() >= 1 and counts.max() <= 7
    with pytest.raises(ValueError):
        LINES.line_counts(rng, 10, 71)
