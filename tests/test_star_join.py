"""A star schema through the broadcast half of the join planning: TPC-DS
query 52 (STORE_SALES cut by DATE_DIM and ITEM, grouped by year, brand and
brand id, the top 100) as `chipbench`'s cell `tpcds_q52_star_sf10` runs it,
here at a small size on the CPU.  Tables, query, reference and comparison
are the benchmark's own files (`chipbench/tables/store_sales.py`,
`date_dim.py`, `item.py`, `queries/q52.py`, `compare.py`), and the
session's conf is the configuration file's; only the reader's batch size is
set IN THE TEST, so that three hundred thousand fact rows come in several
stream batches as 28.8M do by themselves.

What no other test holds together: `dim.join(fact)` swapped so that the
dimension builds, two `TpuBroadcastHashJoinExec` chained under a
`TpuReorderColumnsExec`, a join key that is NULL in 4.5% of the stream
rows, a string payload of up to 50 bytes gathered by the second join and
then grouped on, and the counters `broadcastBytes`, `broadcastRows` and
`joinHostSyncs`.
"""
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.exec.broadcast import (TpuBroadcastExchangeExec,
                                             TpuBroadcastHashJoinExec)
from spark_rapids_tpu.exec.join import TpuReorderColumnsExec
from test_agg_streaming import BENCH, _bench_module

#: DATE_DIM in ONE batch, as in the cell: the exchange then ships it as it
#: is, at its capacity (several batches it would concatenate, and compact)
BATCH = 131_072
#: past twice DATE_DIM's bytes, or the planner would build the fact table
SIZES = {"store_sales": 300_000, "item": 2_000, "date_dim": 73_049}
STREAM_BATCHES = -(-SIZES["store_sales"] // BATCH)
WIDE_BRAND = "w" * 47 + " #1"          # the whole of the char(50)

COMPARE = _bench_module("", "compare")
Q52 = _bench_module("queries", "q52")

with open(os.path.join(BENCH, "configs", "tpcds-sf10-1chip.json")) as _f:
    CELL_CONF = json.load(_f)["conf"]


def star_tables(seed):
    """The cell's three tables from `seed`, as `cells.make_tables` makes
    them, with the best-selling brand of manager 1 renamed to 50 bytes."""
    tables = {}
    for table, columns in Q52.TABLES.items():
        drawn = _bench_module("tables", table).generate(
            SIZES[table], seed, SIZES)
        tables[table] = pa.table({c: drawn[c] for c in columns})
    top = Q52.reference(tables)[0]
    item = tables["item"]
    brand = np.where(item["i_brand_id"].to_numpy() == top[2], WIDE_BRAND,
                     item["i_brand"].to_numpy(zero_copy_only=False))
    tables["item"] = item.set_column(
        item.schema.get_field_index("i_brand"), "i_brand",
        pa.array(brand, type=pa.string()))
    return tables


def session(conf=CELL_CONF):
    return TpuSession({**conf,
                       "spark.rapids.sql.reader.batchSizeRows": str(BATCH)})


def q52(s, tables):
    return Q52.build(s, {t: s.from_arrow(tb) for t, tb in tables.items()})


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


@pytest.mark.parametrize("seed", [3_100_000_007, 11])
def test_q52_equals_the_plain_reference_and_the_cpu_oracle(seed):
    tables = star_tables(seed % 2**32)
    want = Q52.reference(tables)
    assert 10 < len(want) <= 100 and want[0][1] == WIDE_BRAND
    assert tables["store_sales"]["ss_sold_date_sk"].null_count > 12_000
    s = session()
    got, moved = collect_moved(s, q52(s, tables))
    ok, worst = COMPARE.rows_match(got, want)
    assert ok, (worst, got[:3], want[:3])
    assert moved.get("numCpuFallbacks", 0) == 0
    assert moved["joinMergedWindowBatches"] == 2 * STREAM_BATCHES
    # the host executors (ops/cpu_eval.py), which share no join, aggregate
    # or sort code with the device path
    oracle = q52(session({"spark.rapids.sql.enabled": "false"}),
                 tables).collect()
    ok, worst = COMPARE.rows_match(got, oracle)
    assert ok, (worst, got[:3], oracle[:3])


def test_the_plan_is_two_broadcast_joins_over_the_filtered_dimensions():
    s = session()
    plan = q52(s, star_tables(5)).physical_plan()
    joins = [n for n in walk(plan) if isinstance(n, TpuBroadcastHashJoinExec)]
    assert len(joins) == 2
    outer, inner = joins
    # dd.join(store_sales) is swapped so that the dimension builds, and its
    # columns are put back in the order the query names them
    assert isinstance(outer.children[0], TpuReorderColumnsExec)
    assert outer.children[0].children[0] is inner
    built = []
    for join in (inner, outer):
        exchange = join.children[1]
        assert isinstance(exchange, TpuBroadcastExchangeExec)
        # the dimension's filter, fused into a stage of its own
        assert "TpuFilterExec" in exchange.children[0].describe()
        built.append(exchange.schema.names[0])
    assert built == ["d_date_sk", "i_item_sk"]
    # the fact table streams, unfiltered, through both
    assert (inner.children[0].describe()
            == f"TpuScanMemoryExec[rows={SIZES['store_sales']}]")
    assert not [n for n in walk(plan) if type(n).__name__.startswith("Cpu")]


def test_a_null_sold_date_matches_no_date_dim_row():
    """Every sale of the best-selling brand loses its date key: the rows
    still stream through both joins and would match ITEM, but a NULL
    `ss_sold_date_sk` equals no `d_date_sk`, so the brand is in no group."""
    tables = star_tables(7)
    before = Q52.reference(tables)
    brand = before[0][2]
    item, sales = tables["item"], tables["store_sales"]
    its_items = item["i_item_sk"].filter(pc.equal(item["i_brand_id"], brand))
    undated = pc.is_in(sales["ss_item_sk"], value_set=its_items)
    assert pc.sum(undated).as_py() > 100
    tables["store_sales"] = sales.set_column(
        0, "ss_sold_date_sk",
        pc.if_else(undated, pa.scalar(None, pa.int64()),
                   sales["ss_sold_date_sk"]))
    s = session()
    rows, moved = collect_moved(s, q52(s, tables))
    assert brand not in [r[2] for r in rows]
    assert len(rows) == len(before) - 1
    ok, worst = COMPARE.rows_match(rows, Q52.reference(tables))
    assert ok, (worst, rows[:3])
    assert moved.get("numCpuFallbacks", 0) == 0


def test_broadcast_and_join_counters_read_what_the_plan_implies():
    tables = star_tables(9)
    s = session()
    df = q52(s, tables)
    df.collect()
    _, moved = collect_moved(s, df)       # the scan-cache hit
    item, dates = tables["item"], tables["date_dim"]
    live = (np.count_nonzero(item["i_manager_id"].to_numpy() == Q52.MANAGER)
            + 30)                          # the days of November 2000
    assert moved["broadcastRows"] == live
    # each dimension travels at its capacity, not at its live rows: every
    # column's data and validity and the selection, and a string's lengths
    assert moved["broadcastBytes"] == moved["dataSize"]
    least = (dates.num_rows * (3 * (8 + 1) + 1)
             + item.num_rows * (3 * (8 + 1) + 50 + 1 + 4 + 1))
    assert least <= moved["broadcastBytes"] < 3 * least
    # a probe reads its two scalars once a stream batch of each join, and
    # each build side's live rows are read once before its shrink; no
    # recount: the guess of 8 holds, both dimensions' keys are unique
    assert moved["joinHostSyncs"] == 2 * STREAM_BATCHES + 2
    assert moved["joinMergedWindowBatches"] == 2 * STREAM_BATCHES
