"""PR 33's cell, from the real `BENCHMARK.json`: `tpcds_q36_rollup_sf10`
(configuration `tpcds-sf10-rollup-1chip`: TPC-DS query 36 as published over
STORE_SALES at SF10: three broadcast joins, ROLLUP through Expand, a
string-keyed aggregate, grouping(), a rank window): what the configuration
states, the generators' domains, the plain reference against a loop over the
rows, the four new per-layer metrics from made-up evidence, and the
rehearsal off the chip with the counters and the span the small run implies.

`run.py --rows` scales every table by `lineitem`, which this configuration
does not have, so the cell rehearses through `run.Run` with the instance's
`rows` shadowed by a scaled table of rows (as `test_star_cells.py` does)."""
import json
import os
import tempfile

import numpy as np
import pyarrow as pa
import pytest

import cells
import compare
import run
import xplane
from test_readers import read

CELL = "tpcds_q36_rollup_sf10"
CONFIG = "tpcds-sf10-rollup-1chip"
FULL = {"store_sales_margin": 28_800_991, "date_dim": 73_049,
        "item_hierarchy": 102_000, "store": 102}
SMALL = {"store_sales_margin": 200_000, "date_dim": 73_049,
         "item_hierarchy": 10_200, "store": 102}
NEW_METRICS = ("window_device_ms", "window_ms", "expand_rows_per_query",
               "agg_sort_path_batches_per_query")
# the lists of accepted metrics that gain the cell's name: six, and the
# streaming loop's three, because on the chip that loop answers (the 28th
# join output has another capacity: `srt:agg_whole_stage_bail`, PERF.md)
JOINED_LISTS = ("agg_device_ms", "join_device_ms",
                "join_merged_window_batches_per_query",
                "join_host_syncs_per_query", "join_broadcast_ms",
                "join_broadcast_mb_per_query",
                "agg_streamed_batches_per_query",
                "agg_host_syncs_per_query", "agg_shrink_ms")


def bench():
    with open(os.path.join(os.path.dirname(cells.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        return json.load(f)


def names(cell):
    return [m["name"] for m, _ in cell.per_layer]


def table(name):
    return cells.load_module(cells.BENCH_DIR, "tables", name)


def test_the_cell_and_what_it_reports():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    assert cell.rows() == FULL
    join = cells.load_cell("tpch_q3_join_resident").traffic
    assert cell.traffic == {**join, "query": "q36",
                            "rows_in": sum(FULL.values()),
                            "why": cell.traffic["why"]}
    assert cell.traffic["rows_in"] == 28_976_142
    assert (cell.traffic["warmup_queries"], cell.traffic["min_queries"],
            cell.traffic["trace_seconds"],
            cell.traffic["trace_min_queries"]) == (2, 2, 1, 1)
    assert set(NEW_METRICS) | set(JOINED_LISTS) | {
        "hbm_roofline_share", "hbm_peak_gb", "device_idle_share"} <= set(
            names(cell))
    assert not {"scan_device_ms", "collective_share",
                "agg_dense_batches_per_query"} & set(names(cell))
    assert [m["name"] for m in cell.end_to_end] == ["query_s", "setup_s"]
    # five 8-byte fact columns, two of the dates, a key and two char(50) of
    # the items, a key and a char(2) of the stores
    assert cell.query.bytes_needed(FULL) == (
        28_800_991 * 40 + 73_049 * 16 + 102_000 * 108 + 102 * 10
    ) == 1_164_225_444
    # what only this cell has, no other cell reports
    for other in ("tpcds_q52_star_sf10", "tpch_q3_join_resident"):
        assert not set(NEW_METRICS) & set(names(cells.load_cell(other)))


def test_the_entries_in_benchmark_json():
    b = bench()
    assert b["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "q36_resident",
        "chips": 1, "why": b["workloads"][-1]["why"]}
    assert len(b["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert [m["name"] for m in b["per_layer"][-4:]] == list(NEW_METRICS)
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["layer"] == "Operators"
            assert m["moves"] == "query_s"
        elif m["name"] in JOINED_LISTS:
            assert m["workloads"][-1] == CELL
        else:
            assert CELL not in m.get("workloads", [])
    sources = {m["name"]: m["source"] for m in b["per_layer"]}
    assert [sources[n] for n in NEW_METRICS] == [
        "device_trace", "program_span", "program_counter",
        "program_counter"]
    # one pattern finds the window's programs and no other program's
    spec = cells.load_json(cells.BENCH_DIR, "layer_metrics",
                           "window_device_ms")
    import re
    pattern = re.compile(spec["args"]["pattern"])
    assert pattern.search("jit_sort.window(123)")
    for other in ("jit_sort.sort(1)", "jit_agg.whole_stage(2)",
                  "jit_join.hashjoin_probe(3)", "jit_window(4)"):
        assert not pattern.search(other)


def test_the_configuration_states_what_it_must():
    cfg = cells.load_json(cells.BENCH_DIR, "configs", CONFIG)
    star = cells.load_json(cells.BENCH_DIR, "configs", "tpcds-sf10-1chip")
    assert cfg["conf"] == star["conf"]           # nothing steers the path
    for key, promise in star["guarantees"].items():
        assert cfg["guarantees"][key] == promise
    assert "lochierarchy" in cfg["guarantees"]["subtotals"]
    assert "ties share the lowest rank" in cfg["guarantees"]["ranks"]
    assert cfg["chips"] == 1 and cfg["scale_factor"] == 10
    assert cfg["queries"] == [36]
    for sibling in ("86", "70", "NOT claimed"):
        assert sibling in cfg["queries_why"]
    assert {t: spec["rows"] for t, spec in cfg["tables"].items()} == FULL
    assert {t: spec["table"] for t, spec in cfg["tables"].items()} == {
        "store_sales_margin": "store_sales", "date_dim": "date_dim",
        "item_hierarchy": "item", "store": "store"}
    assert {t: list(spec["columns"])
            for t, spec in cfg["tables"].items()} == cells.load_module(
                cells.BENCH_DIR, "queries", "q36").TABLES
    columns = cfg["tables"]
    assert "nullable" in columns["store_sales_margin"]["columns"][
        "ss_sold_date_sk"]
    for c in ("ss_net_profit", "ss_ext_sales_price"):
        assert "decimal(7,2)" in columns["store_sales_margin"]["columns"][c]
    for c in ("i_category", "i_class"):
        assert "char(50)" in columns["item_hierarchy"]["columns"][c]
    assert "char(2)" in columns["store"]["columns"]["s_state"]
    assert list(cfg["reduced"]) == [
        "scale_factor", "store_sales_columns", "date_dim_columns",
        "item_columns", "store_columns"]
    assert cfg["reduced"]["scale_factor"] == star["reduced"]["scale_factor"]
    for key, cut in (("store_sales_columns", "5 columns"),
                     ("date_dim_columns", "2 of the 28"),
                     ("item_columns", "3 of the 22"),
                     ("store_columns", "2 of the 29")):
        assert cut in cfg["reduced"][key]
    assert "23 published" in cfg["reduced"]["store_sales_columns"]
    assert "1.152 GB" in cfg["device_bytes"]["q36"]
    assert "1,164,225,444" in cfg["device_bytes"]["q36"]
    said = " ".join(cfg["assumed"])
    for word in ("int64", "uniform", "4.5%", "[-10,000, 1,400]", "-0.43",
                 "160 pairs", "171 groups", "2001", "TN, SD, AL, GA, MI, "
                 "OH, TX, CA", "dsdgen"):
        assert word in said, word
    assert "v3.2.0" in cfg["source"] and "query 36" in cfg["source"] \
        and "SF10" in cfg["source"] and len(cfg["source"]) <= 200
    [entry] = [c for c in bench()["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == list(cfg["reduced"])
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"


def test_the_generators_keep_their_domains_and_repeat_by_seed():
    drawn = {t: table(t).generate(SMALL[t], 77, SMALL) for t in SMALL}
    again = {t: table(t).generate(SMALL[t], 77, SMALL) for t in SMALL}
    other = table("store_sales_margin").generate(200_000, 78, SMALL)
    for t in SMALL:
        for c in drawn[t]:
            assert len(drawn[t][c]) == SMALL[t]
            assert pa.array(drawn[t][c]).equals(pa.array(again[t][c])), c
    sales, item, store = (drawn[t] for t in (
        "store_sales_margin", "item_hierarchy", "store"))
    assert not np.array_equal(other["ss_item_sk"], sales["ss_item_sk"])
    sold = sales["ss_sold_date_sk"]
    assert abs(sold.null_count / len(sold) - 0.045) < 0.003
    known = sold.drop_null().to_numpy()
    assert known.min() >= 2_450_816 and known.max() <= 2_452_642
    assert set(np.unique(sales["ss_item_sk"])) <= set(item["i_item_sk"])
    assert set(np.unique(sales["ss_store_sk"])) == set(store["s_store_sk"])
    profit, price = sales["ss_net_profit"], sales["ss_ext_sales_price"]
    assert -10_000 <= profit.min() and profit.max() <= 1_400
    assert 0 <= price.min() and price.max() <= 20_000
    for money in (profit, price):
        assert np.array_equal(np.round(money, 2), money)
    assert abs(profit.sum() / price.sum() + 0.43) < 0.01
    # ten categories, sixteen classes under each, at most 16 bytes
    pairs = set(zip(item["i_category"].tolist(), item["i_class"].tolist()))
    assert len(pairs) == 160
    assert len({c for c, _ in pairs}) == 10
    assert len({k for _, k in pairs}) == 16
    assert max(len(s) for pair in pairs for s in pair) <= 16
    assert np.array_equal(item["i_item_sk"], np.arange(1, 10_201))
    states = set(store["s_state"].tolist())
    assert len(states) == 10 and all(len(s) == 2 for s in states)
    assert len(states & set(cells.load_cell(CELL).query.STATES)) == 8


def loop_reference(tables):
    """Query 36 row by row: dictionaries, sums in three dictionaries, the
    rank by counting, a Python sort."""
    q = cells.load_cell(CELL).query
    days = {k for k, y in zip(tables["date_dim"]["d_date_sk"].to_pylist(),
                              tables["date_dim"]["d_year"].to_pylist())
            if y == q.YEAR}
    stores = {r["s_store_sk"] for r in tables["store"].to_pylist()
              if r["s_state"] in q.STATES}
    items = {r["i_item_sk"]: (r["i_category"], r["i_class"])
             for r in tables["item_hierarchy"].to_pylist()}
    sums = {}
    for r in tables["store_sales_margin"].to_pylist():
        if r["ss_sold_date_sk"] in days and r["ss_store_sk"] in stores \
                and r["ss_item_sk"] in items:
            category, klass = items[r["ss_item_sk"]]
            for key in ((0, category, klass), (1, category, None),
                        (2, None, None)):
                s = sums.setdefault(key, [0.0, 0.0])
                s[0] += r["ss_net_profit"]
                s[1] += r["ss_ext_sales_price"]
    rows = [(p / e, category, klass, level)
            for (level, category, klass), (p, e) in sums.items()]
    out = []
    for margin, category, klass, level in rows:
        parent = category if level == 0 else None
        smaller = sum(1 for m, c, _, lv in rows
                      if lv == level and (c if lv == 0 else None) == parent
                      and m < margin)
        out.append((margin, category, klass, level, 1 + smaller))
    out.sort(key=lambda r: (-r[3], r[1] if r[3] == 0 else "", r[4]))
    return out[:100]


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_reference_equals_a_loop_over_the_rows(seed):
    sizes = {"store_sales_margin": 2_000, "date_dim": 73_049,
             "item_hierarchy": 300, "store": 102}
    cell = cells.load_cell(CELL)
    tables = cells.make_tables(cell, seed % 2**32, sizes)
    assert tables["store_sales_margin"]["ss_sold_date_sk"].null_count > 50
    got, want = cell.query.reference(tables), loop_reference(tables)
    assert 20 <= len(want) <= 100
    assert [r[3] for r in want[:11]] == [2] + [1] * 10
    ok, worst = compare.rows_match(got, want, rtol=1e-12)
    assert ok, (worst, got[:3], want[:3])


def test_rank_ties_share_the_lowest_rank_and_gaps_follow():
    q = cells.load_cell(CELL).query
    assert q._rank([0.5, 0.1, 0.5, 0.9, 0.1]).tolist() == [3, 1, 3, 5, 1]


def made_up_evidence(counters, window=True):
    """Two queries of 1000 ns, each with one window launch."""
    thread = [(0, 1000, xplane.QUERY_SPAN), (1000, 2000, xplane.QUERY_SPAN),
              (100, 900, "srt:execute"), (1100, 1900, "srt:execute")]
    launches = [(300, 350, "jit_join.hashjoin_probe(7)"),
                (700, 720, "jit_sort.sort(9)"),
                (1700, 1720, "jit_sort.sort(9)")]
    if window:
        thread += [(600, 640, "srt:window"), (1600, 1660, "srt:window"),
                   (2100, 2900, "srt:window")]            # after the window
        launches += [(640, 700, "jit_sort.window(8)"),
                     (1660, 1700, "jit_sort.window(8)"),
                     (2900, 2990, "jit_sort.window(8)")]
    trace = xplane.Trace(
        devices=[xplane.Device(0, ops=[(300, 350, "fusion.1")],
                               launches=sorted(launches))],
        threads=[sorted(thread)], t0=0, t1=2000, queries=2)
    cell = cells.load_cell(CELL)
    return run.Evidence(cell=cell, rows=cell.rows(), queries=2,
                        counters=counters, compiles=0, spans={}, memory=[],
                        trace=trace, peaks={})


def test_the_four_new_metrics_read_the_programs_names_counters_and_span():
    ev = made_up_evidence({"expandOutputRows": 2 * 21_626_880,
                           "aggSortPathBatches": 56, "windowRows": 4_096})
    assert read("expand_rows_per_query", ev) == 21_626_880.0
    assert read("agg_sort_path_batches_per_query", ev) == 28.0
    assert read("window_ms", ev) == pytest.approx((40 + 60) / 2 / 1e6)
    assert read("window_device_ms", ev) == pytest.approx(
        (60 + 40) / 1e6 / 2)


def test_a_program_without_the_counters_or_the_span_leaves_them_out():
    # the parent: no such counter moves, no such span is opened; it has no
    # window launch in this made-up trace either
    ev = made_up_evidence({"dataSize": 10.0, "aggHostSyncs": 3},
                          window=False)
    for metric in NEW_METRICS:
        assert read(metric, ev) is None


@pytest.mark.parametrize("seed", [2**31 + 11, 7, 3_300_000_019])
def test_the_cell_rehearses_off_the_chip(seed):
    import jax
    cell = cells.load_cell(CELL)
    cell.rows = lambda lineitem_rows=0: dict(SMALL)   # on the instance only
    with tempfile.TemporaryDirectory(prefix="chipbench_") as scratch:
        rehearsal = run.Run(cell, seed, 0, scratch)
        assert rehearsal.rows == SMALL and rehearsal.warm_ok
        assert len(rehearsal.expected) == 100
        assert [r[3] for r in rehearsal.expected[:11]] == [2] + [1] * 10
        plan = rehearsal.session.plan(rehearsal.df.plan).tree_string()
        assert "Cpu" not in plan
        assert plan.count("TpuBroadcastHashJoinExec") == 3
        for op in ("TpuExpandExec", "TpuHashAggregateExec",
                   "TpuWindowExec[Rank", "TpuSortExec",
                   "TpuGlobalLimitExec[100]"):
            assert op in plan, op
        values, attempted, failed, extra = run.measured_run(rehearsal, 0.2)
        assert attempted >= 2 and failed == 0          # the traffic's
        assert extra["window_compiles"] == 0
        assert set(values) >= {"query_s", "setup_s"}
        moved = rehearsal.counters()
        assert moved.get("numCpuFallbacks", 0) == 0
        queries = cell.traffic["warmup_queries"] + attempted
        # one stream batch of capacity 262,144 through three joins; 1 in 5
        # rows meets the year, so the Expand's input capacity is 65,536 or
        # 32,768, its output three times that, and the one whole-stage
        # sort program's 171 rows leave at that capacity for the window
        assert moved["joinMergedWindowBatches"] == 3 * queries
        assert moved["expandBatches"] == queries
        fan_out = moved["expandOutputRows"] // queries
        assert fan_out in (3 * 32_768, 3 * 65_536)
        assert moved["expandOutputRows"] == fan_out * queries
        assert moved["aggSortPathBatches"] == queries
        assert moved["windowBatches"] == queries
        assert moved["windowRows"] == fan_out * queries
        assert rehearsal.worst_err < compare.DOUBLE_RTOL
        if seed != 7:
            return
        # one traced run: the span and the counters as metrics
        device = {}
        values, attempted, failed, extra = run.traced_run(
            rehearsal, 0.2, jax.devices(), os.path.join(scratch, "trace"),
            device)
        assert attempted >= 1 and failed == 0
        assert values["expand_rows_per_query"] == fan_out
        assert values["agg_sort_path_batches_per_query"] == 1.0
        assert values["join_host_syncs_per_query"] == 6.0   # 2 a join
        assert 0 < values["window_ms"] < 60_000
        assert "window_device_ms" not in values     # no device plane here
        per_query = extra["session_metrics_per_query"]
        assert per_query["windowRows"] == fan_out
        assert per_query["windowBatches"] == 1.0
