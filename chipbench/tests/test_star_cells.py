"""PR 31's two cells, from the real `BENCHMARK.json`: `tpcds_q52_star_sf10`
(configuration `tpcds-sf10-1chip`: TPC-DS STORE_SALES at SF10 cut by
DATE_DIM and ITEM through two broadcast joins) and `tpch_q1_parquet` (Q1 of
`tpch-sf1-1chip` from the Parquet file): what the configuration states, the
generators' domains, the plain reference against a loop, the three new
per-layer metrics from made-up evidence, and both rehearsals off the chip.

`run.py --rows` scales every table by `lineitem`, which this configuration
does not have, so the star cell rehearses through `run.Run` with the
instance's `rows` shadowed by a scaled table of rows."""
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
from test_run import run_py

STAR = "tpcds_q52_star_sf10"
Q1PQ = "tpch_q1_parquet"
FULL = {"store_sales": 28_800_991, "date_dim": 73_049, "item": 102_000}
SMALL = {"store_sales": 300_000, "date_dim": 73_049, "item": 10_200}
NEW_METRICS = ("join_broadcast_ms", "join_broadcast_mb_per_query",
               "join_host_syncs_per_query")


def names(cell):
    return [m["name"] for m, _ in cell.per_layer]


def config(name):
    return cells.load_json(cells.BENCH_DIR, "configs", name)


def table(name):
    return cells.load_module(cells.BENCH_DIR, "tables", name)


def test_the_star_cell_and_what_it_reports():
    cell = cells.load_cell(STAR)
    assert cell.chips == 1 and cell.config["name"] == "tpcds-sf10-1chip"
    assert cell.rows() == FULL
    assert cell.traffic == {**cell.traffic, "query": "q52",
                            "residency": "device", "warmup_queries": 3,
                            "min_queries": 5, "trace_seconds": 3,
                            "trace_min_queries": 2,
                            "rows_in": sum(FULL.values())}
    assert cell.traffic["rows_in"] == 28_976_040
    assert set(NEW_METRICS) | {
        "join_device_ms", "agg_device_ms",
        "join_merged_window_batches_per_query", "hbm_roofline_share",
        "hbm_peak_gb", "device_idle_share"} <= set(names(cell))
    assert not {"scan_device_ms", "collective_share"} & set(names(cell))
    assert [m["name"] for m in cell.end_to_end] == ["query_s", "setup_s"]
    # three 8-byte columns of the fact table and of the dates, three and a
    # char(50) of the items
    assert cell.query.bytes_needed(FULL) == (
        28_800_991 * 24 + 102_000 * 74 + 73_049 * 24) == 700_524_960
    # no other cell reports what only a broadcast has
    for other in ("tpch_q3_join_resident", Q1PQ):
        assert not set(NEW_METRICS) & set(names(cells.load_cell(other)))


def test_the_parquet_q1_cell_is_q1_over_the_sf1_files():
    cell, resident = cells.load_cell(Q1PQ), cells.load_cell("tpch_q1_resident")
    assert cell.chips == 1 and cell.config == resident.config
    assert cell.query.__file__ == resident.query.__file__
    scan = cells.load_cell("tpch_q6_parquet").traffic
    assert cell.traffic == {**scan, "query": "q1",
                            "why": cell.traffic["why"]}
    assert cell.traffic["residency"] == "parquet"
    assert {"h2d_mb_per_query", "scan_device_ms", "scan_host_ms",
            "agg_device_ms", "agg_dense_batches_per_query"} <= set(
                names(cell))
    assert "join_device_ms" not in names(cell)


def test_the_star_configuration_states_what_it_must():
    star, sf1 = config("tpcds-sf10-1chip"), config("tpch-sf1-1chip")
    assert star["conf"] == sf1["conf"]            # nothing steers the path
    for key, promise in sf1["guarantees"].items():
        assert star["guarantees"][key] == promise
    assert "NULL ss_sold_date_sk matches no DATE_DIM row" in \
        star["guarantees"]["null_keys"]
    assert star["chips"] == 1 and star["scale_factor"] == 10
    assert star["queries"] == [3, 42, 52, 55]
    assert {t: spec["rows"] for t, spec in star["tables"].items()} == FULL
    assert {t: list(spec["columns"])
            for t, spec in star["tables"].items()} == cells.load_module(
                cells.BENCH_DIR, "queries", "q52").TABLES
    assert "nullable" in star["tables"]["store_sales"]["columns"][
        "ss_sold_date_sk"]
    assert "char(50)" in star["tables"]["item"]["columns"]["i_brand"]
    assert list(star["reduced"]) == ["scale_factor", "store_sales_columns",
                                     "item_columns", "date_dim_columns"]
    assert "SF100" in star["reduced"]["scale_factor"]
    assert "0.691 GB" in star["device_bytes"]["q52"]
    said = " ".join(star["assumed"])
    for word in ("int64", "uniform", "4.5%", "1..100", "960", "dsdgen"):
        assert word in said, word
    assert "v3.2.0" in star["source"] and "query 52" in star["source"] \
        and "SF10" in star["source"] and len(star["source"]) < 200
    with open(os.path.join(os.path.dirname(cells.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        [entry] = [c for c in json.load(f)["configs"]
                   if c["name"] == star["name"]]
    assert entry["source"] == star["source"]
    assert entry["reduced"] == list(star["reduced"])


def test_the_generators_keep_their_domains_and_repeat_by_seed():
    drawn = {t: table(t).generate(SMALL[t], 77, SMALL) for t in SMALL}
    again = {t: table(t).generate(SMALL[t], 77, SMALL) for t in SMALL}
    other = table("store_sales").generate(SMALL["store_sales"], 78, SMALL)
    for t in SMALL:
        for c in drawn[t]:
            assert len(drawn[t][c]) == SMALL[t]
            assert pa.array(drawn[t][c]).equals(pa.array(again[t][c])), c
    assert not pa.array(other["ss_item_sk"]).equals(
        pa.array(drawn["store_sales"]["ss_item_sk"]))
    dates, item, sales = (drawn[t] for t in ("date_dim", "item",
                                              "store_sales"))
    # the calendar: 1900-01-02 .. 2100-01-01, 30 days of November 2000
    assert dates["d_date_sk"][0] == 2_415_022
    assert dates["d_date_sk"][-1] == 2_488_070
    assert (dates["d_year"][0], dates["d_moy"][0]) == (1900, 1)
    assert (dates["d_year"][-1], dates["d_moy"][-1]) == (2100, 1)
    november = (dates["d_year"] == 2000) & (dates["d_moy"] == 11)
    assert november.sum() == 30
    assert dates["d_date_sk"][november][0] == 2_451_850
    # the facts point into both dimensions; 4.5% have no date
    sold = sales["ss_sold_date_sk"]
    assert abs(sold.null_count / len(sold) - 0.045) < 0.002
    known = sold.drop_null().to_numpy()
    assert known.min() == 2_450_816 and known.max() == 2_452_642
    assert set(np.unique(sales["ss_item_sk"])) <= set(item["i_item_sk"])
    assert sales["ss_item_sk"].min() == 1
    price = sales["ss_ext_sales_price"]
    assert 0 <= price.min() and price.max() <= 20_000
    assert np.array_equal(np.round(price, 2), price)
    # managers 1..100, a brand at most 50 bytes wide, one name an id
    assert set(np.unique(item["i_manager_id"])) == set(range(1, 101))
    assert np.array_equal(item["i_item_sk"], np.arange(1, 10_201))
    widths = np.char.str_len(item["i_brand"])
    assert 12 <= widths.min() and widths.max() <= 22 <= 50
    pairs = set(zip(item["i_brand_id"].tolist(), item["i_brand"].tolist()))
    assert len(pairs) == len({i for i, _ in pairs}) == len(
        {b for _, b in pairs}) == 960


def loop_reference(tables):
    """Query 52 row by row, dictionaries and a Python sort."""
    days = {k for k, y, m in zip(*(tables["date_dim"][c].to_pylist()
                                   for c in ("d_date_sk", "d_year", "d_moy")))
            if y == 2000 and m == 11}
    item = tables["item"].to_pylist()
    brands = {r["i_item_sk"]: (r["i_brand"], r["i_brand_id"]) for r in item
              if r["i_manager_id"] == 1}
    sums = {}
    for r in tables["store_sales"].to_pylist():
        if r["ss_sold_date_sk"] in days and r["ss_item_sk"] in brands:
            key = (2000,) + brands[r["ss_item_sk"]]
            sums[key] = sums.get(key, 0.0) + r["ss_ext_sales_price"]
    rows = [key + (total,) for key, total in sums.items()]
    return sorted(rows, key=lambda r: (r[0], -r[3], r[2]))[:100]


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_the_reference_equals_a_loop_over_the_rows(seed):
    sizes = {"store_sales": 120_000, "date_dim": 73_049, "item": 1_000}
    cell = cells.load_cell(STAR)
    tables = cells.make_tables(cell, seed % 2**32, sizes)
    assert tables["store_sales"]["ss_sold_date_sk"].null_count > 4_000
    got, want = cell.query.reference(tables), loop_reference(tables)
    assert len(want) >= 3
    ok, worst = compare.rows_match(got, want, rtol=1e-12)
    assert ok, (worst, got[:3], want[:3])


def made_up_evidence(counters, broadcasts=True):
    """Two queries of 1000 ns, each with two broadcast build sides."""
    thread = [(0, 1000, xplane.QUERY_SPAN), (1000, 2000, xplane.QUERY_SPAN),
              (100, 900, "srt:execute"), (1100, 1900, "srt:execute"),
              (300, 360, "srt:join_stream"), (1300, 1360, "srt:join_stream")]
    if broadcasts:
        thread += [(110, 150, "srt:broadcast_collect"),
                   (150, 160, "srt:broadcast_upload"),
                   (200, 260, "srt:broadcast_collect"),
                   (1110, 1140, "srt:broadcast_collect"),
                   (1200, 1270, "srt:broadcast_collect"),
                   (2100, 2900, "srt:broadcast_collect")]  # after the window
    trace = xplane.Trace(
        devices=[xplane.Device(0, ops=[(300, 350, "fusion.1")],
                               launches=[(300, 350,
                                          "jit_join.hashjoin_probe(7)")])],
        threads=[sorted(thread)], t0=0, t1=2000, queries=2)
    cell = cells.load_cell(STAR)
    return run.Evidence(cell=cell, rows=cell.rows(), queries=2,
                        counters=counters, compiles=0, spans={}, memory=[],
                        trace=trace, peaks={})


def test_the_three_new_metrics_read_the_programs_counters_and_span():
    ev = made_up_evidence({"broadcastBytes": 2 * 9_409_536,
                           "joinHostSyncs": 116, "broadcastRows": 2_100})
    assert read("join_broadcast_mb_per_query", ev) == pytest.approx(9.409536)
    assert read("join_host_syncs_per_query", ev) == 58.0
    assert read("join_broadcast_ms", ev) == pytest.approx(
        (40 + 60 + 30 + 70) / 4 / 1e6)
    assert read("join_device_ms", ev) == pytest.approx(50 / 1e6 / 2)


def test_a_program_without_the_counters_or_the_span_leaves_them_out():
    # the parent: no such counter moves, no such span is opened
    ev = made_up_evidence({"dataSize": 10.0, "joinTime": 1.0},
                          broadcasts=False)
    for metric in NEW_METRICS:
        assert read(metric, ev) is None


def test_the_star_cell_rehearses_off_the_chip():
    cell = cells.load_cell(STAR)
    cell.rows = lambda lineitem_rows=0: dict(SMALL)   # on the instance only
    with tempfile.TemporaryDirectory(prefix="chipbench_") as scratch:
        rehearsal = run.Run(cell, 2**31 + 11, 0, scratch)
        assert rehearsal.rows == SMALL and rehearsal.warm_ok
        assert 10 <= len(rehearsal.expected) <= 100
        values, attempted, failed, extra = run.measured_run(rehearsal, 0.5)
        assert attempted >= 5 and failed == 0          # the traffic's
        assert extra["window_compiles"] == 0
        assert set(values) >= {"query_s", "setup_s"}
        moved = rehearsal.counters()
        assert moved.get("numCpuFallbacks", 0) == 0
        queries = cell.traffic["warmup_queries"] + attempted
        # one stream batch through each of two joins, two broadcasts a query
        assert moved["joinMergedWindowBatches"] == 2 * queries
        assert moved["joinHostSyncs"] == 4 * queries
        assert moved["broadcastBytes"] == moved["dataSize"] > 0
        assert rehearsal.worst_err < compare.DOUBLE_RTOL


def test_the_parquet_q1_cell_rehearses_off_the_chip():
    for trace, reports in ((0, "query_s"), (1, "scan_host_ms")):
        p = run_py("--workload", Q1PQ, "--seconds", "0.5", "--trace",
                   str(trace), "--rows", "200000")
        assert p.returncode == 1, p.stderr[-2000:]
        line = json.loads(p.stdout.splitlines()[-1])
        assert line["correct"] is False and line["metrics"] == {}
        assert line["device"]["platform"] == "cpu"
        assert line["failed"] == 0 and line["attempted"] >= 2
        assert line["rehearsal"]["answers_right"] is True
        assert line["numCpuFallbacks"] == 0
        assert line["window_compiles"] == 0
        assert reports in line["rehearsal"]["would_report"]
