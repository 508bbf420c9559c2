"""The cell `tpch_q1_sf10_resident` (configuration `tpch-sf10-q1-1chip`:
TPC-H query 1 over LINEITEM at SF10, 4.32 GB resident), from the real
`BENCHMARK.json`: what the cell and the configuration state, the query
module's guard on the scan cache's bound, the rehearsal off the chip, and
the two new per-layer metrics read from made-up evidence."""
import json
import os
from types import SimpleNamespace

import pyarrow as pa
import pytest

import cells
import run
import xplane
from test_readers import read
from test_run import run_py

CELL = "tpch_q1_sf10_resident"
CONFIG = "tpch-sf10-q1-1chip"
NEW_METRICS = ("scan_cache_hit_batches_per_query", "agg_fold_ms")
APPENDED = ("agg_device_ms", "agg_owned_device_ms",
            "agg_streamed_batches_per_query", "agg_host_syncs_per_query",
            "agg_shrink_ms", "agg_dense_batches_per_query",
            "agg_sort_path_batches_per_query")


def names(cell):
    return [m["name"] for m, _ in cell.per_layer]


def test_the_cell_and_what_it_reports():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    assert cell.rows()["lineitem"] == 60_000_000
    assert cell.traffic == {**cell.traffic, "query": "q1_sf10",
                            "residency": "device", "warmup_queries": 3,
                            "min_queries": 5, "trace_seconds": 3,
                            "trace_min_queries": 2, "rows_in": 60_000_000}
    assert set(NEW_METRICS) | set(APPENDED) <= set(names(cell))
    assert {"hbm_roofline_share", "hbm_peak_gb",
            "device_idle_share"} <= set(names(cell))
    assert [m["name"] for m in cell.end_to_end] == ["query_s", "setup_s"]
    # q1.py's query, reference and bytes, letter for letter
    q1 = cells.load_cell("tpch_q1_resident").query
    assert cell.query.TABLES == q1.TABLES
    for fn in ("reference", "bytes_needed", "build"):
        assert (getattr(cell.query.q1, fn).__code__.co_code
                == getattr(q1, fn).__code__.co_code), fn
    assert cell.query.bytes_needed(cell.rows()) == 60_000_000 * 42
    # 57 batches of 1,048,576 rows and one of 262,144 capacity, 72 B a row
    assert cell.query.resident_bytes(60_000_000) == 4_322_230_272
    assert cell.query.resident_bytes(200_000) == 262_144 * 72
    # the fold span is read in the other grouped-loop cell too; the new
    # counter only here
    rollup = names(cells.load_cell("tpcds_q36_rollup_sf10"))
    assert "agg_fold_ms" in rollup
    assert "scan_cache_hit_batches_per_query" not in rollup


def test_the_configuration_states_source_cuts_and_assumptions():
    cfg = cells.load_json(cells.BENCH_DIR, "configs", CONFIG)
    sf10 = cells.load_json(cells.BENCH_DIR, "configs", "tpch-sf10-1chip")
    assert cfg["conf"] == sf10["conf"]          # nothing steers the path
    assert cfg["scale_factor"] == 10 and cfg["chips"] == 1
    assert {t: s["rows"] for t, s in cfg["tables"].items()} == {
        "lineitem": 60_000_000, "orders": 15_000_000}
    q1 = cells.load_cell(CELL).query
    assert set(cfg["tables"]["lineitem"]["columns"]) == set(
        q1.TABLES["lineitem"])
    assert list(cfg["reduced"]) == ["lineitem_columns"]
    assert "7 columns" in cfg["reduced"]["lineitem_columns"]
    said = " ".join(cfg["assumed"])
    for word in ("uniform", "int64", "59,986,052", "ORDERS"):
        assert word in said, word
    assert "4,322,230,272" in cfg["device_bytes"]["q1"]
    assert "residency" in cfg["guarantees"]
    assert "query 1" in cfg["source"] and "SF10" in cfg["source"]
    with open(os.path.join(os.path.dirname(cells.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        [entry] = [c for c in json.load(f)["configs"]
                   if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == list(cfg["reduced"])


def _frames(rows):
    """A stand-in for the cell's frame: the guard reads only its rows."""
    return {"lineitem": SimpleNamespace(
        plan=SimpleNamespace(source=SimpleNamespace(num_rows=rows)))}


@pytest.mark.parametrize("conf,builds", [
    ({}, True),                 # half of the nominal 16 GiB pool, 7.2 GiB
    ({"spark.rapids.sql.tpu.memoryScanCache.maxSize": "4g"}, False),
    ({"spark.rapids.memory.tpu.poolSizeBytes": "8g"}, False),
], ids=["half_the_pool", "the_old_fixed_bound", "a_pool_of_8g"])
def test_the_guard_refuses_a_bound_below_the_tables_bytes(conf, builds):
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.utils.scan_cache import resident_bound
    query = cells.load_cell(CELL).query
    session = TpuSession(conf)
    assert query.scan_cache_bound(session) == resident_bound(session.conf)
    if not builds:
        with pytest.raises(RuntimeError, match="4,322,230,272"):
            query.build(session, _frames(60_000_000))
        return
    # past the guard: q1.py's DataFrame over a real (small) frame
    table = pa.table({c: pa.array([], pa.float64()) for c in
                      query.TABLES["lineitem"]})
    frames = {"lineitem": session.from_arrow(table)}
    frames["lineitem"].plan.source = SimpleNamespace(num_rows=60_000_000)
    assert query.build(session, frames) is not None


def test_a_program_without_the_rule_is_held_to_its_conf(monkeypatch):
    """The parent has no `resident_bound`: its bound is the conf's, a fixed
    4 GiB there, and the cell refuses at once."""
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.utils import scan_cache
    query = cells.load_cell(CELL).query
    monkeypatch.delattr(scan_cache, "resident_bound")
    session = TpuSession(
        {"spark.rapids.sql.tpu.memoryScanCache.maxSize": str(4 << 30)})
    assert query.scan_cache_bound(session) == 4 << 30
    with pytest.raises(RuntimeError, match="past the scan cache's bound"):
        query.build(session, _frames(60_000_000))


def test_the_cell_rehearses_off_the_chip():
    for trace, reports in ((0, "query_s"),
                           (1, "scan_cache_hit_batches_per_query")):
        p = run_py("--workload", CELL, "--seconds", "0.5", "--trace",
                   str(trace), "--rows", "200000")
        assert p.returncode == 1, p.stderr[-2000:]
        line = json.loads(p.stdout.splitlines()[-1])
        assert line["correct"] is False and line["metrics"] == {}
        assert line["device"]["platform"] == "cpu"
        assert line["failed"] == 0
        assert line["attempted"] >= (2 if trace else 5)   # the traffic's
        assert line["rehearsal"]["answers_right"] is True
        assert line["numCpuFallbacks"] == 0
        assert line["window_compiles"] == 0
        assert reports in line["rehearsal"]["would_report"]
        if trace:
            # 200,000 rows are one batch: a cache hit a query, and the
            # whole-stage bucket program answers (no loop, no fold)
            moved = line["session_metrics_per_query"]
            assert moved["scanCacheHitBatches"] == 1.0
            assert "aggStreamedBatches" not in moved


def made_up_evidence(counters, folds=True):
    """Two queries of 1000 ns, a fold in each and one after the window."""
    thread = [(0, 1000, xplane.QUERY_SPAN), (1000, 2000, xplane.QUERY_SPAN),
              (100, 900, "srt:execute"), (1100, 1900, "srt:execute"),
              (300, 360, "srt:agg_update"), (1300, 1360, "srt:agg_update")]
    if folds:
        thread += [(400, 700, "srt:agg_fold"), (450, 650, "srt:agg_merge"),
                   (1400, 1500, "srt:agg_fold"),
                   (2100, 2900, "srt:agg_fold")]   # after the window
    trace = xplane.Trace(
        devices=[xplane.Device(0, ops=[(300, 350, "fusion.1")],
                               launches=[(300, 350, "jit_agg.bucket(7)")])],
        threads=[sorted(thread)], t0=0, t1=2000, queries=2)
    cell = cells.load_cell(CELL)
    return run.Evidence(cell=cell, rows=cell.rows(), queries=2,
                        counters=counters, compiles=0, spans={}, memory=[],
                        trace=trace, peaks={})


def test_the_two_new_metrics_read_the_programs_counter_and_span():
    ev = made_up_evidence({"scanCacheHitBatches": 116, "aggHostSyncs": 362})
    assert read("scan_cache_hit_batches_per_query", ev) == 58.0
    assert read("agg_fold_ms", ev) == pytest.approx((300 + 100) / 2 / 1e6)
    assert read("agg_host_syncs_per_query", ev) == 181.0
    # a table that lost residency reads 0, not absent
    ev = made_up_evidence({"scanCacheHitBatches": 0})
    assert read("scan_cache_hit_batches_per_query", ev) == 0.0


def test_a_program_without_the_counter_or_the_span_leaves_them_out():
    # the parent: no such counter moves, no such span is opened
    ev = made_up_evidence({"aggStreamedBatches": 116}, folds=False)
    for metric in NEW_METRICS:
        assert read(metric, ev) is None, metric
    assert read("agg_streamed_batches_per_query", ev) == 58.0
