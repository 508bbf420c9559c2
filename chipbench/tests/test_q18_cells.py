"""PR 37's cell `tpch_q18_resident` (configuration `tpch-sf1-q18-1chip`:
TPC-H query 18 as published over CUSTOMER, ORDERS and LINEITEM at SF1),
from the real `BENCHMARK.json`: what the configuration states, the two new
per-layer metrics and the reader kind `program_roofline` on made-up
evidence, and the rehearsal off the chip.

`run.py --rows` scales every table by `lineitem`, which this configuration
does not have (its tables are named by their generators), so the cell
rehearses through `run.Run` with the instance's `rows` shadowed, as the
TPC-DS cells do."""
import os
import tempfile

import pytest

import cells
import run
import xplane
from test_readers import read

CELL = "tpch_q18_resident"
CONFIG = "tpch-sf1-q18-1chip"
FULL = {"customer": 150_000, "orders_priced": 1_500_000,
        "lineitem_clustered": 6_000_000}
SMALL = {"customer": 5_000, "orders_priced": 50_000,
         "lineitem_clustered": 200_000}
NEW_METRICS = ("join_semi_batches_per_query",
               "agg_whole_stage_roofline_share")
APPENDED = ("agg_device_ms", "agg_owned_device_ms",
            "agg_sort_path_batches_per_query", "join_device_ms",
            "join_owned_device_ms", "join_walk_steps_per_query",
            "join_host_syncs_per_query")


def names(cell):
    return [m["name"] for m, _ in cell.per_layer]


def test_the_cell_and_what_it_reports():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    assert cell.rows() == FULL
    assert cell.traffic == {**cell.traffic, "query": "q18",
                            "residency": "device", "warmup_queries": 2,
                            "min_queries": 2, "trace_seconds": 1,
                            "trace_min_queries": 1,
                            "rows_in": sum(FULL.values())}
    assert set(NEW_METRICS) | set(APPENDED) <= set(names(cell))
    assert [m["name"] for m in cell.end_to_end] == ["query_s", "setup_s"]
    assert cell.query.QUANTITY == 300
    # LINEITEM's two columns twice, ORDERS' four once, CUSTOMER's key and
    # 18-byte name once; the sort program: 16 B a line in, 16 an order out
    assert cell.query.bytes_needed(FULL) == 243_900_000
    assert cell.query.agg_sort_bytes_needed(FULL) == 120_000_000
    for other in ("tpch_q3_join_resident", "tpcds_q52_star_sf10"):
        assert not set(NEW_METRICS) & set(names(cells.load_cell(other)))
    sf1 = cells.load_json(cells.BENCH_DIR, "configs", "tpch-sf1-1chip")
    assert cell.config["conf"] == sf1["conf"]


def test_the_configuration_states_source_cuts_and_assumptions():
    cfg = cells.load_json(cells.BENCH_DIR, "configs", CONFIG)
    assert {t: spec["rows"] for t, spec in cfg["tables"].items()} == FULL
    assert {t: spec["table"] for t, spec in cfg["tables"].items()} == {
        "customer": "customer", "orders_priced": "orders",
        "lineitem_clustered": "lineitem"}
    assert list(cfg["reduced"]) == ["scale_factor", "lineitem_columns",
                                    "orders_columns", "customer_columns"]
    for key, cut in (("lineitem_columns", "2 columns"),
                     ("orders_columns", "4 columns"),
                     ("customer_columns", "2 columns")):
        assert cut in cfg["reduced"][key]
    said = " ".join(cfg["assumed"])
    for word in ("uniform", "[900, 2,100]", "int64", "6,000,000",
                 "QUANTITY is 300", "tie"):
        assert word in said, word
    assert "243,900,000" in cfg["device_bytes"]["q18"]
    assert "query 18" in cfg["source"] and len(cfg["source"]) <= 200
    [entry] = [c for c in cells.load_json(os.path.dirname(cells.BENCH_DIR),
                                          "", "BENCHMARK")["configs"]
               if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == list(cfg["reduced"])


def made_up_evidence(counters, launches):
    """Two queries of 1000 ns on one chip."""
    thread = [(0, 1000, xplane.QUERY_SPAN), (1000, 2000, xplane.QUERY_SPAN)]
    trace = xplane.Trace(
        devices=[xplane.Device(0, ops=[(s, e, "fusion.1")
                                       for s, e, _ in launches],
                               launches=sorted(launches))],
        threads=[thread], t0=0, t1=2000, queries=2)
    cell = cells.load_cell(CELL)
    return run.Evidence(cell=cell, rows=cell.rows(), queries=2,
                        counters=counters, compiles=0, spans={}, memory=[],
                        trace=trace, peaks={"hbm_bytes_per_s": 819e9})


def test_program_roofline_reads_the_sort_program_alone():
    launches = [(100, 400, "jit_agg.whole_stage(3)"),
                (400, 450, "jit_agg.whole_stage_bucket(4)"),
                (500, 560, "jit_join.hashjoin_semi(5)"),
                (1100, 1300, "jit_agg.whole_stage(3)"),
                (2100, 2900, "jit_agg.whole_stage(3)")]   # after the window
    ev = made_up_evidence({"joinSemiBatches": 4}, launches)
    # 120 MB over 819 GB/s, over 250 ns a query
    want = 100 * (120_000_000 / 819e9) / (250 / 1e9)
    assert read("agg_whole_stage_roofline_share", ev) == pytest.approx(want)
    assert read("join_semi_batches_per_query", ev) == 2.0
    assert read("join_device_ms", ev) == pytest.approx(60 / 1e6 / 2)
    reader = cells.load_module(cells.BENCH_DIR, "readers",
                               "program_roofline")
    # the bucket program alone, by its own name
    assert reader.read(ev, r"^jit_agg\.whole_stage_bucket\(",
                       "agg_sort_bytes_needed") == pytest.approx(
        100 * (120_000_000 / 819e9) / (25 / 1e9))
    # a query module without the function, a trace without a device
    assert reader.read(ev, r"^jit_agg\.", "no_such_bytes") is None
    ev.trace.devices = []
    assert reader.read(ev, r"^jit_agg\.", "agg_sort_bytes_needed") is None


def test_a_program_without_the_counter_or_the_sort_program_leaves_them_out():
    # the parent has no joinSemiBatches; a bucket program answered instead
    ev = made_up_evidence({"joinWalkSteps": 68},
                          [(100, 400, "jit_agg.whole_stage_bucket(4)")])
    for metric in NEW_METRICS:
        assert read(metric, ev) is None, metric
    assert read("join_walk_steps_per_query", ev) == 34.0


@pytest.mark.parametrize("seed", [2**31 + 11, 3_700_000_019])
def test_the_cell_rehearses_off_the_chip(seed):
    import jax
    cell = cells.load_cell(CELL)
    cell.rows = lambda lineitem_rows=0: dict(SMALL)   # on the instance only
    with tempfile.TemporaryDirectory(prefix="chipbench_") as scratch:
        rehearsal = run.Run(cell, seed, 0, scratch)
        assert rehearsal.rows == SMALL and rehearsal.warm_ok
        plan = rehearsal.session.plan(rehearsal.df.plan).tree_string()
        assert "Cpu" not in plan
        assert "BroadcastHashJoinExec[left_semi" in plan
        assert "TpuGlobalLimitExec[100]" in plan
        values, attempted, failed, extra = run.measured_run(rehearsal, 0.2)
        assert attempted >= 2 and failed == 0
        assert extra["window_compiles"] == 0
        assert set(values) >= {"query_s", "setup_s"}
        moved = rehearsal.counters()
        assert moved.get("numCpuFallbacks", 0) == 0
        queries = cell.traffic["warmup_queries"] + attempted
        # ORDERS in one batch through the semi join's mask
        assert moved["joinSemiBatches"] == queries
        if seed != 2**31 + 11:
            return
        device = {}
        values, attempted, failed, extra = run.traced_run(
            rehearsal, 0.2, jax.devices(), os.path.join(scratch, "trace"),
            device)
        assert attempted >= 1 and failed == 0
        assert values["join_semi_batches_per_query"] == 1.0
        # LINEITEM in one batch, into the grouped whole-stage sort program
        assert values["agg_sort_path_batches_per_query"] >= 1.0
        # no device plane off the chip: nothing to read
        assert "agg_whole_stage_roofline_share" not in values
