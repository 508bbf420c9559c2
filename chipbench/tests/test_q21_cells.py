"""The cell `tpch_q21_resident` (configuration `tpch-sf1-q21-1chip`: TPC-H
query 21 as published over SUPPLIER, LINEITEM, ORDERS and NATION at SF1),
from the real `BENCHMARK.json`: what the configuration states, the four new
per-layer metrics on made-up evidence, the lists they were appended to, and
the rehearsal off the chip.

`run.py --rows` scales every table by `lineitem`, which this configuration
does not have (its tables are named by their generators), so the cell
rehearses through `run.Run` with the instance's `rows` shadowed, as the
Q18 cell does."""
import os
import tempfile

import pytest

import cells
import run
import xplane
from test_readers import BENCH, read

CELL = "tpch_q21_resident"
CONFIG = "tpch-sf1-q21-1chip"
FULL = {"lineitem_supplied": 6_000_000, "orders_status": 1_500_000,
        "supplier": 10_000, "nation": 25}
SMALL = {"lineitem_supplied": 120_000, "orders_status": 30_000,
         "supplier": 200, "nation": 25}
NEW_METRICS = ("join_residual_batches_per_query",
               "join_anti_batches_per_query", "join_residual_device_ms",
               "join_residual_roofline_share")
APPENDED = ("join_device_ms", "join_owned_device_ms",
            "join_walk_steps_per_query", "join_semi_batches_per_query",
            "join_host_syncs_per_query", "agg_device_ms",
            "agg_owned_device_ms", "join_merged_window_batches_per_query",
            "join_output_space_batches_per_query", "join_broadcast_ms",
            "join_broadcast_mb_per_query",
            "scan_cache_hit_batches_per_query", "agg_host_syncs_per_query")
#: the appended metrics read from the session's counters and host spans,
#: so a traced rehearsal off the chip reads them too
READ_OFF_THE_CHIP = APPENDED[2:5] + APPENDED[7:]


def names(cell):
    return [m["name"] for m, _ in cell.per_layer]


def test_the_cell_and_what_it_reports():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    assert cell.rows() == FULL
    assert cell.traffic == {**cell.traffic, "query": "q21",
                            "residency": "device", "warmup_queries": 2,
                            "min_queries": 2, "trace_seconds": 1,
                            "trace_min_queries": 1,
                            "rows_in": 7_510_025}
    assert sum(FULL.values()) == 7_510_025
    assert set(NEW_METRICS) | set(APPENDED) <= set(names(cell))
    assert [m["name"] for m in cell.end_to_end] == ["query_s", "setup_s"]
    assert cell.query.NATION == "SAUDI ARABIA"
    # LINEITEM's four columns for l1 and l3, two for l2; ORDERS' key and
    # status, SUPPLIER's key, name and nation, NATION's key and name once
    assert cell.query.bytes_needed(FULL) == 493_840_825
    # the two conditional joins: 16 B a row of stream and build, a mask
    # byte a stream row
    assert cell.query.residual_bytes_needed(FULL) == 283_366_293
    q18 = cells.load_json(cells.BENCH_DIR, "configs", "tpch-sf1-q18-1chip")
    assert cell.config["conf"] == q18["conf"]


def test_the_new_metrics_are_this_cells_alone_and_the_lists_were_appended():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for metric in NEW_METRICS:
        assert by_name[metric]["workloads"] == [CELL]
        assert by_name[metric]["layer"] == "Operators"
        assert by_name[metric]["moves"] == "query_s"
    for metric in APPENDED:
        assert by_name[metric]["workloads"][-1] == CELL
        assert by_name[metric]["workloads"].count(CELL) == 1
    for other in BENCH["workloads"]:
        if other["name"] != CELL:
            reported = set(names(cells.load_cell(other["name"])))
            assert not set(NEW_METRICS) & reported, other["name"]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == CONFIG


def test_the_configuration_states_source_cuts_and_assumptions():
    cfg = cells.load_json(cells.BENCH_DIR, "configs", CONFIG)
    assert {t: spec["rows"] for t, spec in cfg["tables"].items()} == FULL
    assert {t: spec["table"] for t, spec in cfg["tables"].items()} == {
        "supplier": "supplier", "lineitem_supplied": "lineitem",
        "orders_status": "orders", "nation": "nation"}
    assert list(cfg["reduced"]) == ["scale_factor", "lineitem_columns",
                                    "orders_columns", "supplier_columns",
                                    "nation_columns"]
    for key, cut in (("lineitem_columns", "4 columns"),
                     ("orders_columns", "2 columns"),
                     ("supplier_columns", "3 columns"),
                     ("nation_columns", "2 columns")):
        assert cut in cfg["reduced"][key]
    said = " ".join(cfg["assumed"])
    for word in ("uniform", "int64", "6,000,000", "SAUDI ARABIA", "unique"):
        assert word in said, word
    assert "493,840,825" in cfg["device_bytes"]["q21"]
    assert "283,366,293" in cfg["device_bytes"]["q21"]
    assert "query 21" in cfg["source"] and len(cfg["source"]) <= 200
    [entry] = [c for c in BENCH["configs"] if c["name"] == CONFIG]
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


def test_the_residual_readers_take_the_conditional_walk_alone():
    launches = [(100, 400, "jit_join.hashjoin_probe_residual(3)"),
                (400, 450, "jit_join.hashjoin_probe(4)"),
                (450, 500, "jit_join.hashjoin_count_residual(8)"),
                (500, 560, "jit_join.hashjoin_semi(5)"),
                (560, 570, "jit_join.hashjoin_rest_residual(6)"),
                (1100, 1300, "jit_join.hashjoin_probe_residual(3)"),
                (2100, 2900, "jit_join.hashjoin_probe_residual(3)")]
    ev = made_up_evidence({"joinResidualBatches": 32, "joinAntiBatches": 16,
                           "joinSemiBatches": 32}, launches)
    assert read("join_residual_batches_per_query", ev) == 16.0
    assert read("join_anti_batches_per_query", ev) == 8.0
    assert read("join_semi_batches_per_query", ev) == 16.0
    # 300 + 50 + 10 + 200 ns of the walk over two queries
    assert read("join_residual_device_ms", ev) == pytest.approx(280 / 1e6)
    assert read("join_device_ms", ev) == pytest.approx(670 / 1e6 / 2)
    want = 100 * (283_366_293 / 819e9) / (280 / 1e9)
    assert read("join_residual_roofline_share", ev) == pytest.approx(want)


def test_a_program_without_the_names_or_counters_leaves_them_out():
    # the parent: the conditional walk launches as the equi join's probe,
    # and neither counter exists
    ev = made_up_evidence({"joinSemiBatches": 32, "joinWalkSteps": 256},
                          [(100, 400, "jit_join.hashjoin_probe(3)"),
                           (450, 500, "jit_join.hashjoin_count(8)")])
    for metric in NEW_METRICS:
        assert read(metric, ev) is None, metric
    assert read("join_walk_steps_per_query", ev) == 128.0
    assert read("join_device_ms", ev) == pytest.approx(350 / 1e6 / 2)


@pytest.mark.parametrize("seed", [2**31 + 21, 3_700_000_043])
def test_the_cell_rehearses_off_the_chip(seed):
    import jax
    cell = cells.load_cell(CELL)
    cell.rows = lambda lineitem_rows=0: dict(SMALL)   # on the instance only
    with tempfile.TemporaryDirectory(prefix="chipbench_") as scratch:
        rehearsal = run.Run(cell, seed, 0, scratch)
        assert rehearsal.rows == SMALL and rehearsal.warm_ok
        assert rehearsal.expected   # some supplier of SAUDI ARABIA waited
        plan = rehearsal.session.plan(rehearsal.df.plan).tree_string()
        assert "Cpu" not in plan
        assert "HashJoinExec[left_semi" in plan
        assert "HashJoinExec[left_anti" in plan
        assert "TpuGlobalLimitExec[100]" in plan
        values, attempted, failed, extra = run.measured_run(rehearsal, 0.2)
        assert attempted >= 2 and failed == 0
        assert extra["window_compiles"] == 0
        assert set(values) >= {"query_s", "setup_s"}
        moved = rehearsal.counters()
        assert moved.get("numCpuFallbacks", 0) == 0
        queries = cell.traffic["warmup_queries"] + attempted
        # l1 in one batch through the semi join's residual walk and mask,
        # and the semi join's output through the anti join's
        assert moved["joinResidualBatches"] == 2 * queries
        assert moved["joinAntiBatches"] == queries
        if seed != 2**31 + 21:
            return
        device = {}
        values, attempted, failed, extra = run.traced_run(
            rehearsal, 0.2, jax.devices(), os.path.join(scratch, "trace"),
            device)
        assert attempted >= 1 and failed == 0
        assert values["join_residual_batches_per_query"] == 2.0
        assert values["join_anti_batches_per_query"] == 1.0
        assert values["join_semi_batches_per_query"] == 2.0
        assert not [m for m in READ_OFF_THE_CHIP if m not in values]
        # no device plane off the chip: nothing to read
        for metric in ("join_residual_device_ms",
                       "join_residual_roofline_share"):
            assert metric not in values
