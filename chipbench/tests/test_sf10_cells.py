"""PR 27's two cells, from the real `BENCHMARK.json`: `tpch_q3_join_resident`
(the mesh cell's files on one chip) and `tpch_q6_sf10_resident`
(configuration `tpch-sf10-1chip`: LINEITEM at SF10, Q6 through the
aggregate's streaming loop), the rehearsal of the second off the chip, and
its three per-layer metrics read from made-up evidence."""
import json
import os

import pytest

import cells
import run
import xplane
from test_readers import read
from test_run import run_py

SF10 = "tpch_q6_sf10_resident"
Q3 = "tpch_q3_join_resident"


def names(cell):
    return [m["name"] for m, _ in cell.per_layer]


def test_the_one_chip_join_cell_is_the_mesh_cells_files_on_one_chip():
    cell, mesh = cells.load_cell(Q3), cells.load_cell("tpch_q3_join_mesh4")
    assert cell.chips == 1 and cell.config["name"] == "tpch-sf1-1chip"
    assert cell.traffic == mesh.traffic
    assert cell.traffic["query"] == "q3_shape"
    assert cell.traffic["rows_in"] == 7_500_000
    assert {"join_device_ms", "agg_device_ms"} <= set(names(cell))
    # what exists only across chips stays with the mesh cell
    assert not {"collective_share", "ici_mb_per_query",
                "d2h_mb_per_query"} & set(names(cell))
    assert [m["name"] for m in cell.end_to_end] == ["query_s", "setup_s"]


def test_the_sf10_cell_and_what_it_reports():
    cell = cells.load_cell(SF10)
    assert cell.chips == 1 and cell.config["name"] == "tpch-sf10-1chip"
    assert cell.rows()["lineitem"] == 60_000_000
    assert cell.query.bytes_needed(cell.rows()) == 1_920_000_000
    assert cell.traffic == {**cell.traffic, "query": "q6",
                            "residency": "device", "warmup_queries": 3,
                            "min_queries": 5, "trace_seconds": 3,
                            "trace_min_queries": 2, "rows_in": 60_000_000}
    assert {"agg_streamed_batches_per_query", "agg_host_syncs_per_query",
            "agg_shrink_ms", "agg_device_ms", "hbm_roofline_share",
            "hbm_peak_gb", "device_idle_share"} <= set(names(cell))
    assert "join_device_ms" not in names(cell)
    # the same query and table files as the SF1 cell, letter for letter
    small = cells.load_cell("tpch_q6_resident")
    assert cell.query.__file__ == small.query.__file__
    assert not {"agg_streamed_batches_per_query", "agg_host_syncs_per_query",
                "agg_shrink_ms"} & set(names(small))


def test_the_sf10_configuration_states_what_it_must():
    with open(os.path.join(cells.BENCH_DIR, "configs",
                           "tpch-sf10-1chip.json")) as f:
        config = json.load(f)
    with open(os.path.join(cells.BENCH_DIR, "configs",
                           "tpch-sf1-1chip.json")) as f:
        sf1 = json.load(f)
    assert config["conf"] == sf1["conf"]          # nothing steers the path
    assert config["guarantees"] == sf1["guarantees"]
    assert config["scale_factor"] == 10 and config["chips"] == 1
    assert (config["tables"]["lineitem"]["columns"]
            == sf1["tables"]["lineitem"]["columns"])
    assert list(config["reduced"]) == ["lineitem_columns"]
    assert (config["reduced"]["lineitem_columns"]
            == sf1["reduced"]["lineitem_columns"])
    assert any("59,986,052" in a for a in config["assumed"])
    assert any("ORDERS" in a for a in config["assumed"])
    assert "1.92 GB" in config["device_bytes"]["q6"]
    # the source names SF10 and differs from the SF1 file's
    assert "SF10" in config["source"] and config["source"] != sf1["source"]


def test_the_sf10_cell_rehearses_off_the_chip():
    for trace, reports in ((0, "query_s"), (1, "window_compiles")):
        p = run_py("--workload", SF10, "--seconds", "0.5", "--trace",
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


def made_up_evidence(counters, shrinks=True):
    """Two queries of 1000 ns, each with two batches of the loop."""
    thread = [(0, 1000, xplane.QUERY_SPAN), (1000, 2000, xplane.QUERY_SPAN),
              (100, 900, "srt:execute"), (1100, 1900, "srt:execute"),
              (110, 110, "srt:agg_whole_stage_bail"),
              (300, 360, "srt:agg_update"), (500, 560, "srt:agg_update")]
    if shrinks:
        thread += [(200, 300, "srt:agg_shrink"), (400, 500, "srt:agg_shrink"),
                   (1200, 1240, "srt:agg_shrink"),
                   (1400, 1440, "srt:agg_shrink"),
                   (2100, 2900, "srt:agg_shrink")]   # after the window
    trace = xplane.Trace(
        devices=[xplane.Device(0, ops=[(300, 350, "fusion.1")],
                               launches=[(300, 350, "jit_agg.update(7)")])],
        threads=[sorted(thread)], t0=0, t1=2000, queries=2)
    cell = cells.load_cell(SF10)
    return run.Evidence(cell=cell, rows=cell.rows(), queries=2,
                        counters=counters, compiles=0, spans={}, memory=[],
                        trace=trace, peaks={})


def test_the_three_new_metrics_read_the_programs_counters_and_span():
    ev = made_up_evidence({"aggStreamedBatches": 116, "aggHostSyncs": 246})
    assert read("agg_streamed_batches_per_query", ev) == 58.0
    assert read("agg_host_syncs_per_query", ev) == 123.0
    assert read("agg_shrink_ms", ev) == pytest.approx(
        (100 + 100 + 40 + 40) / 4 / 1e6)
    assert read("agg_device_ms", ev) == pytest.approx(50 / 1e6 / 2)


def test_a_program_without_the_counters_or_the_span_leaves_them_out():
    # the parent: no such counter moves, no such span is opened
    ev = made_up_evidence({"numOutputRows": 10.0}, shrinks=False)
    for metric in ("agg_streamed_batches_per_query",
                   "agg_host_syncs_per_query", "agg_shrink_ms"):
        assert read(metric, ev) is None
