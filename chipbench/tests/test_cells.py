"""`BENCHMARK.json` and the data files it names hang together, and a later
PR adds a cell, a configuration and a per-layer metric by adding files and
entries only."""
import json
import os
import shutil

import pytest

import cells

ROOT = os.path.dirname(cells.BENCH_DIR)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


@pytest.mark.parametrize("workload", sorted(
    {w["name"] for w in BENCH["workloads"]} | {"tpch_q3_join_mesh4"}))
def test_every_cell_resolves(workload, pending_bench_dir):
    cell = cells.load_cell(workload, bench_dir=pending_bench_dir)
    assert cell.config["chips"] == cell.chips
    assert {m["name"] for m in cell.end_to_end} >= {"query_s", "setup_s"}
    assert cell.per_layer
    for metric, spec in cell.per_layer:
        # a metric's file says the same as BENCHMARK.json, and its reader
        # is there
        for key in ("name", "layer", "unit", "moves"):
            assert spec[key] == metric[key], (metric["name"], key)
        assert hasattr(cells.load_module(cells.BENCH_DIR, "readers",
                                         spec["reader"]), "read")
    for table, columns in cell.query.TABLES.items():
        assert set(columns) <= set(cell.config["tables"][table]["columns"])
    assert cell.traffic["rows_in"] == sum(
        cell.config["tables"][t]["rows"] for t in cell.query.TABLES)
    assert cell.query.bytes_needed(cell.rows()) > 0


def test_configurations_state_what_the_contract_wants():
    for entry in BENCH["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config["source"] == entry["source"]
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
        assert config["guarantees"] and config["assumed"]


def test_a_fifth_cell_is_new_files_and_entries_only(tmp_path):
    """A new configuration, traffic mix, query, cell and per-layer metric of
    an existing reader kind, with no edit to a file that is there."""
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(cells.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    config = json.loads((bench_dir / "configs/tpch-sf1-1chip.json")
                        .read_text())
    config.update(name="tpch-sf2-1chip", scale_factor=2)
    config["tables"]["lineitem"]["rows"] = 12_000_000
    config["tables"]["orders"]["rows"] = 3_000_000
    (bench_dir / "configs/tpch-sf2-1chip.json").write_text(
        json.dumps(config))
    traffic = json.loads((bench_dir / "traffic/q6_resident.json")
                         .read_text())
    traffic.update(query="q6_count", rows_in=12_000_000)
    (bench_dir / "traffic/q6_count_resident.json").write_text(
        json.dumps(traffic))
    (bench_dir / "queries/q6_count.py").write_text(
        "TABLES = {'lineitem': ['l_quantity']}\n"
        "def build(session, frames):\n"
        "    from spark_rapids_tpu.plan.logical import col, functions as F\n"
        "    return frames['lineitem'].agg(\n"
        "        F.sum(col('l_quantity')).alias('q'))\n"
        "def reference(tables):\n"
        "    import pyarrow.compute as pc\n"
        "    return [(pc.sum(tables['lineitem']['l_quantity']).as_py(),)]\n"
        "def bytes_needed(rows):\n"
        "    return rows['lineitem'] * 8\n")
    (bench_dir / "layer_metrics/d2h_kb_per_query.json").write_text(
        json.dumps({"name": "d2h_kb_per_query", "layer": "Entry",
                    "unit": "kB", "moves": "query_s",
                    "reader": "session_metric",
                    "args": {"name": "d2hBytes", "scale": 1e-3}}))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append(
        {"name": "tpch_q6_count_sf2", "config": "tpch-sf2-1chip",
         "traffic": "q6_count_resident", "chips": 1, "why": "a test's"})
    bench["per_layer"].append(
        {"name": "d2h_kb_per_query", "unit": "kB", "better": "lower",
         "source": "program_counter", "layer": "Entry", "moves": "query_s",
         "workloads": ["tpch_q6_count_sf2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load_cell("tpch_q6_count_sf2", bench_dir=str(bench_dir))
    assert cell.rows() == {"lineitem": 12_000_000, "orders": 3_000_000}
    assert "d2h_kb_per_query" in [m["name"] for m, _ in cell.per_layer]
    assert "h2d_mb_per_query" not in [m["name"] for m, _ in cell.per_layer]
    # the new cell runs: its tables, its reference, its query, its reader
    from spark_rapids_tpu.engine import TpuSession
    tables = cells.make_tables(cell, 1, cell.rows(20_000))
    session = TpuSession(cell.config["conf"])
    got = cell.query.build(session, {
        t: session.from_arrow(tb) for t, tb in tables.items()}).collect()
    assert got == cell.query.reference(tables)
    import run
    ev = run.Evidence(cell=cell, rows=cell.rows(20_000), queries=2,
                      counters={"d2hBytes": 160.0}, compiles=0, spans={},
                      memory=[], trace=None, peaks={})
    [(_, spec)] = [(m, s) for m, s in cell.per_layer
                   if m["name"] == "d2h_kb_per_query"]
    reader = cells.load_module(str(bench_dir), "readers", spec["reader"])
    assert reader.read(ev, **spec["args"]) == pytest.approx(0.08)
    # an old cell is what it was, and no file that was there has changed
    assert cells.load_cell("tpch_q6_resident", bench_dir=str(bench_dir)
                           ).rows()["lineitem"] == 6_000_000
    assert all(p.read_bytes() == b for p, b in before.items())


def test_benchmark_json_keeps_to_the_contracts_shape():
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cells_ = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/") and name.match(c["name"])
        assert all(name.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # the metric it moves is reported wherever it is
        moved = next(e for e in BENCH["end_to_end"]
                     if e["name"] == m["moves"])
        assert set(m.get("workloads", cells_)) <= set(
            moved.get("workloads", cells_))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells_
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
