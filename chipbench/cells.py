"""A cell, found by name: `BENCHMARK.json` names its configuration, traffic
mix and metrics, and each of those is a file of its own under this
directory, so a later PR adds a cell, a query, a table or a per-layer
metric by adding files and entries, without editing a file that is there.
"""
import importlib.util
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_module(bench_dir, group, name):
    """`<bench_dir>/<group>/<name>.py`, imported under a name of its own."""
    path = os.path.join(bench_dir, group, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {group} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{group}_{name}".replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(bench_dir, group, name):
    with open(os.path.join(bench_dir, group, name + ".json")) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict       # configs/<config>.json
    traffic: dict      # traffic/<traffic>.json
    query: object      # queries/<traffic.query>.py
    end_to_end: list   # BENCHMARK.json entries this cell reports
    per_layer: list    # (BENCHMARK.json entry, layer_metrics/<name>.json)
    bench_dir: str

    def rows(self, lineitem_rows=0):
        """Table -> rows.  `lineitem_rows` (the CPU rehearsal's `--rows`)
        scales every table by the same factor."""
        full = {t: spec["rows"] for t, spec in self.config["tables"].items()}
        if not lineitem_rows:
            return full
        return {t: max(1, n * lineitem_rows // full["lineitem"])
                for t, n in full.items()}


def _reported(metrics, workload):
    return [m for m in metrics
            if "workloads" not in m or workload in m["workloads"]]


def load_cell(workload, bench_dir=BENCH_DIR):
    """The cell `workload` of the `BENCHMARK.json` beside `bench_dir`."""
    with open(os.path.join(os.path.dirname(bench_dir),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {workload!r}; it "
                       f"has {[w['name'] for w in bench['workloads']]}")
    config = load_json(bench_dir, "configs", entry["config"])
    traffic = load_json(bench_dir, "traffic", entry["traffic"])
    if config["chips"] != entry["chips"]:
        raise ValueError(f"{workload}: BENCHMARK.json asks for "
                         f"{entry['chips']} chips, configuration "
                         f"{entry['config']} is laid out on "
                         f"{config['chips']}")
    per_layer = [(m, load_json(bench_dir, "layer_metrics", m["name"]))
                 for m in _reported(bench["per_layer"], workload)]
    return Cell(name=workload, chips=entry["chips"], config=config,
                traffic=traffic,
                query=load_module(bench_dir, "queries", traffic["query"]),
                end_to_end=_reported(bench["end_to_end"], workload),
                per_layer=per_layer, bench_dir=bench_dir)


def make_tables(cell, seed, rows):
    """The cell's tables as pyarrow, with only the columns its query
    reads, drawn from `seed` by `tables/<table>.py`."""
    import pyarrow as pa
    out = {}
    for table, columns in cell.query.TABLES.items():
        drawn = load_module(cell.bench_dir, "tables", table).generate(
            rows[table], seed, rows)
        out[table] = pa.table({c: drawn[c] for c in columns})
    return out
