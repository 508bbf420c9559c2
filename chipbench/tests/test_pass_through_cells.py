"""The per-layer metric `join_pass_through_batches_per_query`, from the real
`BENCHMARK.json`: the counter `joinPassThroughBatches` per query, listed in
exactly the cells that run the one-chip hash join, read from made-up
evidence."""
import json
import os

import cells
from test_q1_sf10_cells import made_up_evidence, names
from test_readers import read

METRIC = "join_pass_through_batches_per_query"
JOIN_CELLS = ["tpcds_q36_rollup_sf10", "tpcds_q52_star_sf10",
              "tpch_q3_join_resident", "tpch_q18_resident"]


def test_the_pass_through_count_is_read_in_the_one_chip_join_cells():
    """A program without the counter leaves the metric out."""
    with open(os.path.join(os.path.dirname(cells.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        [entry] = [m for m in json.load(f)["per_layer"]
                   if m["name"] == METRIC]
    assert entry["workloads"] == JOIN_CELLS
    assert (entry["layer"], entry["moves"], entry["unit"], entry["better"],
            entry["source"]) == ("Operators", "query_s", "count", "higher",
                                 "program_counter")
    spec = cells.load_json(cells.BENCH_DIR, "layer_metrics", METRIC)
    assert spec["reader"] == "session_metric"
    assert spec["args"] == {"name": "joinPassThroughBatches"}
    for name in JOIN_CELLS + ["tpch_q3_join_mesh4", "tpch_q1_resident"]:
        listed = METRIC in names(cells.load_cell(name))
        assert listed == (name in JOIN_CELLS), name
    ev = made_up_evidence({"joinPassThroughBatches": 112})
    assert read(METRIC, ev) == 56.0
    ev = made_up_evidence({"joinOutputSpaceBatches": 56})
    assert read(METRIC, ev) is None
