"""The per-layer metric `agg_fused_folds_per_query`, from the real
`BENCHMARK.json`: the counter `aggFusedFolds` per query, listed in exactly the
cells whose grouped streaming loop folds, read from made-up evidence."""
import json
import os

import cells
from test_q1_sf10_cells import made_up_evidence, names
from test_readers import read

METRIC = "agg_fused_folds_per_query"
FOLDING_CELLS = ["tpch_q1_sf10_resident", "tpcds_q36_rollup_sf10",
                 "tpch_q3_join_resident"]


def test_the_fused_fold_count_is_read_in_the_three_folding_cells():
    """A program without the counter leaves the metric out."""
    with open(os.path.join(os.path.dirname(cells.BENCH_DIR),
                           "BENCHMARK.json")) as f:
        [entry] = [m for m in json.load(f)["per_layer"]
                   if m["name"] == METRIC]
    assert entry["workloads"] == FOLDING_CELLS
    assert (entry["layer"], entry["moves"], entry["unit"]) == (
        "Operators", "query_s", "count")
    spec = cells.load_json(cells.BENCH_DIR, "layer_metrics", METRIC)
    assert spec["reader"] == "session_metric"
    assert spec["args"] == {"name": "aggFusedFolds"}
    for name in FOLDING_CELLS + ["tpch_q1_resident", "tpch_q6_sf10_resident"]:
        listed = METRIC in names(cells.load_cell(name))
        assert listed == (name in FOLDING_CELLS), name
    ev = made_up_evidence({"aggFusedFolds": 16})
    assert read(METRIC, ev) == 8.0
    ev = made_up_evidence({"aggStreamedBatches": 116})
    assert read(METRIC, ev) is None
