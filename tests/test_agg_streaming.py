"""The aggregate's STREAMING loop (`exec/aggregate.py _execute_device`: per
batch a live-row read, a shrink, an update, and a fold every
`agg.mergeFanIn` batches) against the benchmark's plain references.

`chipbench`'s cell `tpch_q6_sf10_resident` takes this loop because 1.92 GB
of input is past half of `spark.rapids.sql.batchSizeBytes`; here the budget
is set low IN THE TEST so a few hundred thousand rows take it on the CPU.
Tables, queries and references are the benchmark's own files
(`chipbench/tables/lineitem.py`, `queries/q6.py`, `queries/q1.py`,
`compare.py`: integers and strings exact, doubles within 1e-10), and the
session's conf is the configuration file's.  `aggStreamedBatches` tells the
loop from the whole-stage program: a test that meant the loop fails if the
whole-stage program quietly answered.
"""
import importlib.util
import json
import os

import pyarrow as pa
import pytest

from spark_rapids_tpu.engine import TpuSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")

BATCH = 65_536
#: five batches of capacity 65,536, the last holding 37,856 rows
EQUAL_CAPS = 4 * BATCH + 37_856
#: four of 65,536 and a last one of capacity 32,768 (20,000 rows): a second
#: batch shape in one query, as the 60M-row table's 58th batch is
RAGGED_CAP = 4 * BATCH + 20_000
#: Q6 reads four 8-byte columns: data, a validity byte each, a selection byte
Q6_BATCH_BYTES = BATCH * (4 * (8 + 1) + 1)


def _bench_module(group, name):
    path = os.path.join(BENCH, group, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"streaming_test_{group}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


COMPARE = _bench_module("", "compare")
LINEITEM = _bench_module("tables", "lineitem")
QUERIES = {q: _bench_module("queries", q) for q in ("q6", "q1")}

with open(os.path.join(BENCH, "configs", "tpch-sf10-1chip.json")) as _f:
    CELL_CONF = json.load(_f)["conf"]


def _table(query, rows, seed):
    drawn = LINEITEM.generate(rows, seed, {"lineitem": rows,
                                           "orders": rows // 4})
    return pa.table({c: drawn[c] for c in query.TABLES["lineitem"]})


def _run(query, table, **conf):
    """(rows, counters moved by the SECOND of two queries, by the first):
    the second is the scan-cache hit the cell measures."""
    s = TpuSession({**CELL_CONF,
                    "spark.rapids.sql.reader.batchSizeRows": str(BATCH),
                    **conf})
    df = query.build(s, {"lineitem": s.from_arrow(table)})
    moved = []
    for _ in range(2):
        before = dict(s.query_metrics_total)
        rows = df.collect()
        moved.append({k: v - before.get(k, 0)
                      for k, v in s.query_metrics_total.items()})
    assert s.query_metrics_total.get("numCpuFallbacks", 0) == 0
    return rows, moved[1], moved[0]


def _assert_matches(rows, query, table):
    ok, worst = COMPARE.rows_match(rows, query.reference({"lineitem": table}))
    assert ok, (worst, rows)


@pytest.mark.parametrize("rows,streams_by_default", [
    (EQUAL_CAPS, False), (RAGGED_CAP, True)],
    ids=["equal_caps", "ragged_cap"])
@pytest.mark.parametrize("name", ["q6", "q1"])
def test_streaming_loop_against_reference(name, rows, streams_by_default):
    query = QUERIES[name]
    table = _table(query, rows, seed=2_700_000_011 % 2**32)
    # half of 12 MB holds two of Q6's batches and one of Q1's: the probe
    # bails on bytes with batches in hand and the scan not yet drained
    looped, moved, first = _run(query, table, **{
        "spark.rapids.sql.batchSizeBytes": "12m"})
    _assert_matches(looped, query, table)
    assert moved["aggStreamedBatches"] == 5
    assert moved.get("numFusedStages", 0) == first.get("numFusedStages", 0)
    # the counts repeat exactly, scan or scan-cache hit
    for counter in ("aggStreamedBatches", "aggHostSyncs", "aggDenseBatches"):
        assert moved.get(counter, 0) == first.get(counter, 0), counter
    # every batch's live rows are read once (capacity >= 8192), every fold
    # reads a count per part: 5 pending parts at the end of the input
    syncs = 5 + 5 + (5 if name == "q1" else 0)   # q1: the bucket check
    assert moved["aggHostSyncs"] == syncs

    default, moved, _ = _run(query, table)
    _assert_matches(default, query, table)
    ok, worst = COMPARE.rows_match(looped, default, rtol=1e-12)
    assert ok, worst
    if streams_by_default:
        # unequal batch shapes: no whole-stage program at any budget
        assert moved["aggStreamedBatches"] == 5
    else:
        assert moved.get("aggStreamedBatches", 0) == 0
        assert moved["numFusedStages"] >= 1
        assert moved.get("aggHostSyncs", 0) == (1 if name == "q1" else 0)


@pytest.mark.parametrize("slack,streamed", [(0, 0), (-2, 5)],
                         ids=["just_under", "just_over"])
def test_whole_stage_budget_edge(slack, streamed):
    """Half of batchSizeBytes is the whole-stage budget: five batches of Q6
    fit it to the byte, and one byte less sends all five, already drained by
    the probe, through the loop."""
    query = QUERIES["q6"]
    table = _table(query, EQUAL_CAPS, seed=2_700_000_029 % 2**32)
    rows, moved, _ = _run(query, table, **{
        "spark.rapids.sql.batchSizeBytes": str(2 * 5 * Q6_BATCH_BYTES
                                               + slack)})
    _assert_matches(rows, query, table)
    assert moved.get("aggStreamedBatches", 0) == streamed
    assert moved.get("numFusedStages", 0) == (0 if streamed else 1)


def test_a_dropped_or_doubled_batch_is_caught():
    """The comparison this file rests on sees one batch of five missing or
    counted twice (the reference over four or six batches' rows)."""
    query = QUERIES["q6"]
    table = _table(query, EQUAL_CAPS, seed=7)
    want = query.reference({"lineitem": table})
    dropped = query.reference({"lineitem": table.slice(BATCH)})
    doubled = query.reference({"lineitem": pa.concat_tables(
        [table, table.slice(0, BATCH)])})
    assert not COMPARE.rows_match(dropped, want)[0]
    assert not COMPARE.rows_match(doubled, want)[0]
