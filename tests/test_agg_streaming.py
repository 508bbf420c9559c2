"""The aggregate's STREAMING loop (`exec/aggregate.py _execute_device`)
against the benchmark's plain references.  Grouped (Q1): per batch a
live-row read, a shrink, an update, and a fold every `agg.mergeFanIn`
batches.  No grouping keys (Q6; `_stream_keyless`): ONE step program a
batch, `carry' = merge(carry, update(pre(batch)))`, and no host read, no
shrink and no concat; the second half of this file holds that loop to the
shape of its work and to every function `_global_kernel` has.

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

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import Column, ColumnarBatch
from spark_rapids_tpu.columnar import batch as batch_module
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.exec import aggregate as aggregate_module
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.base import ExecContext, ExecNode
from spark_rapids_tpu.metrics.registry import DEVICE_SYNCS
from spark_rapids_tpu.ops import expressions as E
from spark_rapids_tpu.ops.aggregates import AggregateExpression
from spark_rapids_tpu.plan.logical import col, functions as F, lit
from spark_rapids_tpu.utils import faults, kernel_cache

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
    for counter in ("aggStreamedBatches", "aggHostSyncs", "aggDenseBatches",
                    "aggBucketBatches", "aggSortPathBatches"):
        assert moved.get(counter, 0) == first.get(counter, 0), counter
    if name == "q1":
        # the bucket update takes every batch; the sort path's counter is
        # there and says 0
        assert moved["aggBucketBatches"] == 5
        assert "aggSortPathBatches" in moved
        assert moved["aggSortPathBatches"] == 0
        # grouped: every batch's live rows are read once (capacity >=
        # 8192), the bucket check once, and the one fold (of the 5 pending
        # parts at the end of the input) reads their counts once
        assert moved["aggHostSyncs"] == 5 + 5 + 1
        assert moved["aggFusedFolds"] == 1
        assert moved.get("aggSyncFreeBatches", 0) == 0
    else:
        # keyless: the step program a batch reads nothing back; the
        # counter is there and says so
        assert "aggHostSyncs" in moved and moved["aggHostSyncs"] == 0
        assert moved["aggSyncFreeBatches"] == 5

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


#: batches of 8,192 rows (each read for its live rows before the shrink):
#: 18 of them and a last one of capacity 4,096, past 2 x agg.mergeFanIn (8)
FOLD_BATCH = 8_192
FOLD_ROWS = 18 * FOLD_BATCH + 3_000


def test_grouped_loop_folds_mid_stream_and_on_the_tail(tmp_path):
    """Q1 through the grouped loop over 19 batches of two capacities (the
    whole-stage probe bails on shapes): a fold of 8 parts after the 8th
    batch, of 9 (the running state and 8) after the 16th, of 4 on the tail,
    each inside one `srt:agg_fold` that says how many parts it merged, with
    `srt:agg_merge` in it.  Host reads, exactly: 18 live-row reads (the
    4,096-row batch takes no shrink), 19 bucket checks, one read a fold.
    A fold launches two programs, the parts' counts and `agg.fold`, and no
    eager op: no compaction, no per-part slice or update."""
    import glob
    import jax
    query = QUERIES["q1"]
    table = _table(query, FOLD_ROWS, seed=3_900_000_017 % 2**32)
    s = TpuSession({**CELL_CONF, "spark.rapids.sql.reader.batchSizeRows":
                    str(FOLD_BATCH)})
    df = query.build(s, {"lineitem": s.from_arrow(table)})
    df.collect()                      # the scan-cache load
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    before = dict(s.query_metrics_total)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        rows = df.collect()
    finally:
        jax.profiler.stop_trace()
    moved = {k: v - before.get(k, 0) for k, v in s.query_metrics_total.items()}
    _assert_matches(rows, query, table)
    assert moved["scanCacheHitBatches"] == 19
    assert moved["aggStreamedBatches"] == moved["aggBucketBatches"] == 19
    assert moved["aggSortPathBatches"] == 0
    assert moved["aggHostSyncs"] == 18 + 19 + 3
    assert moved["aggFusedFolds"] == 3
    [pb] = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))
    [host] = [p for p in jax.profiler.ProfileData.from_file(pb).planes
              if p.name == "/host:CPU"]
    events = sorted([(e.start_ns, e.start_ns + e.duration_ns, e)
                     for line in host.lines for e in line.events],
                    key=lambda t: t[0])

    def spans(name):
        return [t for t in events if t[2].name == name]
    folds, merges = spans("srt:agg_fold"), spans("srt:agg_merge")
    assert [int(dict(f[2].stats)["parts"]) for f in folds] == [8, 9, 4]
    assert len(merges) == 3
    assert all(f[0] <= m[0] and m[1] <= f[1] for f, m in zip(folds, merges))
    for f in folds:
        calls = [t[2].name for t in events if f[0] <= t[0] and t[1] <= f[1]
                 and t[2].name.startswith("PjitFunction")]
        # (a call can show on more than one line of the host plane)
        assert set(calls) == {"PjitFunction(agg.part_rows)",
                              "PjitFunction(agg.fold)"}, calls
        assert calls[0] == "PjitFunction(agg.part_rows)", calls
        assert calls[-1] == "PjitFunction(agg.fold)", calls


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


# ---------------------------------------------------------------------------
# the keyless loop (`_stream_keyless`): every function `_global_kernel` has
# ---------------------------------------------------------------------------

SMALL = 4_096
#: batches of capacity 4,096: five equal ones, or four and one of 1,024
KEYLESS_ROWS = {"equal_caps": 5 * SMALL - 300, "ragged_cap": 4 * SMALL + 1_000}
#: half of it holds one batch of the table below: the probe bails on bytes
LOOP_BUDGET = {"spark.rapids.sql.batchSizeBytes": "256k"}


def _mixed_table(rows, seed=28):
    """Doubles, longs and short strings with nulls, and a filter key."""
    rng = np.random.default_rng(seed)
    return pa.table({
        "x": pa.array(rng.normal(size=rows) * 1e3, mask=rng.random(rows) < .2),
        "i": pa.array(rng.integers(-1000, 1000, rows),
                      mask=rng.random(rows) < .1),
        "k": pa.array(rng.integers(0, 10, rows)),
        "void": pa.array(np.zeros(rows), mask=np.ones(rows, bool)),
    })


def _kept(table, name):
    """The column's values, nulls too, in the rows the filter keeps."""
    return [v for v, k in zip(table[name].to_pylist(), table["k"].to_pylist())
            if k < 7]


def _live(table, name):
    return [v for v in _kept(table, name) if v is not None]


#: name -> (aggregate, plain reference over the table)
KEYLESS_FUNCTIONS = {
    "sum_double": (lambda: F.sum(col("x")),
                   lambda t: float(np.sum(_live(t, "x")))),
    "sum_long": (lambda: F.sum(col("i")), lambda t: sum(_live(t, "i"))),
    "count_column": (lambda: F.count(col("x")), lambda t: len(_live(t, "x"))),
    "count_star": (lambda: F.count(lit(1)), lambda t: len(_live(t, "k"))),
    "average": (lambda: F.avg(col("x")),
                lambda t: float(np.mean(_live(t, "x")))),
    "min_long": (lambda: F.min(col("i")), lambda t: min(_live(t, "i"))),
    "max_double": (lambda: F.max(col("x")), lambda t: max(_live(t, "x"))),
    "count_distinct": (lambda: F.count_distinct(col("i")),
                       lambda t: len(set(_live(t, "i")))),
    # First/Last: the row offset rides the carry on the device
    "first": (lambda: F.first(col("i")), lambda t: _kept(t, "i")[0]),
    "last": (lambda: F.last(col("x")), lambda t: _kept(t, "x")[-1]),
    # nulls only: a sum and a minimum of nothing are null, a count is 0
    "sum_of_nulls": (lambda: F.sum(col("void")), lambda t: None),
    "min_of_nulls": (lambda: F.min(col("void")), lambda t: None),
    "count_of_nulls": (lambda: F.count(col("void")), lambda t: 0),
}


def _keyless_session(**conf):
    return TpuSession({**CELL_CONF,
                       "spark.rapids.sql.reader.batchSizeRows": str(SMALL),
                       **conf})


def _collect_counted(df):
    s = df.session
    before = dict(s.query_metrics_total)
    rows = df.collect()
    return rows, {k: v - before.get(k, 0)
                  for k, v in s.query_metrics_total.items()}


@pytest.mark.parametrize("shape", sorted(KEYLESS_ROWS))
@pytest.mark.parametrize("name", sorted(KEYLESS_FUNCTIONS))
def test_keyless_function_through_the_loop(name, shape):
    """Each function through the step program against the plain reference
    and against what the default budget answers with (the whole-stage
    program where the capacities are equal and no row offset is needed)."""
    aggregate, reference = KEYLESS_FUNCTIONS[name]
    table = _mixed_table(KEYLESS_ROWS[shape])
    answers = []
    for conf in (LOOP_BUDGET, {}):
        s = _keyless_session(**conf)
        df = s.from_arrow(table).filter(col("k") < 7).agg(
            aggregate().alias("a"), F.count(lit(1)).alias("n"))
        rows, moved = _collect_counted(df)
        answers.append(rows)
        if conf:
            # count_distinct's child coalesces to ONE batch (its partial
            # states are not mergeable): one step
            assert moved["aggStreamedBatches"] == \
                moved["aggSyncFreeBatches"] == (
                    1 if name == "count_distinct" else 5)
            assert moved["aggHostSyncs"] == 0
        assert s.query_metrics_total.get("numCpuFallbacks", 0) == 0
    want = [(reference(table), len(_live(table, "k")))]
    assert COMPARE.rows_match(answers[0], want)[0], (answers[0], want)
    assert COMPARE.rows_match(answers[0], answers[1], rtol=1e-12)[0], answers


def test_float_sum_order_drift_between_loop_and_whole_stage_is_named():
    """The cross-path drift, by name: the loop merges the per-batch
    partial sums pairwise into the running state, batch after batch; the
    whole-stage program (and the 8-way fold this loop replaced) reduces
    the same partials in one segmented pass.  Same addends per batch,
    another order across batches: counts are exact, a double sum agrees to
    1e-12 (`variableFloatAgg`, on in the cell's conf; 1e-14 measured on
    the chip over 58 batches, tolerance of `correct` 1e-10)."""
    table = _mixed_table(KEYLESS_ROWS["equal_caps"], seed=29)
    got = []
    for conf in (LOOP_BUDGET, {}):
        df = _keyless_session(**conf).from_arrow(table).agg(
            F.sum(col("x") * col("x")).alias("sxx"),
            F.count(col("x")).alias("n"))
        rows, moved = _collect_counted(df)
        assert moved.get("aggStreamedBatches", 0) == (5 if conf else 0)
        got.append(rows[0])
    assert got[0][1] == got[1][1]
    np.testing.assert_allclose(got[0][0], got[1][0], rtol=1e-12, atol=0)


@pytest.mark.parametrize("case", ["all_filtered", "empty_input"])
def test_keyless_loop_over_nothing_still_answers_one_row(case):
    """No live row in any batch, and no batch at all: one row either way,
    a null sum and a zero count (today's all-dead-batch answer)."""
    table = _mixed_table(KEYLESS_ROWS["ragged_cap"])
    if case == "empty_input":
        table = table.slice(0, 0)
    s = _keyless_session(**LOOP_BUDGET)
    df = s.from_arrow(table).filter(col("k") < -1).agg(
        F.sum(col("x")).alias("sx"), F.count(lit(1)).alias("n"),
        F.max(col("i")).alias("mi"))
    rows, moved = _collect_counted(df)
    assert rows == [(None, 0, None)]
    assert moved.get("aggHostSyncs", 0) == 0
    if case == "all_filtered":
        assert moved["aggSyncFreeBatches"] == 5


def test_keyless_loop_takes_the_output_of_a_child_it_cannot_absorb():
    """`monotonically_increasing_id()` threads a row offset through the
    projection, which one absorbing program cannot vary per batch: the
    projection runs as its own program and the step takes its output.
    The ids of 5 batches are 0..n-1, so their sum says no batch's offset
    restarted and none was merged twice."""
    n = KEYLESS_ROWS["ragged_cap"]
    s = _keyless_session(**LOOP_BUDGET)
    df = (s.from_arrow(_mixed_table(n))
          .with_column("id", F.monotonically_increasing_id())
          .agg(F.sum(col("id")).alias("s"), F.max(col("id")).alias("m"),
               F.count(col("i")).alias("c")))
    rows, moved = _collect_counted(df)
    assert rows[0][:2] == (n * (n - 1) // 2, n - 1)
    assert moved["aggSyncFreeBatches"] == 5 and moved["aggHostSyncs"] == 0


class _Batches(ExecNode):
    """A source of prepared batches: not row-local, so nothing to absorb."""

    def __init__(self, batches):
        super().__init__()
        self.batches = batches

    @property
    def schema(self):
        return self.batches[0].schema

    def execute(self, ctx):
        yield from self.batches


def _string_batches(widths=(8, 16, 8), cap=1_024, seed=30):
    """One string column a batch, each batch as wide as its longest value
    (a string state is as wide as the batch it was taken from)."""
    rng = np.random.default_rng(seed)
    schema = T.Schema([T.StructField("s", T.StringType)])
    values, batches = [], []
    for width in widths:
        strs = ["".join(rng.choice(list("abcxyz"), rng.integers(1, width + 1)))
                for _ in range(cap - 100)]
        strs[::7] = [None] * len(strs[::7])
        values += strs
        c = Column.from_strings(strs, capacity=cap)
        assert c.max_len == width
        batches.append(ColumnarBatch([c], np.arange(cap) < len(strs), schema))
    return batches, [v for v in values if v is not None]


@pytest.mark.parametrize("whole_stage", [False, True],
                         ids=["loop", "whole_stage_refuses_shapes"])
def test_keyless_string_min_max_over_batches_of_unequal_width(whole_stage):
    """Min/Max over strings (the planner keeps them on the CPU today; the
    kernel has them): the running state widens to the widest batch seen."""
    batches, live = _string_batches()
    s = E.BoundReference(0, T.StringType, "s")
    node = TpuHashAggregateExec([], [], [
        AggregateExpression("Min", s, output_name="lo"),
        AggregateExpression("Max", s, output_name="hi"),
        AggregateExpression("Count", s, output_name="n")], _Batches(batches))
    ctx = ExecContext(TpuConf({
        "spark.rapids.sql.tpu.wholeStage.enabled": str(whole_stage).lower()}))
    out, = list(node.execute(ctx))
    assert out.to_pylist() == [(min(live), max(live), len(live))]
    assert node.metrics.snapshot()["aggSyncFreeBatches"] == 3


# ---------------------------------------------------------------------------
# the shape of the keyless loop's work, and its retries
# ---------------------------------------------------------------------------

def _step_programs():
    return [fn for key, fn in kernel_cache._CACHE.items()
            if key[0] == "stream_step"]


@pytest.mark.parametrize("shape", sorted(KEYLESS_ROWS))
def test_keyless_loop_is_one_step_dispatch_a_batch_and_nothing_else(
        shape, monkeypatch):
    """Not its time, its shape: over N batches at most two step programs
    (one a batch capacity), N dispatches, no host read, and the grouped
    loop's compaction never called."""
    def refuse(*a, **k):
        raise AssertionError("the keyless loop compacts nothing")
    query = QUERIES["q6"]
    table = _table(query, KEYLESS_ROWS[shape], seed=28)
    s = _keyless_session(**LOOP_BUDGET)
    df = query.build(s, {"lineitem": s.from_arrow(table)})
    kernel_cache.clear()
    df.collect()                    # scan, compile
    streaming = TpuHashAggregateExec._stream_keyless

    def guarded(self, ctx, materialized):
        with monkeypatch.context() as mp:
            mp.setattr(aggregate_module, "_concat_prefixes", refuse)
            mp.setattr(batch_module, "concat_batches", refuse)
            for method in ("shrink_to", "maybe_shrink", "compact",
                           "num_rows_host"):
                mp.setattr(ColumnarBatch, method, refuse)
            return streaming(self, ctx, materialized)
    monkeypatch.setattr(TpuHashAggregateExec, "_stream_keyless", guarded)
    before, syncs = kernel_cache.stats(), DEVICE_SYNCS.count
    rows, moved = _collect_counted(df)
    after = kernel_cache.stats()
    _assert_matches(rows, query, table)
    assert moved["aggStreamedBatches"] == moved["aggSyncFreeBatches"] == 5
    assert moved["aggHostSyncs"] == 0 and DEVICE_SYNCS.count == syncs
    # the filter is inside the step: the steps are the query's dispatches
    assert after["dispatches"] - before["dispatches"] == 5
    assert after["builds"] == before["builds"]
    step, = _step_programs()
    assert step._cache_size() == (1 if shape == "equal_caps" else 2)


def _q6_like(conf):
    """Q6's shape over the mixed table through the loop: a double sum, an
    exact long sum and a count, 5 batches."""
    faults.INJECTOR.reset()
    s = _keyless_session(**LOOP_BUDGET, **conf)
    df = s.from_arrow(_mixed_table(KEYLESS_ROWS["ragged_cap"])) \
        .filter(col("k") < 7).agg(F.sum(col("x")).alias("sx"),
                                  F.sum(col("i")).alias("si"),
                                  F.count(lit(1)).alias("n"))
    rows, moved = _collect_counted(df)
    return rows[0], moved


@pytest.mark.faultinject
def test_a_retried_and_a_split_batch_each_merge_once():
    """The fault injector at every reserve of the query, one at a time: a
    retried step re-runs against the SAME carry (the reservation fails
    before the step is issued), so the answer is bit for bit the
    fault-free one.  Then a window wide enough to exhaust the retries at
    the first `agg.update`: the batch is split by rows and its pieces
    merge in order, once each: integers exact, the double sum in another
    order."""
    want, moved = _q6_like({})
    assert moved["aggSyncFreeBatches"] == 5
    sites = dict(faults.INJECTOR.site_counts)
    assert sites["agg.update"] == 5
    retried_updates = 0
    for ordinal in range(1, faults.INJECTOR.oom_ops + 1):
        got, moved = _q6_like(
            {"spark.rapids.tpu.test.injectOom": str(ordinal)})
        assert got == want, ordinal
        assert moved["aggSyncFreeBatches"] == 5
        (_, _, site), = faults.INJECTOR.injected_log
        retried_updates += site == "agg.update"
        assert moved.get("aggUpdateRetries", 0) == (site == "agg.update")
    assert retried_updates == 5
    first_update = next(
        n for n in range(1, 50) if _q6_like(
            {"spark.rapids.tpu.test.injectOom": str(n)})[1].get(
                "aggUpdateRetries"))
    got, moved = _q6_like({
        "spark.rapids.tpu.test.injectOom": f"{first_update}x2",
        "spark.rapids.memory.tpu.retry.maxRetries": "1"})
    assert moved["aggUpdateSplits"] == 1
    assert moved["aggSyncFreeBatches"] == 5     # input batches, not pieces
    assert got[1:] == want[1:]
    np.testing.assert_allclose(got[0], want[0], rtol=1e-12, atol=0)
