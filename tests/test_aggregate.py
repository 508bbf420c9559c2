"""TPU hash aggregate vs CPU oracle."""
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.plan.logical import col, functions as f, lit

from compare import assert_tpu_and_cpu_are_equal, run_both, assert_rows_equal
from data_gen import gen_df

FLOAT_AGG = {"spark.rapids.sql.variableFloatAgg.enabled": "true"}


def _assert_on_tpu(build, conf=None):
    """The TPU side must actually plan the agg on device."""
    from spark_rapids_tpu.engine import TpuSession
    c = dict(conf or {})
    s = TpuSession(c)
    text = build(s).explain()
    assert "!HashAggregateExec" not in text, text


def test_groupby_sum_count_long():
    def q(s):
        df = gen_df(s, seed=20, n=800, k=T.IntegerType, v=T.LongType)
        return df.group_by("k").agg(f.sum(col("v")).alias("sv"),
                                    f.count(col("v")).alias("cv"),
                                    f.count(lit(1)).alias("cstar"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_groupby_min_max():
    def q(s):
        df = gen_df(s, seed=21, n=600, k=T.IntegerType, v=T.IntegerType,
                    d=T.DoubleType)
        return df.group_by("k").agg(f.min(col("v")).alias("mnv"),
                                    f.max(col("v")).alias("mxv"),
                                    f.min(col("d")).alias("mnd"),
                                    f.max(col("d")).alias("mxd"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_groupby_avg_float_conf_gated():
    def q(s):
        df = gen_df(s, seed=22, n=500, k=T.IntegerType, v=T.IntegerType)
        return df.group_by("k").agg(f.avg(col("v")).alias("av"),
                                    f.sum(col("v")).alias("sv"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_float_agg_requires_conf():
    from spark_rapids_tpu.engine import TpuSession

    def q(s):
        df = gen_df(s, seed=23, n=100, k=T.IntegerType, v=T.DoubleType)
        return df.group_by("k").agg(f.sum(col("v")).alias("sv"))
    # without the conf, falls back (explain shows reason)
    text = q(TpuSession()).explain()
    assert "variableFloatAgg" in text
    # with the conf, runs on TPU and matches
    _assert_on_tpu(q, FLOAT_AGG)
    assert_tpu_and_cpu_are_equal(q, conf=FLOAT_AGG)


def test_groupby_string_keys():
    def q(s):
        df = gen_df(s, seed=24, n=600, k=T.StringType, v=T.LongType)
        return df.group_by("k").agg(f.sum(col("v")).alias("sv"),
                                    f.count(col("v")).alias("cv"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_groupby_multi_keys_with_nulls_nans():
    def q(s):
        df = gen_df(s, seed=25, n=700, k1=T.IntegerType, k2=T.DoubleType,
                    v=T.LongType)
        return df.group_by("k1", "k2").agg(f.count(lit(1)).alias("c"),
                                           f.sum(col("v")).alias("sv"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_groupby_first_last():
    # first/last depend on row order; use a key-sorted deterministic frame
    def q(s):
        df = s.from_pydict({"k": [1, 1, 2, 2, 2, 3],
                            "v": [10, None, 30, 40, None, 60]},
                           T.schema_of(k=T.IntegerType, v=T.IntegerType))
        return df.group_by("k").agg(f.first(col("v")).alias("fv"),
                                    f.last(col("v")).alias("lv"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_global_agg():
    def q(s):
        df = gen_df(s, seed=26, n=500, v=T.LongType, d=T.DoubleType)
        return df.agg(f.sum(col("v")).alias("sv"),
                      f.count(col("v")).alias("cv"),
                      f.min(col("d")).alias("mnd"),
                      f.max(col("d")).alias("mxd"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_global_agg_empty_input():
    def q(s):
        df = s.from_pydict({"v": []}, T.schema_of(v=T.LongType))
        return df.agg(f.sum(col("v")).alias("sv"),
                      f.count(col("v")).alias("cv"))
    cpu, tpu = run_both(q)
    assert tpu == [(None, 0)]
    assert_rows_equal(cpu, tpu)


def test_groupby_empty_input():
    def q(s):
        df = s.from_pydict({"k": [], "v": []},
                           T.schema_of(k=T.IntegerType, v=T.LongType))
        return df.group_by("k").agg(f.sum(col("v")).alias("sv"))
    cpu, tpu = run_both(q)
    assert cpu == tpu == []


def test_agg_over_multiple_batches():
    # force multiple scan batches so the merge path runs
    conf = {"spark.rapids.sql.reader.batchSizeRows": "100"}

    def q(s):
        df = gen_df(s, seed=27, n=950, k=T.IntegerType, v=T.LongType)
        return df.group_by("k").agg(f.sum(col("v")).alias("sv"),
                                    f.count(lit(1)).alias("c"))
    assert_tpu_and_cpu_are_equal(q, conf=conf)


def test_agg_expression_keys_and_values():
    def q(s):
        df = gen_df(s, seed=28, n=400, a=T.IntegerType, b=T.IntegerType)
        return df.group_by((col("a") % 10).alias("bucket")) \
            .agg(f.sum(col("a") + col("b")).alias("sab"),
                 f.max(col("b") * 2).alias("mb2"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_single_distinct_agg_on_device():
    """One distinct child dedups inside the update kernel (sorted
    (group, value) adjacency; exec/aggregate.py _distinct_child)."""
    def q(s):
        df = gen_df(s, seed=29, n=300, k=T.IntegerType, v=T.IntegerType)
        return df.group_by("k").agg(
            f.count_distinct(col("v")).alias("cd"),
            f.sum(col("v")).alias("sv"),        # mixed: non-distinct too
            f.count(col("v")).alias("c"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_distinct_agg_strings_and_sum_distinct():
    def q(s):
        df = gen_df(s, seed=30, n=300, k=T.IntegerType, s_=T.StringType)
        return df.group_by("k").agg(
            f.count_distinct(col("s_")).alias("cd"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)

    def q2(s):
        df = gen_df(s, seed=31, n=300, k=T.IntegerType, v=T.LongType)
        return df.group_by("k").agg(
            f._agg("Sum", col("v"), distinct=True).alias("sd"))
    _assert_on_tpu(q2)
    assert_tpu_and_cpu_are_equal(q2)


def test_multi_distinct_agg_falls_back():
    """Two DIFFERENT distinct children cannot share one sorted dedup pass;
    falls back like the reference (GpuHashAggregateMeta.tagPlanForGpu)."""
    from spark_rapids_tpu.engine import TpuSession

    def q(s):
        df = gen_df(s, seed=32, n=300, k=T.IntegerType, v=T.IntegerType,
                    w=T.IntegerType)
        return df.group_by("k").agg(
            f.count_distinct(col("v")).alias("cv"),
            f.count_distinct(col("w")).alias("cw"))
    text = q(TpuSession()).explain()
    assert "multiple distinct" in text
    assert_tpu_and_cpu_are_equal(q)


def test_min_with_inf_and_nan_group():
    def q(s):
        df = s.from_pydict(
            {"k": [1, 1, 2, 2, 3],
             "v": [float("inf"), float("nan"), float("nan"), float("nan"),
                   1.5]},
            T.schema_of(k=T.IntegerType, v=T.DoubleType))
        return df.group_by("k").agg(f.min(col("v")).alias("mn"),
                                    f.max(col("v")).alias("mx"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_first_last_across_filtered_batches():
    conf = {"spark.rapids.sql.reader.batchSizeRows": "64"}

    def q(s):
        n = 300
        df = s.from_pydict({"k": [i % 3 for i in range(n)],
                            "v": list(range(n))},
                           T.schema_of(k=T.IntegerType, v=T.IntegerType))
        # filter leaves non-compacted batches; last() must still pick the
        # globally latest surviving row per key
        return df.filter(col("v") % 7 != 0) \
                 .group_by("k").agg(f.first(col("v")).alias("fv"),
                                    f.last(col("v")).alias("lv"))
    assert_tpu_and_cpu_are_equal(q, conf=conf)


def test_global_first_last_strings():
    def q(s):
        df = s.from_pydict({"s": ["aa", None, "cc"]},
                           T.schema_of(s=T.StringType))
        return df.agg(f.first(col("s")).alias("fs"),
                      f.last(col("s")).alias("ls"))
    _assert_on_tpu(q)
    assert_tpu_and_cpu_are_equal(q)


def test_agg_deferred_merge_fan_in_variants():
    """K-way deferred merge must equal the pairwise fold for associative
    and order-sensitive (First/Last) aggregates alike, at fan-ins that
    divide, straddle, and exceed the batch count."""
    for fan_in in ("2", "3", "8", "64"):
        conf = {"spark.rapids.sql.reader.batchSizeRows": "64",
                "spark.rapids.sql.tpu.agg.mergeFanIn": fan_in}

        def q(s):
            df = gen_df(s, seed=91, n=700, k=T.IntegerType, v=T.LongType)
            return df.group_by("k").agg(
                f.sum(col("v")).alias("sv"),
                f.min(col("v")).alias("mn"),
                f.max(col("v")).alias("mx"),
                f.count(lit(1)).alias("c"),
                f.first(col("v")).alias("fst"),
                f.last(col("v")).alias("lst"))
        assert_tpu_and_cpu_are_equal(q, conf=conf)


def test_whole_stage_single_dispatch_agg():
    """Whole-stage path: multi-batch scan -> fused filter/project ->
    aggregate matches the streaming loop (conf off) exactly."""
    conf_on = {"spark.rapids.sql.reader.batchSizeRows": "128"}
    conf_off = {**conf_on, "spark.rapids.sql.tpu.wholeStage.enabled":
                "false"}

    def q(s):
        df = gen_df(s, seed=71, n=1000, k=T.IntegerType, v=T.LongType)
        return (df.filter(col("v") % 2 == 0)
                .select(col("k"), (col("v") * 3).alias("w"))
                .group_by("k").agg(f.sum(col("w")).alias("s"),
                                   f.count(lit(1)).alias("c"),
                                   f.max(col("w")).alias("mx")))
    a = assert_tpu_and_cpu_are_equal(q, conf=conf_on)
    b = assert_tpu_and_cpu_are_equal(q, conf=conf_off)
    assert sorted(a, key=repr) == sorted(b, key=repr)


def test_whole_stage_global_agg():
    conf = {"spark.rapids.sql.reader.batchSizeRows": "64"}

    def q(s):
        df = gen_df(s, seed=72, n=700, v=T.LongType)
        return df.agg(f.sum(col("v")).alias("s"),
                      f.min(col("v")).alias("mn"))
    assert_tpu_and_cpu_are_equal(q, conf=conf)


def test_whole_stage_unequal_batches_fall_back():
    """A trailing short batch (different capacity bucket) must take the
    streaming path and still be correct."""
    conf = {"spark.rapids.sql.reader.batchSizeRows": "600"}

    def q(s):
        # 1000 rows -> batches of 600 (cap 1024) and 400 (cap 512)
        df = gen_df(s, seed=73, n=1000, k=T.IntegerType, v=T.LongType)
        return df.group_by("k").agg(f.count(lit(1)).alias("c"))
    assert_tpu_and_cpu_are_equal(q, conf=conf)


def test_whole_stage_monotonic_id_correct():
    """Row-offset expressions must NOT take the whole-stage path (vmapped
    offset-0 would repeat per-batch id streams; review regression)."""
    conf = {"spark.rapids.sql.reader.batchSizeRows": "128"}

    def q(s):
        df = gen_df(s, seed=74, n=256, v=T.LongType)
        return (df.select(f.monotonically_increasing_id().alias("id"))
                .agg(f.max(col("id")).alias("mx"),
                     f.count(col("id")).alias("c")))
    rows = assert_tpu_and_cpu_are_equal(q, conf=conf)
    assert rows[0] == (255, 256), rows


def test_whole_stage_mixed_string_widths_fall_back():
    """Equal capacities but different string width buckets must stream,
    not crash at jnp.stack (review regression)."""
    import pyarrow as pa
    from spark_rapids_tpu.engine import TpuSession
    conf = {"spark.rapids.sql.reader.batchSizeRows": "128"}

    def q(s):
        t = pa.table({"s": ["ab"] * 128 + ["x" * 40] * 128,
                      "v": list(range(256))})
        return (s.from_arrow(t).group_by("s")
                .agg(f.sum(col("v")).alias("sv")))
    assert_tpu_and_cpu_are_equal(q, conf=conf)


def test_whole_stage_fallback_does_not_rescan():
    """When the probe bails (unequal caps) the scan must not re-execute
    (review: double I/O)."""
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec.base import ExecContext
    s = TpuSession({"spark.rapids.sql.reader.batchSizeRows": "600"})
    df = gen_df(s, seed=75, n=1000, k=T.IntegerType, v=T.LongType)
    q = df.group_by("k").agg(f.count(lit(1)).alias("c"))
    node = s.plan(q.plan)

    def find_scan(n):
        if type(n).__name__ == "TpuScanMemoryExec":
            return n
        for c in n.children:
            r = find_scan(c)
            if r:
                return r
    scan = find_scan(node)
    list(node.execute(ExecContext(s.conf, runtime=s.runtime)))
    # 1000 rows in 600-row batches = 2 scan output batches, counted ONCE
    assert scan.metrics.values.get("numOutputBatches") == 2, \
        scan.metrics.values


def test_rollup_grouping_sets():
    """ROLLUP = Expand fan-out + one aggregate; a data-null key must stay a
    separate output row from the rolled-up subtotal row (grouping-id
    distinguishes them, like Spark's grouping_id)."""
    def q(s):
        df = s.from_pydict(
            {"ch": ["a", "a", "b", "b", None],
             "id": ["x", "y", "x", "x", "z"],
             "v": [1.0, 2.0, 3.0, 4.0, 5.0]},
            T.schema_of(ch=T.StringType, id=T.StringType, v=T.DoubleType))
        return (df.rollup(col("ch"), col("id"))
                .agg(f.sum(col("v")).alias("sv"),
                     f.count(col("v")).alias("c")))
    _assert_on_tpu(q, FLOAT_AGG)
    rows = assert_tpu_and_cpu_are_equal(q, conf=FLOAT_AGG)
    # 4 leaf groups + 3 channel subtotals (a, b, None) + grand total
    assert len(rows) == 8
    assert (None, None, 15.0, 5) in rows       # grand total
    assert (None, None, 5.0, 1) in rows        # ch=None data group


def test_rollup_compound_agg():
    def q(s):
        df = gen_df(s, seed=33, n=200, k=T.IntegerType, g=T.IntegerType,
                    v=T.LongType)
        return df.rollup(col("k"), col("g")).agg(
            (f.sum(col("v")) / f.count(col("v"))).alias("m"))
    assert_tpu_and_cpu_are_equal(q)


def test_rollup_aggregate_over_key_column():
    """Aggregates over a grouping-key column must see REAL values in
    subtotal rows (Expand nulls only the grouping copies, not the
    originals — Spark semantics)."""
    def q(s):
        df = s.from_pydict(
            {"k": [1, 1, 2, 2], "v": [10, 20, 30, 40]},
            T.schema_of(k=T.IntegerType, v=T.LongType))
        return df.rollup(col("k")).agg(f.sum(col("k")).alias("sk"),
                                       f.sum(col("v")).alias("sv"))
    rows = assert_tpu_and_cpu_are_equal(q)
    assert (None, 6, 100) in rows  # grand total: sum(k)=6, not NULL


def test_cube_grouping_sets():
    """CUBE = every subset of the keys; 2^n grouping sets through the same
    Expand + grouping-id plan as rollup."""
    def q(s):
        df = s.from_pydict(
            {"a": [1, 1, 2, 2], "b": ["x", "y", "x", "y"],
             "v": [10, 20, 30, 40]},
            T.schema_of(a=T.IntegerType, b=T.StringType, v=T.LongType))
        return df.cube(col("a"), col("b")).agg(f.sum(col("v")).alias("sv"))
    rows = assert_tpu_and_cpu_are_equal(q)
    # 4 leaf + 2 a-subtotals + 2 b-subtotals + grand = 9
    assert len(rows) == 9
    assert (None, "x", 40) in rows   # b-only set: a rolled up
    assert (1, None, 30) in rows     # a-only set
    assert (None, None, 100) in rows


def _find_agg(node):
    if isinstance(node, TpuHashAggregateExec):
        return node
    for c in node.children:
        r = _find_agg(c)
        if r is not None:
            return r


# --------------------------------------------------------------------------
# segment reducers (sorted ids, masked): _seg_multi and its _seg_sum face
# --------------------------------------------------------------------------

def test_seg_sum_float_keeps_scatter_semantics():
    """Float sums must survive huge-magnitude neighbors (prefix-diff would
    absorb small segments after a 1e300 running total — the reason floats
    keep scatter, exec/aggregate.py _seg_sum)."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.exec.aggregate import _seg_sum
    cap = 1024
    gid = np.zeros(cap, np.int32)
    gid[2:] = np.arange(2, cap)  # seg 0: rows 0-1, then singletons
    vals = np.full(cap, 123.5)
    vals[0] = 1e300
    contribute = np.ones(cap, bool)
    got = np.asarray(_seg_sum(jnp.asarray(vals), jnp.asarray(gid),
                              jnp.asarray(contribute), cap))
    assert got[0] == 1e300 + 123.5
    assert got[5] == 123.5  # NOT absorbed to 0.0


def test_seg_sum_gather_matches_scatter():
    """The prefix-sum segmented sum, read at the bounds off the sorted ids,
    must equal XLA's
    scatter-based segment_sum on sorted ids, including empty segments,
    masked rows, and the dead-rows-at-cap-1 convention."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.exec.aggregate import _seg_sum
    rng = np.random.RandomState(9)
    cap = 2048
    n_live = 1500
    gid = np.sort(rng.randint(0, 40, n_live))
    gid = np.concatenate([gid, np.full(cap - n_live, cap - 1)])
    vals = rng.randint(-100, 100, cap).astype(np.int64)
    contribute = rng.rand(cap) < 0.8
    contribute[n_live:] = False
    got = np.asarray(_seg_sum(jnp.asarray(vals), jnp.asarray(gid),
                              jnp.asarray(contribute), cap))
    v = np.where(contribute, vals, 0)
    want = np.asarray(jax.ops.segment_sum(
        jnp.asarray(v), jnp.asarray(gid), num_segments=cap,
        indices_are_sorted=True))
    assert (got == want).all()


def test_seg_sum_int_overflow_wraps_like_scatter():
    """int64 prefix-diff wraps identically to per-segment accumulation
    (modular addition is associative)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.exec.aggregate import _seg_sum
    cap = 1024
    gid = np.sort(np.arange(cap) % 7).astype(np.int32)
    vals = np.full(cap, 2**61, np.int64)
    contribute = np.ones(cap, bool)
    got = np.asarray(_seg_sum(jnp.asarray(vals), jnp.asarray(gid),
                              jnp.asarray(contribute), cap))
    want = np.asarray(jax.ops.segment_sum(
        jnp.asarray(vals), jnp.asarray(gid), num_segments=cap,
        indices_are_sorted=True))
    assert (got == want).all()


def test_seg_sum_fewer_segments_than_rows():
    """cap (segment count) smaller than the row count — the global
    kernel's 1-segment whole-batch reduction shape (regression: prefix
    indices were clipped to cap-1 instead of rows-1)."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.exec.aggregate import _seg_sum
    rows = 1024
    gid = np.zeros(rows, np.int32)
    vals = np.arange(rows, dtype=np.int64)
    contribute = (np.arange(rows) % 3) == 0
    got = np.asarray(_seg_sum(jnp.asarray(vals), jnp.asarray(gid),
                              jnp.asarray(contribute), 1))
    want = int(vals[contribute].sum())
    assert got.tolist() == [want]


@pytest.mark.parametrize("dtype", ["int32", "int64", "float32", "float64"])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_seg_multi_against_numpy(op, dtype):
    """Each reduction against a plain loop: a segment of thousands of
    rows, an empty segment, masked-out rows, the dead rows at gid cap-1,
    and all three requested in one call sharing the segment bounds."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.exec.aggregate import _seg_multi
    rng = np.random.RandomState(17)
    cap, n_live = 8192, 7000
    # segment 0 spans 5,000 rows, segment 1 is empty, 2..40 share the rest
    gid = np.concatenate([np.zeros(5000, np.int32),
                          np.sort(rng.randint(2, 41, n_live - 5000)),
                          np.full(cap - n_live, cap - 1)]).astype(np.int32)
    dt = np.dtype(dtype)
    # integer-valued, so a float sum is exact in any order
    vals = rng.randint(-1000, 1000, cap).astype(dt)
    contribute = rng.rand(cap) < 0.7
    contribute[n_live:] = False
    contribute[gid == 7] = False   # a segment with every row masked out
    if dt.kind == "f":
        lo, hi = dt.type(-np.inf), dt.type(np.inf)
    else:
        lo, hi = np.iinfo(dt).min, np.iinfo(dt).max
    # min/max: a masked row compares as `fill`; an empty segment holds
    # the reducer's identity
    fill = {"sum": 0, "min": hi, "max": lo}[op]
    want = np.full(cap, fill, dt)
    for g, v, c in zip(gid, vals, contribute):
        if not c:
            continue
        if op == "sum":
            want[g] += v
        else:
            want[g] = min(want[g], v) if op == "min" else max(want[g], v)
    reqs = [(o, jnp.asarray(vals), jnp.asarray(contribute),
             jnp.asarray({"sum": 0, "min": hi, "max": lo}[o], dt))
            for o in ("sum", "min", "max")]
    got = _seg_multi(reqs, jnp.asarray(gid), cap)
    got = np.asarray(got[("sum", "min", "max").index(op)])
    assert got.dtype == dt
    np.testing.assert_array_equal(got, want)
    assert want[1] == fill and want[7] == fill and abs(want[0]) > 0


def _seg_layout(layout):
    """(gid, cap) of one sorted-id layout the segment bounds must read."""
    import numpy as np
    rng = np.random.RandomState(5)
    if layout == "every_row_its_group":      # ngroups == cap, no dead rows
        return np.arange(512, dtype=np.int32), 512
    if layout == "empty_between_live":       # ids 0, 3, 4, 9, ... skipped
        live = np.sort(rng.choice([0, 3, 4, 9, 10, 11, 40], 400))
        return np.concatenate([live, np.full(112, 511)]).astype(np.int32), 512
    if layout == "single_live_row":
        return np.concatenate([[0], np.full(511, 511)]).astype(np.int32), 512
    if layout == "no_live_row":
        return np.full(512, 511, np.int32), 512
    if layout == "fewer_segments_than_rows":  # the keyless kernels' form
        return np.zeros(1024, np.int32), 1
    assert layout == "fewer_segments_two_ids"
    return np.sort(rng.randint(0, 2, 1024)).astype(np.int32), 2


_SEG_LAYOUTS = ["every_row_its_group", "empty_between_live",
                "single_live_row", "no_live_row", "fewer_segments_than_rows",
                "fewer_segments_two_ids"]


@pytest.mark.parametrize("layout", _SEG_LAYOUTS)
def test_seg_bounds_against_searchsorted(layout):
    """Every id that rows carry: [start, end) is numpy's searchsorted
    left/right; an id no row carries: start == end."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.exec.aggregate import _seg_bounds
    gid, cap = _seg_layout(layout)
    start, end = (np.asarray(x) for x in _seg_bounds(jnp.asarray(gid), cap))
    ids = np.arange(cap)
    present = np.isin(ids, gid)
    np.testing.assert_array_equal(
        start[present], np.searchsorted(gid, ids[present], "left"))
    np.testing.assert_array_equal(
        end[present], np.searchsorted(gid, ids[present], "right"))
    assert (start[~present] == end[~present]).all()


@pytest.mark.parametrize("dtype", ["int64", "float64"])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("layout", _SEG_LAYOUTS)
def test_seg_multi_layouts(layout, op, dtype):
    """Each reduction against a plain loop on the layouts the bounds must
    read: every row its own group, empty ids between live ones, one live
    row, none, and fewer segments than rows."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.exec.aggregate import _seg_multi
    gid, cap = _seg_layout(layout)
    rng = np.random.RandomState(23)
    dt = np.dtype(dtype)
    vals = rng.randint(-1000, 1000, gid.size).astype(dt)
    contribute = rng.rand(gid.size) < 0.8
    if dt.kind == "f":
        lo, hi = dt.type(-np.inf), dt.type(np.inf)
    else:
        lo, hi = np.iinfo(dt).min, np.iinfo(dt).max
    fill = {"sum": 0, "min": hi, "max": lo}[op]
    want = np.full(cap, fill, dt)
    for g, v, c in zip(gid, vals, contribute):
        if not c:
            continue
        if op == "sum":
            want[g] += v
        else:
            want[g] = min(want[g], v) if op == "min" else max(want[g], v)
    [got] = _seg_multi([(op, jnp.asarray(vals), jnp.asarray(contribute),
                         jnp.asarray(fill, dt))], jnp.asarray(gid), cap)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("kernel", ["update", "merge"])
def test_group_keys_are_each_groups_first_row(kernel):
    """The key columns of the sort path's update and merge, a group's
    first row read off the segment bounds, equal the scatter-min form
    (the smallest sorted position of a live row of the group, cap - 1
    where none) on every row, dead ones included; keys carry nulls, a
    string key too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec import aggregate as A
    from spark_rapids_tpu.exec.base import ExecContext
    n = 96
    data = {"k": [None if i % 5 == 0 else i % 7 for i in range(n)],
            "s": [None if i % 4 == 1 else "ab"[i % 2] * (1 + i % 3)
                  for i in range(n)],
            "v": list(range(n))}
    schema = T.Schema([T.StructField("k", T.LongType),
                       T.StructField("s", T.StringType),
                       T.StructField("v", T.LongType)])
    s = TpuSession({**FLOAT_AGG,
                    "spark.rapids.sql.reader.batchSizeRows": "32"})
    q = s.from_pydict(data, schema).filter(col("v") % 3 != 1) \
        .group_by("k", "s").agg(f.sum(col("v")).alias("sv"))
    agg = _find_agg(s.plan(q.plan))
    batches = list(agg.children[0].execute(ExecContext(s.conf,
                                                       runtime=s.runtime)))
    assert len(batches) == 3
    if kernel == "update":
        batch = batches[0]
        keys = [g.eval(batch) for g in agg.grouping]
        got = jax.jit(agg._update_kernel)(batch)
    else:
        # states stacked as the whole-stage program does: dead rows
        # between live ones
        batch = A._flatten_stacked(
            A._stack_states([agg._update_kernel(b) for b in batches]),
            agg._state_schema)
        keys = list(batch.columns[:len(agg.grouping)])
        got = jax.jit(agg._merge_kernel)(batch)
    cap = batch.capacity
    order, gid, _b, ngroups = A.group_rows(keys, batch.sel)
    live_s = jnp.take(batch.sel, order)
    gid = jnp.where(live_s, gid, cap - 1)
    first_pos = jax.ops.segment_min(
        jnp.where(live_s, jnp.arange(cap, dtype=jnp.int64), A._I64_MAX),
        gid, num_segments=cap, indices_are_sorted=True)
    first_idx = jnp.take(order, jnp.clip(first_pos, 0, cap - 1))
    assert 0 < int(ngroups) < cap
    for k, c in zip(keys, got.columns):
        want = k.take(first_idx)
        if not k.dtype.is_string:
            want = want.with_valid(want.valid & got.sel).mask_invalid()
        for a, b in ((c.data, want.data), (c.valid, want.valid),
                     (c.lengths, want.lengths)):
            if a is not None:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_FOLD_SCHEMA = T.Schema([T.StructField("k", T.LongType),
                         T.StructField("s", T.StringType),
                         T.StructField("x", T.DoubleType)])


def _fold_batch(i, n, width, cap=None):
    """Input batch i of the fold cases: n rows, string keys up to `width`
    bytes (the batch's string width is that width's bucket), NULL keys,
    doubles whose sums round."""
    from spark_rapids_tpu.columnar import ColumnarBatch
    rows = range(i * 1000, i * 1000 + n)
    return ColumnarBatch.from_pydict(
        {"k": [None if r % 11 == 0 else r % 5 for r in rows],
         "s": [None if r % 7 == 3 else "abc"[r % 3] * (1 + r % width)
               for r in rows],
         "x": [(r % 13) * 0.1 - 0.35 for r in rows]},
        _FOLD_SCHEMA, capacity=cap)


def _fold_agg(first_last):
    from spark_rapids_tpu.engine import TpuSession
    s = TpuSession(FLOAT_AGG)
    df = s.from_pydict({"k": [1], "s": ["a"], "x": [0.5]}, _FOLD_SCHEMA)
    if first_last:
        q = df.group_by("k").agg(f.first(col("s")).alias("fs"),
                                 f.last(col("x")).alias("lx"),
                                 f.sum(col("x")).alias("sx"))
    else:
        q = df.group_by("k", "s").agg(f.sum(col("x")).alias("sx"),
                                      f.count(lit(1)).alias("n"),
                                      f.min(col("x")).alias("mn"),
                                      f.max(col("x")).alias("mx"),
                                      f.avg(col("x")).alias("ax"))
    return _find_agg(s.plan(q.plan))


def _bucket_states(agg, batches):
    import jax
    bucket = jax.jit(agg._bucket_update_kernel)
    states = []
    for b in batches:
        took, state = bucket(b)
        assert int(took) >= 0
        states.append(state)
    return states


def _fold_case(case):
    """(agg, parts): partial states as the grouped loop folds them."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import concat_batches
    from spark_rapids_tpu.ops import expressions as E
    if case == "sort_first_last_unequal_caps":
        agg = _fold_agg(first_last=True)
        update = jax.jit(lambda b, off: E.eval_with_row_offset(
            agg._update_kernel, b, off))
        parts, off = [], 0
        for i, (n, cap) in enumerate([(1500, 2048), (700, 1024),
                                      (3000, 4096)]):
            b = _fold_batch(i, n, 6, cap)
            parts.append(update(b, jnp.int64(off)))
            off += n
        return agg, parts
    agg = _fold_agg(first_last=False)
    if case == "bucket_string_widths":
        return agg, _bucket_states(agg, [_fold_batch(i, 600, w) for i, w in
                                         enumerate([4, 12, 3, 20])])
    if case == "empty_part":
        b = _fold_batch(1, 600, 9)
        empty = b.with_sel(jnp.zeros_like(b.sel))
        return agg, _bucket_states(agg, [_fold_batch(0, 600, 5), empty,
                                         _fold_batch(2, 600, 5)])
    assert case == "state_plus_8"
    first = _bucket_states(agg, [_fold_batch(i, 600, 4) for i in range(2)])
    state = jax.jit(agg._merge_kernel)(concat_batches(first))
    return agg, [state] + _bucket_states(
        agg, [_fold_batch(i, 600, 3 + 2 * i) for i in range(2, 10)])


def _bits(x):
    import numpy as np
    a = np.asarray(x)
    return a.view(f"u{a.dtype.itemsize}") if a.dtype.kind == "f" else a


@pytest.mark.parametrize("case", ["bucket_string_widths",
                                  "sort_first_last_unequal_caps",
                                  "empty_part", "state_plus_8"])
def test_fold_program_equals_concat_then_merge(case):
    """`jit_agg.fold` (the grouped loop's fold in one launch) against
    what it replaced, `concat_batches` then `_merge_kernel`, on the same
    partial states: the merge's input and its output equal leaf for leaf
    and bit for bit, string widths, capacity and dead rows included."""
    import jax
    import numpy as np
    from spark_rapids_tpu.columnar import bucket_rows, concat_batches
    from spark_rapids_tpu.exec import aggregate as A
    agg, parts = _fold_case(case)
    total = sum(int(p.num_rows()) for p in parts)
    assert total > 0 and len({p.capacity for p in parts}) >= 1
    cap = bucket_rows(max(total, 1))
    want_in = concat_batches(parts)
    got_in = jax.jit(lambda ps: A._concat_prefixes(ps, cap))(parts)
    want = jax.jit(agg._merge_kernel)(want_in)
    got = agg._fold_program(cap)(parts)
    for w, g in ((want_in, got_in), (want, got)):
        w_leaves, w_tree = jax.tree_util.tree_flatten(w)
        g_leaves, g_tree = jax.tree_util.tree_flatten(g)
        assert g_tree == w_tree
        for a, b in zip(w_leaves, g_leaves):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(_bits(a), _bits(b))
    if case == "sort_first_last_unequal_caps":
        assert len({p.capacity for p in parts}) == 3
    if case == "empty_part":
        assert int(parts[1].num_rows()) == 0
