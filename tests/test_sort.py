"""TPU sort vs CPU oracle (order-sensitive comparisons)."""
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.plan.logical import SortOrder, col, functions as f

from compare import assert_rows_equal, run_both
from data_gen import gen_df


def _assert_on_tpu(build, conf=None):
    from spark_rapids_tpu.engine import TpuSession
    s = TpuSession(dict(conf or {}))
    text = build(s).explain()
    assert "!SortExec" not in text, text


def check(build, conf=None):
    cpu, tpu = run_both(build, conf)
    assert_rows_equal(cpu, tpu, ignore_order=False)


def test_sort_int_asc():
    def q(s):
        df = gen_df(s, seed=30, n=500, a=T.IntegerType, b=T.LongType)
        return df.order_by("a", "b")  # b tiebreak keeps order deterministic
    _assert_on_tpu(q)
    check(q)


def test_sort_int_desc():
    def q(s):
        df = gen_df(s, seed=31, n=500, a=T.IntegerType, b=T.LongType)
        return df.order_by(SortOrder(col("a"), ascending=False),
                           SortOrder(col("b"), ascending=False))
    _assert_on_tpu(q)
    check(q)


@pytest.mark.parametrize("asc", [True, False])
@pytest.mark.parametrize("nulls_first", [True, False, None])
def test_sort_double_nan_nulls(asc, nulls_first):
    def q(s):
        df = gen_df(s, seed=32, n=400, d=T.DoubleType, t=T.LongType)
        return df.order_by(
            SortOrder(col("d"), ascending=asc, nulls_first=nulls_first),
            SortOrder(col("t")))
    _assert_on_tpu(q)
    check(q)


@pytest.mark.parametrize("asc", [True, False])
def test_sort_strings(asc):
    def q(s):
        df = gen_df(s, seed=33, n=400, st=T.StringType, t=T.LongType)
        return df.order_by(SortOrder(col("st"), ascending=asc),
                           SortOrder(col("t")))
    _assert_on_tpu(q)
    check(q)


def test_sort_multi_key_mixed_direction():
    def q(s):
        df = gen_df(s, seed=34, n=500, a=T.ShortType, b=T.DoubleType,
                    st=T.StringType, t=T.LongType)
        return df.order_by(SortOrder(col("a")),
                           SortOrder(col("b"), ascending=False),
                           SortOrder(col("st")),
                           SortOrder(col("t")))
    _assert_on_tpu(q)
    check(q)


def test_sort_expression_key():
    def q(s):
        df = gen_df(s, seed=35, n=300, a=T.IntegerType, b=T.IntegerType,
                    t=T.LongType)
        return df.order_by(SortOrder(col("a") + col("b")),
                           SortOrder(col("t")))
    _assert_on_tpu(q)
    check(q)


def test_sort_dates_timestamps_bools():
    def q(s):
        df = gen_df(s, seed=36, n=400, d=T.DateType, ts=T.TimestampType,
                    bo=T.BooleanType, t=T.LongType)
        return df.order_by(SortOrder(col("bo"), nulls_first=False),
                           SortOrder(col("d"), ascending=False),
                           SortOrder(col("ts")), SortOrder(col("t")))
    _assert_on_tpu(q)
    check(q)


def test_sort_then_limit_topn():
    def q(s):
        df = gen_df(s, seed=37, n=600, a=T.IntegerType, t=T.LongType)
        return df.order_by(SortOrder(col("a"), ascending=False),
                           SortOrder(col("t"))).limit(25)
    _assert_on_tpu(q)
    check(q)


def test_sort_after_filter_groupby():
    def q(s):
        df = gen_df(s, seed=38, n=700, k=T.IntegerType, v=T.LongType)
        return (df.filter(col("v").is_not_null())
                .group_by("k").agg(f.sum(col("v")).alias("sv"))
                .order_by(SortOrder(col("sv"), nulls_first=False),
                          SortOrder(col("k"))))
    _assert_on_tpu(q)
    check(q)


def test_sort_empty_input():
    def q(s):
        df = gen_df(s, seed=39, n=50, a=T.IntegerType)
        return df.filter(col("a") > 10**9).order_by("a")
    check(q)


def test_sort_fallback_disabled_conf():
    """Kill-switch conf falls back to CPU and still answers correctly."""
    def q(s):
        df = gen_df(s, seed=40, n=200, a=T.IntegerType, t=T.LongType)
        return df.order_by("a", "t")
    cpu, tpu = run_both(q, {"spark.rapids.sql.exec.SortExec": "false"})
    assert_rows_equal(cpu, tpu, ignore_order=False)


def test_external_sort_range_partitioned():
    """Inputs past the batch target sort via range exchange + per-partition
    lexsort instead of one giant concat; output order must still be exact
    (including nulls/NaN placement) and arrive as multiple batches."""
    conf = {"spark.rapids.sql.reader.batchSizeRows": "256",
            "spark.rapids.sql.batchSizeBytes": "8k"}

    def q(s):
        df = gen_df(s, seed=41, n=4000, a=T.IntegerType, b=T.DoubleType,
                    c=T.StringType)
        return df.order_by(col("a"), col("b").desc(), "c")
    cpu, tpu = run_both(q, conf=conf)
    assert_rows_equal(cpu, tpu, ignore_order=False, approx_float=True)

    # the external path actually produced multiple output batches
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec.base import ExecContext
    s = TpuSession(conf)
    df = q(s)
    node = s.plan(df.plan)
    nb = sum(1 for _ in node.execute(ExecContext(s.conf,
                                                 runtime=s.runtime)))
    assert nb > 1, "external sort did not partition"


@pytest.mark.parametrize("asc", [True, False])
def test_double_sort_on_the_tpu_branch_rides_the_packed_path(monkeypatch, asc):
    """XLA:TPU has no f64->int bitcast, so there doubles sort by the two
    32-bit keys of their f32 pair (exec/sort.py f64_pair_keys) on the
    packed single-operand path: the multi-operand f64 lexsort it replaces
    took 9 minutes to compile for a v5e.  Steered from the test: the
    permutation must equal the CPU branch's over values the device holds
    (f32 pairs), NaN/inf/zeros/duplicates/nulls included."""
    import jax
    import numpy as np

    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.exec.sort import sort_order
    from spark_rapids_tpu.ops import expressions as E
    from spark_rapids_tpu.types import (DoubleType, LongType, Schema,
                                        StructField)
    rng = np.random.RandomState(5)
    x = rng.uniform(-1e5, 1e5, 1000)
    hi = x.astype(np.float32)
    x = hi.astype(np.float64) + (x - hi).astype(np.float32)  # f32 pairs
    x[:40] = np.repeat(x[40:50], 4)
    x[100:106] = [np.nan, np.inf, -np.inf, 0.0, -0.0, np.nan]
    vals = [None if i % 97 == 0 else float(v) for i, v in enumerate(x)]
    schema = Schema([StructField("d", DoubleType),
                     StructField("i", LongType)])
    batch = ColumnarBatch.from_pydict(
        {"d": vals, "i": list(range(len(vals)))}, schema)
    keys = [E.BoundReference(0, DoubleType), E.BoundReference(1, LongType)]

    def order():
        stats: dict = {}
        out = np.asarray(sort_order(batch, keys, [asc, True],
                                    [asc, True], stats=stats))
        assert stats["packed"]
        return out
    on_cpu = order()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    np.testing.assert_array_equal(order(), on_cpu)
