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
    32-bit keys of their f32 pair (ops/sort_keys.py f64_pair_keys) on the
    packed single-operand path: the multi-operand f64 lexsort it replaces
    took 9 minutes to compile for a v5e.  Steered from the test: the
    permutation must equal the CPU branch's over values the device holds
    (f32 pairs), NaN/inf/zeros/duplicates/nulls included."""
    import jax
    import numpy as np

    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.ops.sort_keys import sort_order
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


# --------------------------------------------------------------------------
# the one stable argsort (utils/packed_sort.stable_argsort)
# --------------------------------------------------------------------------

def test_packed_argsort_equals_lexsort():
    """Identical permutation to jnp.lexsort over the same components —
    including ties (stability via the embedded row id)."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.utils.packed_sort import stable_argsort
    rng = np.random.RandomState(2)
    cap = 4096
    a = rng.randint(0, 50, cap).astype(np.uint64)     # many ties
    b = rng.randint(0, 1 << 40, cap).astype(np.uint64)
    got = np.asarray(stable_argsort(
        [(jnp.asarray(a), 6), (jnp.asarray(b), 40)], cap))
    want = np.asarray(jnp.lexsort((jnp.asarray(b), jnp.asarray(a))))
    assert np.array_equal(got, want)


def test_packed_argsort_multiword_radix():
    """Total width far past one 64-bit word: the LSD radix pass
    composition must still equal the one-shot ordering."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.utils.packed_sort import stable_argsort
    rng = np.random.RandomState(3)
    cap = 2048
    comps = [(rng.randint(0, 2**60, cap).astype(np.uint64), 64)
             for _ in range(3)]
    got = np.asarray(stable_argsort(
        [(jnp.asarray(c), w) for c, w in comps], cap))
    want = np.asarray(jnp.lexsort(tuple(
        jnp.asarray(c) for c, _ in reversed(comps))))
    assert np.array_equal(got, want)


def _argsort_components(shape, cap, rng):
    """The component lists the engine's callers pass, with ties and dead
    rows: (uint64 values < 2^width, width) pairs, MSB first."""
    import numpy as np
    live = rng.rand(cap) < 0.8
    # few distinct 64-bit values, the extremes among them: many ties
    pool = np.concatenate([rng.randint(0, 2**63, 35).astype(np.uint64) * 2,
                           np.array([0, 2**64 - 1], np.uint64)])

    def hashes():
        h = pool[rng.randint(0, len(pool), cap)]
        return np.where(live, h, np.uint64(2**64 - 1))  # dead rows last
    if shape == "live_mask":       # compact, the keyless group_rows
        return [((~live).astype(np.uint64), 1)]
    if shape == "hash":            # the join build
        return [(hashes(), 64)]
    if shape == "hash2":           # group_rows
        return [(hashes(), 64), (hashes(), 64)]
    if shape == "hash4":           # group_rows with distinct values
        return [(hashes(), 64) for _ in range(4)]
    assert shape == "small_int"    # partition split, quota split
    n = 13
    dest = np.where(live, rng.randint(0, n, cap), n).astype(np.uint64)
    return [(dest, n.bit_length())]


@pytest.mark.parametrize("cap", [1024, 8192, 1000, 1536])
@pytest.mark.parametrize("shape", ["live_mask", "hash", "hash2", "hash4",
                                   "small_int"])
def test_stable_argsort_equals_lexsort(shape, cap):
    """What every caller relies on: the permutation jnp.lexsort gives
    over the same components, ties in original order, whatever the
    capacity (1,000 and 1,536 take the in-module variadic fallback)."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.utils.packed_sort import plan_passes, \
        stable_argsort
    rng = np.random.RandomState(cap + len(shape))
    comps = _argsort_components(shape, cap, rng)
    got = np.asarray(stable_argsort(
        [(jnp.asarray(a), w) for a, w in comps], cap))
    # lexsort: LAST key is primary
    want = np.asarray(jnp.lexsort(tuple(
        jnp.asarray(a) for a, _ in reversed(comps))))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    packed = cap & (cap - 1) == 0
    assert (plan_passes(sum(w for _, w in comps), cap) > 0) == packed


_LATCH_MODULES = ("exec.aggregate", "exec.sort", "exec.join", "exec.window",
                  "exec.base", "ops.sort_keys", "utils.packed_sort")


def _conf_latches():
    """Every module-level list of booleans in the operators layer and
    what it sorts with: the shape a per-query conf latch has."""
    import importlib
    for name in _LATCH_MODULES:
        mod = importlib.import_module("spark_rapids_tpu." + name)
        for val in vars(mod).values():
            if isinstance(val, list) and val and \
                    all(isinstance(x, bool) for x in val):
                yield val


def _keyed_plan(kind, s):
    from spark_rapids_tpu import Window
    df = s.from_pydict({"k": [i % 5 for i in range(64)],
                        "v": list(range(64))})
    if kind == "TpuHashAggregateExec":
        return df.group_by("k").agg(f.sum(col("v")).alias("sv"))
    if kind == "TpuSortExec":
        return df.order_by(SortOrder(col("k"), ascending=False), "v")
    if kind == "TpuHashJoinExec":
        return df.join(df.select(col("k"), col("v").alias("w")), on="k")
    assert kind == "TpuWindowExec"
    w = Window.partitionBy(col("k")).orderBy(col("v"))
    return df.select(col("k"), f.row_number().over(w).alias("rn"))


@pytest.mark.parametrize("kind", ["TpuHashAggregateExec", "TpuSortExec",
                                  "TpuHashJoinExec", "TpuWindowExec"])
def test_kernel_keys_do_not_depend_on_process_state(kind):
    """A kernel key states the plan, nothing else: the same plan under
    two sessions gives the same key, and no flag of a module can move it
    (a key read from process state can cache a program that was traced
    after another query flipped the flag)."""
    from spark_rapids_tpu.engine import TpuSession

    def key():
        todo = [_keyed_plan(kind, TpuSession({})).physical_plan()]
        while todo:
            node = todo.pop()
            if kind in [c.__name__ for c in type(node).__mro__]:
                return node.kernel_key()
            todo.extend(node.children)
        raise AssertionError(f"no {kind} in the plan")
    first = key()
    assert first == key()
    latches = list(_conf_latches())
    try:
        for flag in latches:
            flag[:] = [not x for x in flag]
        assert key() == first
    finally:
        for flag in latches:
            flag[:] = [not x for x in flag]


@pytest.mark.parametrize("asc", [True, False])
def test_sort_order_at_a_capacity_that_is_no_power_of_two(asc):
    """A whole-stage program hands the sort its N per-batch states
    concatenated (TPC-H Q1: 6 x 1,024 rows): no power of two, so the one
    variadic lexsort over the packed components orders it.  Same live
    rows in the same order as at the next power of two, strings, doubles,
    NaN, nulls and ties included."""
    import numpy as np

    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.ops import expressions as E
    from spark_rapids_tpu.ops.sort_keys import sort_order
    from spark_rapids_tpu.types import (DoubleType, Schema, StringType,
                                        StructField)
    rng = np.random.RandomState(11)
    n = 1400
    s = [None if i % 53 == 0 else "k%d" % rng.randint(0, 9)
         for i in range(n)]
    d = rng.uniform(-5, 5, n).round(1)
    d[::97] = np.nan
    d = [None if i % 41 == 0 else float(v) for i, v in enumerate(d)]
    schema = Schema([StructField("s", StringType),
                     StructField("d", DoubleType)])
    keys = [E.BoundReference(0, StringType), E.BoundReference(1, DoubleType)]

    def order(capacity):
        batch = ColumnarBatch.from_pydict({"s": s, "d": d}, schema,
                                          capacity=capacity)
        stats: dict = {}
        out = np.asarray(sort_order(batch, keys, [asc, not asc],
                                    [asc, not asc], stats=stats))
        assert stats["packed"] == (capacity & (capacity - 1) == 0)
        assert sorted(out[n:]) == list(range(n, capacity))  # dead rows last
        return out[:n]
    np.testing.assert_array_equal(order(1536), order(2048))
