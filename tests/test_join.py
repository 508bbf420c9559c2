"""TPU hash join vs CPU oracle.

Mirrors integration_tests/src/main/python/join_test.py from the reference:
every join type crossed with nasty key data (nulls, NaN, -0.0, duplicate
keys, empty sides), all checked CPU-vs-TPU.
"""
import random

import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.plan.logical import col

from compare import assert_tpu_and_cpu_are_equal
from data_gen import gen_value


def keyed_df(session, seed, n, key_range=15, key_type=T.IntegerType,
             null_ratio=0.1, extra=None):
    """A table whose key column collides often (join selectivity)."""
    rng = random.Random(seed)
    keys = []
    for _ in range(n):
        if rng.random() < null_ratio:
            keys.append(None)
        elif key_type is T.StringType:
            keys.append(f"k{rng.randint(0, key_range)}")
        elif key_type is T.DoubleType:
            r = rng.random()
            if r < 0.1:
                keys.append(float("nan"))
            elif r < 0.2:
                keys.append(rng.choice([0.0, -0.0]))
            else:
                keys.append(float(rng.randint(0, key_range)))
        else:
            keys.append(rng.randint(0, key_range))
    data = {"k": keys}
    fields = [T.StructField("k", key_type)]
    for name, dt in (extra or {}).items():
        data[name] = [gen_value(rng, dt) for _ in range(n)]
        fields.append(T.StructField(name, dt))
    return session.from_pydict(data, T.Schema(fields))


def _assert_join_on_tpu(build, conf=None):
    from spark_rapids_tpu.engine import TpuSession
    s = TpuSession(dict(conf or {}))
    text = build(s).explain()
    assert "!SortMergeJoinExec" not in text, text


def _check(build, conf=None):
    _assert_join_on_tpu(build, conf)
    assert_tpu_and_cpu_are_equal(build, conf)


@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti"])
@pytest.mark.parametrize("key_type", [T.IntegerType, T.LongType,
                                      T.StringType, T.DoubleType])
def test_join_types(how, key_type):
    def q(s):
        left = keyed_df(s, 100, 300, key_type=key_type,
                        extra={"a": T.LongType})
        right = keyed_df(s, 200, 200, key_type=key_type,
                         extra={"b": T.DoubleType})
        return left.join(right, "k", how)
    _check(q)


def test_inner_join_then_filter():
    def q(s):
        left = keyed_df(s, 101, 250, extra={"a": T.LongType})
        right = keyed_df(s, 201, 250, extra={"b": T.LongType})
        return left.join(right, on="k", how="inner") \
            .filter(col("a").is_not_null())
    _check(q)


def test_join_duplicate_heavy():
    """Many duplicates on both sides (fan-out join)."""
    def q(s):
        left = keyed_df(s, 102, 400, key_range=3, extra={"a": T.IntegerType})
        right = keyed_df(s, 202, 300, key_range=3, extra={"b": T.IntegerType})
        return left.join(right, "k", "inner")
    _check(q)


def test_join_no_matches():
    def q(s):
        left = keyed_df(s, 103, 100, key_range=5, extra={"a": T.LongType})
        rng = random.Random(203)
        right = s.from_pydict(
            {"k": [rng.randint(100, 200) for _ in range(80)],
             "b": [rng.random() for _ in range(80)]},
            T.Schema([T.StructField("k", T.IntegerType),
                      T.StructField("b", T.DoubleType)]))
        return left.join(right, "k", "left")
    _check(q)


@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti"])
def test_join_empty_build_side(how):
    def q(s):
        left = keyed_df(s, 104, 120, extra={"a": T.LongType})
        right = s.from_pydict(
            {"k": [], "b": []},
            T.Schema([T.StructField("k", T.IntegerType),
                      T.StructField("b", T.DoubleType)]))
        return left.join(right, "k", how)
    _check(q)


def test_join_empty_stream_side():
    def q(s):
        left = s.from_pydict(
            {"k": [], "a": []},
            T.Schema([T.StructField("k", T.IntegerType),
                      T.StructField("a", T.LongType)]))
        right = keyed_df(s, 205, 120, extra={"b": T.DoubleType})
        return left.join(right, "k", "inner")
    _check(q)


def test_join_multi_key():
    def q(s):
        rng = random.Random(106)
        n = 300

        def mk(seed):
            r = random.Random(seed)
            return {
                "k1": [r.randint(0, 8) if r.random() > 0.1 else None
                       for _ in range(n)],
                "k2": [f"s{r.randint(0, 5)}" if r.random() > 0.1 else None
                       for _ in range(n)],
                "v": [r.random() for _ in range(n)],
            }
        schema = T.Schema([T.StructField("k1", T.IntegerType),
                           T.StructField("k2", T.StringType),
                           T.StructField("v", T.DoubleType)])
        left = s.from_pydict(mk(1061), schema)
        right = s.from_pydict(mk(1062), schema)
        return left.join(right, ["k1", "k2"], "inner")
    _check(q)


def test_join_with_residual_condition():
    """Equi keys + non-equi residual: inner joins post-filter on TPU."""
    def q(s):
        left = keyed_df(s, 107, 200, extra={"a": T.IntegerType})
        right = keyed_df(s, 207, 200, extra={"b": T.IntegerType}) \
            .select(col("k").alias("kr"), col("b"))
        return left.join(right,
                         (col("k") == col("kr")) & (col("a") > col("b")),
                         "inner")

    assert_tpu_and_cpu_are_equal(q)


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
def test_conditional_semi_anti_on_device(how):
    """Equi keys + residual for EXISTS semantics run ON DEVICE: the
    condition participates in the candidate-walk counts (beyond the
    reference's inner-only conditional joins, GpuHashJoin tagJoin)."""
    from spark_rapids_tpu.engine import TpuSession

    def q(s):
        left = keyed_df(s, 117, 200, extra={"a": T.IntegerType})
        right = keyed_df(s, 217, 200, extra={"b": T.IntegerType}) \
            .select(col("k").alias("kr"), col("b"))
        return left.join(right,
                         (col("k") == col("kr")) & (col("a") > col("b")),
                         how)

    s = TpuSession({})
    text = q(s).explain()
    assert "!SortMergeJoinExec" not in text, text
    assert_tpu_and_cpu_are_equal(q)


def test_conditional_semi_self_inequality():
    """q16/q94's EXISTS shape: same order, DIFFERENT warehouse — the
    residual references both sides of a self semi-join."""
    def q(s):
        rows = keyed_df(s, 118, 300, key_range=40,
                        extra={"w": T.IntegerType})
        other = rows.select(col("k").alias("k2"), col("w").alias("w2"))
        return rows.join(other, (col("k") == col("k2"))
                         & (col("w") != col("w2")), "left_semi")
    _check(q)


def test_full_join_partitioned_empty_left_partition():
    """Partitioned FULL OUTER: a partition with build rows but NO probe
    rows must still emit its build rows with left nulls (regression: the
    empty-left-partition skip dropped them)."""
    def q(s):
        import spark_rapids_tpu.types as T2
        left = s.from_pydict(
            {"k": [1, 2], "a": [10, 20]},
            T2.Schema([T2.StructField("k", T2.LongType),
                       T2.StructField("a", T2.LongType)]))
        right = s.from_pydict(
            {"kr": [1, 5, 6, 7, 8], "b": [100, 500, 600, 700, 800]},
            T2.Schema([T2.StructField("kr", T2.LongType),
                       T2.StructField("b", T2.LongType)]))
        return left.join(right, col("k") == col("kr"), "full")
    _check(q, conf={
        "spark.rapids.sql.tpu.join.partitioned.enabled": "true",
        "spark.rapids.sql.tpu.join.partitioned.threshold": "1",
        "spark.rapids.sql.tpu.shuffle.partitions": "4"})


def test_cast_accepts_spark_type_names():
    """col.cast('integer')/'int'/'bigint'/'double' all resolve (Spark's
    string type-name surface)."""
    def q(s):
        df = keyed_df(s, 119, 50, extra={"a": T.IntegerType})
        return df.select(col("a").cast("bigint").alias("l"),
                         col("a").cast("double").alias("d"),
                         col("a").cast("int").alias("i"),
                         col("a").cast("integer").alias("i2"))
    _check(q)


def test_conditional_left_join_falls_back():
    """Conditional non-inner joins must fall back to CPU (and be right)."""
    from spark_rapids_tpu.engine import TpuSession

    def q(s):
        left = keyed_df(s, 108, 150, extra={"a": T.IntegerType})
        right = keyed_df(s, 208, 150, extra={"b": T.IntegerType}) \
            .select(col("k").alias("kr"), col("b"))
        return left.join(right,
                         (col("k") == col("kr")) & (col("a") > col("b")),
                         "left")

    s = TpuSession({})
    text = q(s).explain()
    assert "!SortMergeJoinExec" in text
    assert_tpu_and_cpu_are_equal(q)


def test_full_join_on_device():
    """Expression-keyed FULL OUTER runs on device (never-matched build
    rows surface as a left-null tail batch); USING full joins still fall
    back for Spark's coalesced-key contract."""
    from spark_rapids_tpu.engine import TpuSession

    def q(s):
        left = keyed_df(s, 109, 100, extra={"a": T.IntegerType})
        right = keyed_df(s, 209, 100, extra={"b": T.IntegerType}) \
            .select(col("k").alias("kr"), col("b"))
        return left.join(right, col("k") == col("kr"), "full")

    s = TpuSession({})
    text = q(s).explain()
    assert "!SortMergeJoinExec" not in text, text
    assert_tpu_and_cpu_are_equal(q)


def test_right_join_on_device():
    """Expression-keyed RIGHT OUTER runs on device as a side-swapped left
    join under a column-reorder pass-through (the reference has no device
    right join, GpuHashJoin.scala:31-32)."""
    from spark_rapids_tpu.engine import TpuSession

    def q(s):
        left = keyed_df(s, 120, 90, extra={"a": T.IntegerType})
        right = keyed_df(s, 220, 140, extra={"b": T.IntegerType}) \
            .select(col("k").alias("kr"), col("b"))
        return left.join(right, col("k") == col("kr"), "right")

    s = TpuSession({})
    text = q(s).explain()
    assert "!SortMergeJoinExec" not in text, text
    assert_tpu_and_cpu_are_equal(q)


def test_right_join_using_on_device():
    """Right USING joins run on device: the key surfaces from the RIGHT
    block via the post-join reorder (Spark's coalesced-key contract for a
    right-preserving join), in both broadcast and shuffled variants."""
    from spark_rapids_tpu.engine import TpuSession

    def q(s):
        left = keyed_df(s, 121, 60, extra={"a": T.IntegerType})
        right = keyed_df(s, 221, 90, extra={"b": T.IntegerType})
        return left.join(right, "k", "right")

    s = TpuSession({})
    text = q(s).explain()
    assert "!SortMergeJoinExec" not in text, text
    assert_tpu_and_cpu_are_equal(q)
    assert_tpu_and_cpu_are_equal(
        q, conf={"spark.sql.autoBroadcastJoinThreshold": "-1"})


def test_full_join_using_falls_back():
    from spark_rapids_tpu.engine import TpuSession

    def q(s):
        left = keyed_df(s, 109, 100, extra={"a": T.IntegerType})
        right = keyed_df(s, 209, 100, extra={"b": T.IntegerType})
        return left.join(right, "k", "full")

    s = TpuSession({})
    text = q(s).explain()
    assert "!SortMergeJoinExec" in text
    assert_tpu_and_cpu_are_equal(q)


def test_join_then_aggregate():
    """Join feeding an aggregation (the TPC-H shape)."""
    def q(s):
        from spark_rapids_tpu.plan.logical import functions as F
        left = keyed_df(s, 110, 400, key_range=10,
                        extra={"qty": T.LongType})
        right = keyed_df(s, 210, 50, key_range=10,
                         extra={"price": T.DoubleType})
        j = left.join(right, "k", "inner")
        return j.group_by("k").agg(
            F.count(col("qty")).alias("n"),
            F.max(col("price")).alias("mx"))
    assert_tpu_and_cpu_are_equal(q)


def test_self_join_disambiguation():
    def q(s):
        df = keyed_df(s, 111, 150, extra={"a": T.LongType})
        other = keyed_df(s, 111, 150, extra={"a": T.LongType})
        return df.join(other, "k", "left_semi")
    _check(q)


@pytest.mark.parametrize("how", ["right", "full"])
def test_outer_using_join_key_coalesce(how):
    """Unmatched build rows must surface their key in the kept key column
    (CPU fallback path; Spark coalesces USING keys)."""
    from spark_rapids_tpu.engine import TpuSession
    s = TpuSession({"spark.rapids.sql.enabled": "false"})
    left = s.from_pydict(
        {"k": [1], "a": [10]},
        T.Schema([T.StructField("k", T.IntegerType),
                  T.StructField("a", T.LongType)]))
    right = s.from_pydict(
        {"k": [1, 2], "b": [1.0, 2.0]},
        T.Schema([T.StructField("k", T.IntegerType),
                  T.StructField("b", T.DoubleType)]))
    rows = sorted(left.join(right, "k", how).collect(), key=str)
    assert (2, None, 2.0) in rows, rows


def test_left_outer_alias_matches_left():
    """'left_outer' must behave exactly like 'left' on the TPU path."""
    import spark_rapids_tpu.plan.logical as L
    from spark_rapids_tpu.engine import TpuSession, DataFrame

    def q(how):
        s = TpuSession({})
        left = keyed_df(s, 113, 120, extra={"a": T.LongType})
        right = keyed_df(s, 213, 80, extra={"b": T.DoubleType})
        return DataFrame(s, L.LogicalJoin(
            left.plan, right.plan, how, using=["k"])).collect()

    from compare import assert_rows_equal
    assert_rows_equal(q("left"), q("left_outer"))


# --------------------------------------------------------------------------
# the probe kernels against a plain pairing (PR 36: pairs placed in output
# space from the count walk's verified-candidate bits)
# --------------------------------------------------------------------------

def _find_join(node):
    from spark_rapids_tpu.exec.join import TpuHashJoinExec
    if isinstance(node, TpuHashJoinExec):
        return node
    for c in node.children:
        got = _find_join(c)
        if got is not None:
            return got
    return None


def _slot_rows(batch):
    """Every slot of the batch as a row, dead ones too, and the live mask."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar import ColumnarBatch
    every = ColumnarBatch(batch.columns,
                          jnp.ones(batch.capacity, jnp.bool_), batch.schema)
    return every.to_pylist(), np.asarray(batch.sel)


def _pairing_case(case, tag):
    """-> (left columns, right columns, key type).  Column names carry
    `tag`: the kernels are cached by expression and schema, and one case
    traces them under forged hashes."""
    rng = random.Random(len(case) * 1000 + 36)
    kt = T.StringType if case == "string_key" else T.LongType
    n_l, n_r = 90, 70

    def key(v):
        return f"key-{v:03d}" if kt is T.StringType else v

    if case == "unique_keys":
        lk = rng.sample(range(200), n_l)
        rk = rng.sample(range(200), n_r)
    elif case == "many_to_many_dup_3":
        lk = [v for v in range(30) for _ in range(3)]
        rk = [v for v in range(10, 34) for _ in range(3)][:n_r]
        rng.shuffle(lk)
        rng.shuffle(rk)
    elif case == "dup_above_32":
        lk = [7] * 3 + rng.sample(range(100, 300), n_l - 3)
        rk = [7] * 40 + rng.sample(range(100, 300), n_r - 40)
        rng.shuffle(lk)
        rng.shuffle(rk)
    elif case == "out_cap_larger_than_total":
        lk = list(range(n_l))
        rk = [5, 5, 41] + list(range(1000, 1000 + n_r - 3))
    else:
        lk = [rng.randint(0, 25) for _ in range(n_l)]
        rk = [rng.randint(5, 30) for _ in range(n_r)]
    lk = [key(v) for v in lk]
    rk = [key(v) for v in rk]
    if case == "null_and_dead_rows":
        lk = [None if rng.random() < 0.15 else v for v in lk]
        rk = [None if rng.random() < 0.15 else v for v in rk]
    left = {f"k_{tag}": lk, f"a_{tag}": list(range(len(lk)))}
    right = {f"kr_{tag}": rk, f"b_{tag}": [1000 + j for j in range(len(rk))]}
    if case == "with_condition":
        left[f"x_{tag}"] = [rng.randint(0, 9) for _ in lk]
        right[f"y_{tag}"] = [rng.randint(0, 9) for _ in rk]
    return left, right, kt


@pytest.mark.parametrize("case", [
    "unique_keys", "many_to_many_dup_3", "window_wider_than_matches",
    "dup_above_32", "null_and_dead_rows", "string_key", "with_condition",
    "out_cap_larger_than_total"])
@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_probe_kernels_against_plain_pairing(how, case, monkeypatch):
    """The output batch row for row, IN ORDER: for each live stream row in
    order, the build rows of its window whose key is equal (and whose pair
    passes the condition), in window order, which is the hash-sorted build
    side's; a `left` / `full` row without one comes out once with the
    right side null, in its place; the `full` tail is the never-matched
    build rows in that order."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec import join as J
    from spark_rapids_tpu.exec.base import ExecContext

    if case == "window_wider_than_matches":
        real = J.hash_columns_double

        def forged(cols, sel):
            # 4 prefixes with the low bits free: a window holds about a
            # quarter of the build side, nearly all of it other keys
            h1, h2 = real(cols, sel)
            h = ((h1 >> jnp.uint64(62)) << jnp.uint64(62)) \
                | (h1 & jnp.uint64(0xFF))
            return jnp.where(sel, h, jnp.uint64(2**64 - 1)), h2
        monkeypatch.setattr(J, "hash_columns_double", forged)

    tag = f"{case[:6]}{len(case)}_{how}"
    ldata, rdata, kt = _pairing_case(case, tag)
    s = TpuSession({})

    def frame(data):
        return s.from_pydict(data, T.Schema(
            [T.StructField(n, kt if n.startswith("k") else T.LongType)
             for n in data]))
    on = col(f"k_{tag}") == col(f"kr_{tag}")
    if case == "with_condition":
        # the planner keeps conditional outer joins off the device; the
        # kernels' per-pair condition is the same for every type, so the
        # inner plan's node stands in with its type set
        on = on & (col(f"x_{tag}") > col(f"y_{tag}"))
        join = _find_join(s.plan(frame(ldata).join(frame(rdata), on,
                                                   "inner").plan))
        join.join_type = how
    else:
        join = _find_join(s.plan(frame(ldata).join(frame(rdata), on,
                                                   how).plan))
    assert join is not None
    ctx = ExecContext(s.conf, s.runtime)
    (lb,) = list(join.children[0].execute(ctx))
    (rb,) = list(join.children[1].execute(ctx))
    if case == "null_and_dead_rows":
        rng = np.random.default_rng(36)
        lb = lb.with_sel(lb.sel & jnp.asarray(rng.random(lb.capacity) > 0.2))
        rb = rb.with_sel(rb.sel & jnp.asarray(rng.random(rb.capacity) > 0.2))

    # the reference: window order is the hash-sorted build side's
    sorted_build, _bkeys, _h = jax.jit(join._build_kernel)(rb)
    brows, blive = _slot_rows(sorted_build)
    lrows, llive = _slot_rows(lb)
    lnames, rnames = lb.schema.names, rb.schema.names
    lk_i = [i for i, n in enumerate(lnames) if n.startswith("k")][0]
    rk_i = [i for i, n in enumerate(rnames) if n.startswith("k")][0]

    def passes(lrow, brow):
        if lrow[lk_i] is None or lrow[lk_i] != brow[rk_i]:
            return False
        if case != "with_condition":
            return True
        both = dict(zip(lnames + rnames, lrow + brow))
        return both[f"x_{tag}"] > both[f"y_{tag}"]

    expect, hit = [], np.zeros(len(brows), bool)
    for i, lrow in enumerate(lrows):
        if not llive[i]:
            continue
        found = [j for j, brow in enumerate(brows)
                 if blive[j] and passes(lrow, brow)]
        hit[found] = True
        expect += [lrow + brows[j] for j in found]
        if not found and how != "inner":
            expect.append(lrow + (None,) * len(rnames))
    if how == "full":
        expect += [(None,) * len(lnames) + brow
                   for j, brow in enumerate(brows) if blive[j] and not hit[j]]
    assert expect, "the case produces no row"

    got = [row for out in join._join_stream(rb, [lb]) for row in
           out.to_pylist()]
    assert got == expect
    if case == "dup_above_32":
        assert join._dup_guess == 64                    # two words a row
    if case == "window_wider_than_matches":
        assert join._dup_guess >= 16


# --------------------------------------------------------------------------
# the pass-through output: where no live stream row has more than one
# candidate and the output's capacity bucket is the stream batch's, the
# stream batch itself comes out under a mask, its columns untouched
# --------------------------------------------------------------------------

def _pass_through_case(how, case):
    """-> (join node, stream batch, build batch): 900 stream rows against
    200 build rows of unique keys (two thirds of the stream rows match),
    one 1,024-row stream batch, so the output's bucket is the stream's."""
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec.base import ExecContext
    rng = random.Random(len(case) * 100 + len(how))
    tag = f"{case[:5]}{len(case)}_{how}"
    n_l, n_r = 900, 200
    rk = rng.sample(range(300), n_r)
    lk = [rng.randint(0, 299) for _ in range(n_l)]
    if case == "null_stream_keys":
        lk = [None if rng.random() < 0.2 else v for v in lk]
    s = TpuSession({})
    k, kr = f"k_{tag}", f"kr_{tag}"
    if case == "using_drop":
        kr = k
    left = s.from_pydict(
        {k: lk, f"a_{tag}": list(range(n_l)),
         f"x_{tag}": [rng.randint(0, 9) for _ in lk]},
        T.Schema([T.StructField(k, T.LongType),
                  T.StructField(f"a_{tag}", T.LongType),
                  T.StructField(f"x_{tag}", T.IntegerType)]))
    right = s.from_pydict(
        {kr: rk, f"b_{tag}": [f"b-{j}" for j in range(n_r)],
         f"y_{tag}": [rng.randint(0, 9) for _ in rk]},
        T.Schema([T.StructField(kr, T.LongType),
                  T.StructField(f"b_{tag}", T.StringType),
                  T.StructField(f"y_{tag}", T.IntegerType)]))
    if case == "using_drop":
        df = left.join(right, k, how if how != "full" else "inner")
    elif case == "with_condition":
        df = left.join(right, (col(k) == col(kr))
                       & (col(f"x_{tag}") > col(f"y_{tag}")), "inner")
    else:
        df = left.join(right, col(k) == col(kr), how)
    join = _find_join(s.plan(df.plan))
    # the planner keeps conditional outer joins and full USING joins off
    # the device; the kernels are the same for every type, so the inner
    # plan's node stands in with its type set
    join.join_type = how
    ctx = ExecContext(s.conf, s.runtime)
    (lb,) = list(join.children[0].execute(ctx))
    (rb,) = list(join.children[1].execute(ctx))
    assert (lb.capacity, rb.capacity) == (1024, 1024)
    if case == "dead_build_rows":
        keep = np.random.default_rng(41).random(rb.capacity) > 0.3
        rb = rb.with_sel(rb.sel & jnp.asarray(keep))
    return join, lb, rb


def _probed(join, lb, rb):
    """The build and the fused probe at the first batch's guess of 8 ->
    (build batch, lo, counts, starts, hits, window width, total)."""
    import jax
    import numpy as np
    build, bkeys, h1s = jax.jit(join._build_kernel)(rb)
    lo, _hi, counts, starts, hits, scalars = jax.jit(
        lambda *a: join._probe_kernel(8, *a))(lb, build, bkeys, h1s)
    md, total = (int(x) for x in np.asarray(scalars))
    return build, lo, counts, starts, hits, md, total


@pytest.mark.parametrize("case", [
    "unique_keys", "with_condition", "using_drop", "null_stream_keys",
    "dead_build_rows"])
@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_pass_through_equals_output_space(how, case):
    """The pass-through output against the output-space gather's over the
    same probe: the same live rows in the same order, the selection the
    matched rows (inner) or the stream's own (left, full), the right
    side's validity slot for slot over the live rows, the same build hits
    (full); and `_join_stream` takes it, with the stream batch's own
    column arrays in the output and the total as its known row count."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from spark_rapids_tpu.columnar.batch import bucket_rows
    from spark_rapids_tpu.metrics import names as MN
    join, lb, rb = _pass_through_case(how, case)
    build, lo, counts, starts, hits, md, total = _probed(join, lb, rb)
    assert md <= 1 and bucket_rows(max(total, 1)) == lb.capacity
    hits = hits[:1]
    placed = jax.jit(functools.partial(join._gather_kernel, lb.capacity))(
        lb, build, lo, counts, starts, jnp.int64(total), hits)
    sel, rcols, b_hit = jax.jit(join._passthrough_kernel)(lb, build, lo,
                                                          hits)
    passed = join._joined_batch(lb.columns, rcols, sel, lb.schema,
                                build.schema)
    if how == "full":
        placed, placed_hit = placed
        np.testing.assert_array_equal(np.asarray(b_hit),
                                      np.asarray(placed_hit))
    else:
        assert b_hit is None
    rows = passed.to_pylist()
    assert rows == placed.to_pylist()
    assert len(rows) == total and total > 100
    live = np.asarray(passed.sel)
    matched = np.asarray(rcols[0].valid) & np.asarray(lb.sel)
    np.testing.assert_array_equal(
        live, matched if how == "inner" else np.asarray(lb.sel))
    n_left = len(lb.columns)
    assert all(i >= n_left for i in join.using_drop)
    assert len(passed.columns) == len(placed.columns) \
        == n_left + len(rb.columns) - len(join.using_drop)
    for p, g in zip(passed.columns[n_left:], placed.columns[n_left:]):
        np.testing.assert_array_equal(np.asarray(p.valid)[live],
                                      np.asarray(g.valid)[:total])
    if how != "inner":
        assert 0 < matched.sum() < total       # some rows are unmatched

    outs = list(join._join_stream(rb, [lb]))
    out = outs[0]
    assert all(o.data is i.data for o, i in zip(out.columns, lb.columns))
    assert out.known_rows == total and out.to_pylist() == rows
    got = join.metrics.values
    assert got.get(MN.JOIN_PASS_THROUGH_BATCHES, 0) == 1
    assert got.get(MN.JOIN_OUTPUT_SPACE_BATCHES, 0) == 0


def _bypass_query(case):
    """-> a query whose one stream batch the pass-through must NOT take:
    a build key duplicated (window width 2), a selective join (1,024-row
    output bucket of a 4,096-row stream batch), or every key's hash
    forged onto 4 prefixes (distinct build keys share a window)."""
    def q(s):
        rng = random.Random(len(case) + 41)
        n_l = 4000 if case == "selective" else 900
        lk = [rng.randint(0, n_l - 1) for _ in range(n_l)]
        rk = rng.sample(range(n_l), 100 if case == "selective" else 200)
        if case == "duplicate_build_key":
            rk[1] = rk[0]
            lk[5] = rk[0]
        tag = f"bp{len(case)}"
        left = s.from_pydict(
            {f"k_{tag}": lk, f"a_{tag}": list(range(n_l))},
            T.Schema([T.StructField(f"k_{tag}", T.LongType),
                      T.StructField(f"a_{tag}", T.LongType)]))
        right = s.from_pydict(
            {f"kr_{tag}": rk, f"b_{tag}": list(range(len(rk)))},
            T.Schema([T.StructField(f"kr_{tag}", T.LongType),
                      T.StructField(f"b_{tag}", T.LongType)]))
        return left.join(right, col(f"k_{tag}") == col(f"kr_{tag}"),
                         "inner")
    return q


@pytest.mark.parametrize("case", ["duplicate_build_key", "selective",
                                  "prefix_collision"])
def test_pass_through_bypasses(case, monkeypatch):
    """Where a row may multiply or the output compacts, the output-space
    gather answers, as before, and the answer is the host executors'."""
    import jax.numpy as jnp
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec import join as J
    if case == "prefix_collision":
        real = J.hash_columns_double

        def forged(cols, sel):
            h1, h2 = real(cols, sel)
            h = ((h1 >> jnp.uint64(62)) << jnp.uint64(62)) \
                | (h1 & jnp.uint64(0xFF))
            return jnp.where(sel, h, jnp.uint64(2**64 - 1)), h2
        monkeypatch.setattr(J, "hash_columns_double", forged)
    q = _bypass_query(case)
    s = TpuSession({})
    before = dict(s.query_metrics_total)
    q(s).collect()

    def moved(name):
        return s.query_metrics_total.get(name, 0) - before.get(name, 0)
    assert moved("joinMergedWindowBatches") == 1
    assert moved("joinOutputSpaceBatches") == 1
    assert moved("joinPassThroughBatches") == 0
    assert_tpu_and_cpu_are_equal(q)


@pytest.mark.parametrize("how", ["inner", "left"])
def test_pass_through_and_output_space_batches_counted(how):
    """Three stream batches of 1,024 rows; the third holds the one key the
    build side has twice: two batches pass through, the third is gathered,
    and the two counters sum to `joinMergedWindowBatches`."""
    from spark_rapids_tpu.engine import TpuSession

    def q(s):
        n_l = 3000
        lk = list(range(n_l))
        lk[2500] = 5000
        rk = list(range(0, n_l, 5)) + [5000, 5000]
        left = s.from_pydict(
            {"kcnt": lk, "acnt": list(range(n_l))},
            T.Schema([T.StructField("kcnt", T.LongType),
                      T.StructField("acnt", T.LongType)]))
        right = s.from_pydict(
            {"krcnt": rk, "bcnt": list(range(len(rk)))},
            T.Schema([T.StructField("krcnt", T.LongType),
                      T.StructField("bcnt", T.LongType)]))
        return left.join(right, col("kcnt") == col("krcnt"), how)
    conf = {"spark.rapids.sql.reader.batchSizeRows": "1024"}
    s = TpuSession(dict(conf))
    before = dict(s.query_metrics_total)
    rows = q(s).collect()
    assert len(rows) == (601 if how == "inner" else 3001)

    def moved(name):
        return s.query_metrics_total.get(name, 0) - before.get(name, 0)
    assert moved("joinMergedWindowBatches") == 3
    assert moved("joinPassThroughBatches") == 2
    assert moved("joinOutputSpaceBatches") == 1
    assert_tpu_and_cpu_are_equal(q, conf)


def test_whole_stage_over_a_join_does_not_donate_the_stream_arrays(
        monkeypatch):
    """The pass-through output holds the stream batch's own arrays, so no
    consumer may donate them: the fusion pass never marks a stage over a
    join donatable, and with donation on and the scan cache off (the case
    where a scan's batches are donated) every stream array the join handed
    on is alive after the query."""
    import jax
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec import join as J
    from spark_rapids_tpu.exec.whole_stage import TpuWholeStageExec
    from spark_rapids_tpu.plan.fusion import source_donatable
    conf = {"spark.rapids.sql.tpu.memoryScanCache.enabled": "false",
            "spark.rapids.sql.tpu.donation.enabled": "true"}
    handed_on = []
    real = J.TpuHashJoinExec._joined_batch

    def spy(self, lcols, rcols, sel, lschema, rschema):
        if not isinstance(sel, jax.core.Tracer):    # not inside a program
            handed_on.extend(c.data for c in lcols)
        return real(self, lcols, rcols, sel, lschema, rschema)
    monkeypatch.setattr(J.TpuHashJoinExec, "_joined_batch", spy)

    def q(s):
        left = keyed_df(s, 141, 900, key_range=400, null_ratio=0.0,
                        extra={"a": T.LongType})
        right = s.from_pydict(
            {"kr": list(range(0, 400, 2)), "b": list(range(200))},
            T.Schema([T.StructField("kr", T.IntegerType),
                      T.StructField("b", T.LongType)]))
        return left.join(right, col("k") == col("kr"), "inner") \
            .select((col("a") + col("b")).alias("s"), col("k"))
    s = TpuSession(dict(conf))
    plan = s.plan(q(s).plan)
    stages = []

    def walk(n):
        if isinstance(n, TpuWholeStageExec) \
                and isinstance(n.children[0], J.TpuHashJoinExec):
            stages.append(n)
        for c in n.children:
            walk(c)
    walk(plan)
    assert stages and not any(ws.donate_inputs for ws in stages)
    assert not source_donatable(stages[0].children[0])
    assert_tpu_and_cpu_are_equal(q, conf)
    assert handed_on
    assert not any(a.is_deleted() for a in handed_on)
