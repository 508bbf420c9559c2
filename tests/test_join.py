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
