"""Seeded plan/schema fuzzer (VERDICT r3 item 7).

Random schemas over the supported type surface, random operator trees
(project / filter / aggregate / join / sort / distinct / union / window),
executed on both engines and compared.  Every case is a fixed seed — a
failure names the seed in the test id and the assertion message, so
`pytest "tests/test_fuzz.py::test_fuzz_plan[seed17]"` replays it exactly.

Reference analogue: tests/.../FuzzerUtils.scala (random schemas/tables)
and integration_tests/.../data_gen.py (seeded value generation with
special-value injection — reused here via tests/data_gen.py).

Run the tier: `pytest -m fuzz -q` (200 seeded cases + edge seeds).
"""
import random
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from compare import assert_rows_equal  # noqa: E402
from data_gen import gen_table  # noqa: E402
from spark_rapids_tpu import types as T  # noqa: E402
from spark_rapids_tpu.engine import TpuSession  # noqa: E402
from spark_rapids_tpu.plan.logical import (  # noqa: E402
    Window, col, functions as F, lit)

pytestmark = pytest.mark.fuzz

# the device-supported flat type surface (SUPPORTED_TYPES minus timestamp
# to keep value generation simple; timestamps are covered by the typed
# suites)
FUZZ_TYPES = [T.IntegerType, T.LongType, T.ShortType, T.DoubleType,
              T.FloatType, T.StringType, T.BooleanType, T.DateType]
KEYABLE = [T.IntegerType, T.LongType, T.StringType, T.DateType]
NUMERIC = [T.IntegerType, T.LongType, T.ShortType, T.DoubleType,
           T.FloatType]


def _random_schema(rng: random.Random):
    n_cols = rng.randint(2, 6)
    cols = {"k0": rng.choice(KEYABLE)}  # a keyable column always exists
    for i in range(1, n_cols):
        cols[f"c{i}"] = rng.choice(FUZZ_TYPES)
    return cols


def _cols_of(cols, types):
    return [name for name, t in cols.items() if t in types]


def _random_predicate(rng, name, dtype):
    c = col(name)
    if dtype is T.DateType:
        # date literals are strings (the engine rejects date-vs-int)
        pivot = rng.choice(["1995-06-17", "2001-01-01", "1970-01-01"])
        op = rng.choice(["lt", "ge", "ne", "null"])
        if op == "lt":
            return c < pivot
        if op == "ge":
            return c >= pivot
        if op == "ne":
            return c != pivot
        return c.is_null() if rng.random() < 0.5 else ~c.is_null()
    if dtype in NUMERIC:
        pivot = rng.choice([0, 1, -17, 1000])
        op = rng.choice(["lt", "ge", "ne", "null"])
        if op == "lt":
            return c < pivot
        if op == "ge":
            return c >= pivot
        if op == "ne":
            return c != pivot
        return c.is_null() if rng.random() < 0.5 else ~c.is_null()
    if dtype is T.StringType:
        return rng.choice([c.startswith("a"), c.contains("1"),
                           c.is_null(), c != ""])
    if dtype is T.BooleanType:
        return c if rng.random() < 0.5 else ~c
    return ~c.is_null()


def _random_projection(rng, df, cols):
    nums = _cols_of(cols, NUMERIC)
    strs = _cols_of(cols, [T.StringType])
    if nums and rng.random() < 0.7:
        a = col(rng.choice(nums))
        b = col(rng.choice(nums))
        expr = rng.choice([a + b, a - b, a * lit(2), -a])
    elif strs:
        s = col(rng.choice(strs))
        expr = rng.choice([F.upper(s), F.length(s), F.substring(s, 1, 3)])
    else:
        expr = lit(1)
    name = _fresh(rng, cols, "d")
    return df.with_column(name, expr), {**cols, name: None}


def _fresh(rng, cols, prefix):
    """A column name not already in the plan: duplicate output names are
    ambiguous (engines may resolve them differently), so the fuzzer never
    generates them."""
    while True:
        name = f"{prefix}{rng.randint(0, 9999)}"
        if name not in cols:
            return name


def _random_agg(rng, df, cols):
    keyable = _cols_of(cols, KEYABLE + [T.BooleanType])
    if not keyable:
        return df, cols
    keys = [n for n in keyable if rng.random() < 0.6][:2] or keyable[:1]
    nums = _cols_of(cols, NUMERIC)
    cnt = _fresh(rng, cols, "cnt")
    aggs = [F.count(lit(1)).alias(cnt)]
    out_cols = {k: cols[k] for k in keys}
    out_cols[cnt] = T.LongType
    for n in nums[:3]:
        fn = rng.choice([F.sum, F.min, F.max, F.avg])
        alias = _fresh(rng, out_cols, "a")
        aggs.append(fn(col(n)).alias(alias))
        out_cols[alias] = None
    return (df.group_by(*[col(k) for k in keys]).agg(*aggs), out_cols)


def _random_window(rng, df, cols):
    keys = _cols_of(cols, KEYABLE + [T.BooleanType])
    nums = _cols_of(cols, NUMERIC)
    if not keys or not nums:
        return df, cols
    part = col(rng.choice(keys))
    order = col(rng.choice(nums))
    w = Window.partition_by(part).order_by(order)
    # rank/dense_rank/sum are deterministic under ties (row_number is not)
    expr = rng.choice([F.rank().over(w), F.dense_rank().over(w),
                       F.sum(col(rng.choice(nums)))
                       .over(Window.partition_by(part))])
    name = _fresh(rng, cols, "w")
    return df.with_column(name, expr), {**cols, name: None}


def _random_join(rng, session, df, cols, seed):
    keyable = [n for n in _cols_of(cols, KEYABLE)]
    if not keyable:
        return df, cols
    key = rng.choice(keyable)
    ktype = cols[key]
    if rng.random() < 0.33:
        # USING join (shared column name), right included — exercises the
        # coalesced-key reorder and the build-side swap paths
        data, schema = gen_table(seed ^ 0x05ED, rng.randint(5, 80),
                                 **{key: ktype, "jv": T.LongType})
        dim = session.from_pydict(data, schema)
        how = rng.choice(["inner", "left", "right", "left_semi",
                          "left_anti"])
        joined = df.join(dim, on=key, how=how)
        if how in ("left_semi", "left_anti"):
            return joined, cols
        return joined, {**cols, "jv": T.LongType}
    # FRESH column names per join: stacking two joins that both emit a
    # literal "jk" produces a duplicate-name schema whose collect order
    # is ambiguous — the engines legitimately disagree there, so the
    # oracle comparison would be ill-defined (found by seed 130)
    jk = _fresh(rng, cols, "jk")
    jv = _fresh(rng, {**cols, jk: None}, "jv")
    data, schema = gen_table(seed ^ 0x5EED, rng.randint(5, 80),
                             **{jk: ktype, jv: T.LongType})
    dim = session.from_pydict(data, schema)
    how = rng.choice(["inner", "left", "right", "left_semi", "left_anti"])
    joined = df.join(dim, on=col(key) == col(jk), how=how)
    if how in ("left_semi", "left_anti"):
        return joined, cols
    return joined, {**cols, jk: ktype, jv: T.LongType}


def _build_query(session, seed: int):
    rng = random.Random(seed)
    schema_cols = _random_schema(rng)
    n = rng.choice([20, 100, 400])
    data, schema = gen_table(seed, n, **schema_cols)
    df = session.from_pydict(data, schema)
    cols = dict(schema_cols)
    n_ops = rng.randint(1, 4)
    for _ in range(n_ops):
        op = rng.choice(["filter", "project", "agg", "join", "sort",
                         "distinct", "union", "window"])
        if op == "filter":
            name = rng.choice(list(cols))
            if cols[name] is not None:
                df = df.filter(_random_predicate(rng, name, cols[name]))
        elif op == "project":
            df, cols = _random_projection(rng, df, cols)
        elif op == "agg":
            df, cols = _random_agg(rng, df, cols)
        elif op == "join":
            df, cols = _random_join(rng, session, df, cols, seed)
        elif op == "sort":
            name = rng.choice(list(cols))
            df = df.order_by(col(name).desc() if rng.random() < 0.5
                             else col(name))
        elif op == "distinct" and rng.random() < 0.5:
            df = df.distinct()
        elif op == "union":
            df = df.union(df)
        elif op == "window":
            df, cols = _random_window(rng, df, cols)
    return df


def _run(seed: int, conf: dict):
    session = TpuSession(conf)
    return _build_query(session, seed).collect()


N_CASES = 200


@pytest.mark.parametrize("seed", range(N_CASES),
                         ids=[f"seed{i}" for i in range(N_CASES)])
def test_fuzz_plan(seed):
    cpu = _run(seed, {"spark.rapids.sql.enabled": "false"})
    tpu = _run(seed, {"spark.rapids.sql.variableFloatAgg.enabled": "true"})
    try:
        assert_rows_equal(cpu, tpu, ignore_order=True, approx_float=True)
    except AssertionError as e:
        raise AssertionError(
            f"fuzz seed {seed} diverged (replay: pytest "
            f"'tests/test_fuzz.py::test_fuzz_plan[seed{seed}]')\n{e}"
        ) from e


@pytest.mark.parametrize("seed", range(5))
def test_fuzz_distributed_mesh(seed):
    """A smaller SPMD tier: the same random plans through the 8-device
    mesh planner (distributed agg/join/sort swap in where eligible)."""
    cpu = _run(seed + 1000, {"spark.rapids.sql.enabled": "false"})
    tpu = _run(seed + 1000, {
        "spark.rapids.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.sql.tpu.mesh.devices": "8",
        "spark.rapids.sql.tpu.mesh.inputChunkRows": "256",
        "spark.rapids.sql.reader.batchSizeRows": "128",
        "spark.sql.autoBroadcastJoinThreshold": "-1"})
    try:
        assert_rows_equal(cpu, tpu, ignore_order=True, approx_float=True)
    except AssertionError as e:
        raise AssertionError(
            f"fuzz seed {seed + 1000} diverged on the mesh path\n{e}"
        ) from e
