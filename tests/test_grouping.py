"""`F.grouping(col)` / `F.grouping_id()` over ROLLUP and CUBE
(plan/grouping.py): the device path against the CPU twin and against a
hand-written reference that never sees an Expand: one plain group-by per
grouping set, with the bits set by hand.  A NULL in a grouping column's DATA
must read 0 where a subtotal's NULL reads 1.  Then the places the functions
may stand: the aggregate's own outputs, a later `with_column`, a window's
partition keys, `order_by`, a filter; and the analysis error anywhere else.
"""
import itertools

import numpy as np
import pyarrow as pa
import pytest

from compare import assert_rows_equal
from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.plan.analysis import AnalysisError
from spark_rapids_tpu.plan.logical import Window, col, functions as F

DEVICE = {"spark.rapids.sql.test.enabled": "true",
          "spark.rapids.sql.variableFloatAgg.enabled": "true"}
HOST = {"spark.rapids.sql.enabled": "false"}
KEYS = ["a", "b", "c"]


def table(n=3000, seed=7):
    rng = np.random.RandomState(seed)
    return pa.table({
        # a NULL in the data of every grouping column
        "a": pa.array(rng.choice(["x", "y", "z", None], n).tolist()),
        "b": pa.array(rng.choice([1, 2, None], n).tolist(), pa.int64()),
        "c": pa.array(rng.choice(["p", "q", None], n).tolist()),
        "v": rng.randint(0, 1000, n).astype(np.int64),
    })


def grouping_sets(kind, keys):
    """(kept columns, grouping id) of every set, Spark's bit order: the
    first grouping column is the highest bit, set where it is NOT kept."""
    n = len(keys)
    if kind == "rollup":
        kept_sets = [keys[:g] for g in range(n, -1, -1)]
    else:
        kept_sets = [[k for k, keep in zip(keys, mask) if keep]
                     for mask in itertools.product([True, False], repeat=n)]
    return [(kept, sum(1 << (n - 1 - i)
                       for i, k in enumerate(keys) if k not in kept))
            for kept in kept_sets]


def by_hand(t, kind, keys):
    """A separate group-by per grouping set: (keys..., sum, grouping() of
    every key..., grouping_id)."""
    rows = t.to_pylist()
    out = []
    for kept, gid in grouping_sets(kind, keys):
        sums = {}
        for r in rows:
            k = tuple(r[c] for c in kept)
            sums[k] = sums.get(k, 0) + r["v"]
        for k, total in sums.items():
            named = dict(zip(kept, k))
            out.append(tuple(named.get(c) for c in keys) + (total,)
                       + tuple(0 if c in kept else 1 for c in keys)
                       + (gid,))
    return out


def rolled(session, t, kind, keys, *outputs):
    df = session.from_arrow(t)
    grouped = (df.rollup if kind == "rollup" else df.cube)(
        *[col(k) for k in keys])
    return grouped.agg(F.sum(col("v")).alias("sv"), *outputs)


@pytest.mark.parametrize("kind", ["rollup", "cube"])
@pytest.mark.parametrize("nkeys", [2, 3])
def test_grouping_in_the_aggregates_outputs(kind, nkeys):
    keys, t = KEYS[:nkeys], table()

    def build(session):
        return rolled(session, t, kind, keys,
                      *[F.grouping(k).alias("g_" + k) for k in keys],
                      F.grouping_id().alias("gid"))
    device = build(TpuSession(DEVICE))
    assert [f.name for f in device.schema] == (
        keys + ["sv"] + ["g_" + k for k in keys] + ["gid"])
    assert [f.dtype.name for f in device.schema][-nkeys - 1:] == (
        ["byte"] * nkeys + ["long"])          # Spark's types
    assert "Cpu" not in device.session.plan(device.plan).tree_string()
    got, twin = device.collect(), build(TpuSession(HOST)).collect()
    want = by_hand(t, kind, keys)
    assert len(want) == len(got) > 2 ** nkeys
    assert_rows_equal(want, got, approx_float=False)
    assert_rows_equal(twin, got, approx_float=False)
    # the data's NULL and the subtotal's NULL in one column, told apart
    first = [(r[0], r[nkeys + 1]) for r in got]
    assert (None, 0) in first and (None, 1) in first


@pytest.mark.parametrize("kind", ["rollup", "cube"])
@pytest.mark.parametrize("nkeys", [2, 3])
def test_grouping_after_the_aggregate(kind, nkeys):
    """with_column over the rolled-up frame, twice (the second one reaches
    the id through the first one's Project), then a filter and a sort that
    do not project: the id must not leak into the answer."""
    keys, t = KEYS[:nkeys], table()
    level = F.grouping(keys[0])
    for k in keys[1:]:
        level = level + F.grouping(k)

    def build(session):
        return (rolled(session, t, kind, keys)
                .with_column("level", level)
                .with_column("gid", F.grouping_id())
                .filter(F.grouping(keys[-1]) == 1)
                .order_by(F.grouping_id().desc(), col("sv"),
                          *[col(k) for k in keys]))
    device = build(TpuSession(DEVICE))
    assert [f.name for f in device.schema] == keys + ["sv", "level", "gid"]
    got, twin = device.collect(), build(TpuSession(HOST)).collect()
    want = [r[:nkeys + 1] + (sum(r[nkeys + 1:2 * nkeys + 1]), r[-1])
            for r in by_hand(t, kind, keys) if r[2 * nkeys] == 1]
    assert len(want) == len(got) > 1
    assert_rows_equal(want, got, approx_float=False)
    assert got == twin                      # the order too
    gids = [r[-1] for r in got]
    assert gids == sorted(gids, reverse=True) and len(set(gids)) > 1


@pytest.mark.parametrize("kind", ["rollup", "cube"])
def test_grouping_in_a_window_partition_and_its_order(kind):
    """Query 36's shape: the rank of a measure among the rows of one level
    under one parent."""
    keys, t = KEYS[:2], table()
    level = F.grouping("a") + F.grouping("b")

    def build(session):
        w = Window.partition_by(
            level, F.when(F.grouping("b") == 0, col("a"))
        ).order_by(col("sv").desc(), F.grouping_id())
        return (rolled(session, t, kind, keys)
                .select(col("a"), col("b"), col("sv"),
                        level.alias("level"),
                        F.rank().over(w).alias("r"))
                .order_by(col("level").desc(),
                          F.when(col("level") == 0, col("a")), col("r"),
                          col("a"), col("b")))
    device = build(TpuSession(DEVICE))
    assert "Cpu" not in device.session.plan(device.plan).tree_string()
    got, twin = device.collect(), build(TpuSession(HOST)).collect()
    assert got == twin
    # by hand: rank by numpy per (level, parent)
    hand = by_hand(t, kind, keys)
    parts = {}
    for a, b, sv, ga, gb, _gid in hand:
        parts.setdefault((ga + gb, a if gb == 0 else None), []).append(
            (a, b, sv, ga + gb))
    want = []
    for rows in parts.values():
        sums = np.array([r[2] for r in rows])
        for r in rows:
            want.append(r + (1 + int((sums > r[2]).sum()),))
    assert_rows_equal(want, got, approx_float=False)
    assert {r[3] for r in got} == {0, 1, 2}


def test_grouping_by_column_object_and_by_name():
    t = table(500)
    s = TpuSession(DEVICE)
    a = rolled(s, t, "rollup", ["a", "b"], F.grouping(col("b")).alias("g"))
    b = rolled(s, t, "rollup", ["a", "b"], F.grouping("b").alias("g"))
    assert a.collect() == b.collect()


@pytest.mark.parametrize("misuse", [
    lambda df: df.group_by(col("a")).agg(F.sum(col("v")).alias("sv"),
                                         F.grouping("a").alias("g")),
    lambda df: df.group_by(col("a")).agg(F.sum(col("v")).alias("sv"))
                 .order_by(F.grouping_id()),
    lambda df: df.select(F.grouping("a").alias("g")),
    lambda df: df.filter(F.grouping_id() == 0),
    # not a grouping column of the rollup
    lambda df: df.rollup(col("a")).agg(F.sum(col("v")).alias("sv"),
                                       F.grouping("b").alias("g")),
    # a join between the rollup and the use
    lambda df: df.rollup(col("a")).agg(F.sum(col("v")).alias("sv"))
                 .join(df.select(col("a").alias("a2")),
                       on=col("a") == col("a2"))
                 .with_column("g", F.grouping("a")),
], ids=["group_by_agg", "group_by_order", "select", "filter",
        "not_a_key", "past_a_join"])
def test_grouping_outside_a_rollup_is_an_analysis_error(misuse):
    df = TpuSession(DEVICE).from_arrow(table(100))
    with pytest.raises(AnalysisError, match="grouping"):
        misuse(df)
