"""Planner-integrated SPMD execution (plan/transitions.py distribute pass).

A session conf (spark.rapids.sql.tpu.mesh.devices=8) must make PLANNED
DataFrame queries — not hand-built execs — run aggregate/join/sort subtrees
over the virtual 8-device mesh and match the CPU oracle (reference analogue:
every exchange executes through the shuffle manager,
rapids/GpuShuffleExchangeExec.scala:60-155)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from compare import assert_rows_equal, assert_tpu_and_cpu_are_equal  # noqa: E402
from data_gen import gen_df  # noqa: E402
from spark_rapids_tpu import types as T  # noqa: E402
from spark_rapids_tpu.engine import TpuSession  # noqa: E402
from spark_rapids_tpu.plan.logical import col, functions as f, lit  # noqa: E402

MESH_CONF = {"spark.rapids.sql.tpu.mesh.devices": "8"}



def _plan_str(session, df):
    node = session.plan(df.plan)
    out = []

    def walk(n, d=0):
        out.append("  " * d + n.describe())
        for c in n.children:
            walk(c, d + 1)
    walk(node)
    return "\n".join(out)


class TestDistributedPlanning:
    def test_grouped_agg_plans_distributed(self):
        s = TpuSession(MESH_CONF)
        df = gen_df(s, seed=1, n=100, k=T.IntegerType, v=T.LongType)
        q = df.group_by("k").agg(f.sum(col("v")).alias("s"))
        assert "TpuDistributedAggregateExec" in _plan_str(s, q)

    def test_global_agg_stays_single_chip(self):
        s = TpuSession(MESH_CONF)
        df = gen_df(s, seed=1, n=100, v=T.LongType)
        q = df.agg(f.sum(col("v")).alias("s"))
        assert "TpuDistributedAggregateExec" not in _plan_str(s, q)

    def test_join_plans_distributed(self):
        s = TpuSession({**MESH_CONF,
                        "spark.sql.autoBroadcastJoinThreshold": "-1"})
        a = gen_df(s, seed=2, n=100, k=T.IntegerType, v=T.LongType)
        b = gen_df(s, seed=3, n=100, k=T.IntegerType, w=T.LongType)
        q = a.join(b, on="k")
        assert "TpuDistributedJoinExec" in _plan_str(s, q)

    def test_sort_plans_distributed(self):
        s = TpuSession(MESH_CONF)
        df = gen_df(s, seed=4, n=100, v=T.LongType)
        q = df.order_by("v")
        assert "TpuDistributedSortExec" in _plan_str(s, q)

    def test_no_mesh_no_distribution(self):
        s = TpuSession()
        df = gen_df(s, seed=1, n=100, k=T.IntegerType, v=T.LongType)
        q = df.group_by("k").agg(f.sum(col("v")).alias("s"))
        assert "Distributed" not in _plan_str(s, q)

    def test_mesh_larger_than_devices_is_an_error(self):
        # a mesh that was asked for never quietly runs on one chip
        s = TpuSession({"spark.rapids.sql.tpu.mesh.devices": "64"})
        df = gen_df(s, seed=1, n=100, k=T.IntegerType, v=T.LongType)
        q = df.group_by("k").agg(f.sum(col("v")).alias("s"))
        with pytest.raises(RuntimeError, match="mesh.devices=64"):
            _plan_str(s, q)

    def test_non_pow2_mesh_rejected(self):
        s = TpuSession({"spark.rapids.sql.tpu.mesh.devices": "6"})
        df = gen_df(s, seed=1, n=100, k=T.IntegerType, v=T.LongType)
        q = df.group_by("k").agg(f.sum(col("v")).alias("s"))
        with pytest.raises(ValueError, match="power of two"):
            _plan_str(s, q)


class TestDistributedExecution:
    """CPU-vs-mesh oracle on planned queries (virtual 8-device CPU mesh)."""

    def test_grouped_agg(self):
        def q(s):
            df = gen_df(s, seed=11, n=3000, k=T.IntegerType, v=T.LongType)
            return df.group_by("k").agg(
                f.sum(col("v")).alias("sv"),
                f.count(lit(1)).alias("c"),
                f.min(col("v")).alias("mn"),
                f.max(col("v")).alias("mx"))
        assert_tpu_and_cpu_are_equal(q, conf=MESH_CONF)

    def test_grouped_agg_string_keys(self):
        def q(s):
            df = gen_df(s, seed=12, n=1500, k=T.StringType, v=T.DoubleType)
            return df.group_by("k").agg(f.count(lit(1)).alias("c"))
        assert_tpu_and_cpu_are_equal(q, conf=MESH_CONF)

    def test_agg_with_filter_project_below(self):
        def q(s):
            df = gen_df(s, seed=13, n=4000, k=T.IntegerType, v=T.LongType)
            return (df.filter(col("v") % 3 == 0)
                    .select(col("k"), (col("v") * 2).alias("v2"))
                    .group_by("k").agg(f.sum(col("v2")).alias("s")))
        assert_tpu_and_cpu_are_equal(q, conf=MESH_CONF)

    @pytest.mark.parametrize("how", ["inner", "left", "left_semi",
                                     "left_anti"])
    def test_join_types(self, how):
        def q(s):
            a = gen_df(s, seed=14, n=800, k=T.IntegerType, v=T.LongType)
            b = gen_df(s, seed=15, n=600, k=T.IntegerType, w=T.LongType)
            return a.join(b, on="k", how=how)
        assert_tpu_and_cpu_are_equal(
            q, conf={**MESH_CONF,
                     "spark.sql.autoBroadcastJoinThreshold": "-1"})

    def test_join_then_agg_distributed(self):
        def q(s):
            a = gen_df(s, seed=16, n=1000, k=T.IntegerType, v=T.LongType)
            b = gen_df(s, seed=17, n=500, k=T.IntegerType, w=T.LongType)
            return (a.join(b, on="k")
                    .group_by("k").agg(f.sum(col("w")).alias("sw")))
        assert_tpu_and_cpu_are_equal(
            q, conf={**MESH_CONF,
                     "spark.sql.autoBroadcastJoinThreshold": "-1"})

    def test_global_sort(self):
        def q(s):
            df = gen_df(s, seed=18, n=3000, a=T.IntegerType, b=T.DoubleType)
            return df.order_by("a", "b")
        cpu, tpu = __import__("compare").run_both(q, conf=MESH_CONF)
        assert_rows_equal(cpu, tpu, ignore_order=False, approx_float=True)

    def test_sort_desc_with_nulls(self):
        def q(s):
            df = gen_df(s, seed=19, n=2000, a=T.IntegerType, b=T.StringType)
            return df.order_by(col("a").desc(), "b")
        cpu, tpu = __import__("compare").run_both(q, conf=MESH_CONF)
        assert_rows_equal(cpu, tpu, ignore_order=False, approx_float=True)

    def test_distinct_on_device_and_mesh(self):
        def q(s):
            df = gen_df(s, seed=20, n=2000, k=T.IntegerType,
                        m=T.StringType)
            return df.distinct()
        assert_tpu_and_cpu_are_equal(q, conf=MESH_CONF)

    def test_tpch_q1_on_mesh(self):
        """VERDICT round-3 'done' criterion: TPC-H Q1 through TpuSession on
        the 8-device mesh matches the CPU oracle."""
        from benchmarks.tpch import QUERIES, load_tables

        def run(conf):
            s = TpuSession(conf)
            return QUERIES[1](load_tables(s, sf=0.002)).collect()
        cpu = run({"spark.rapids.sql.enabled": "false"})
        tpu = run(dict(MESH_CONF))
        assert_rows_equal(cpu, tpu, ignore_order=True, approx_float=True)

    def test_tpch_q3_on_mesh(self):
        """Joins + aggregate + sort through the mesh planner."""
        from benchmarks.tpch import QUERIES, load_tables

        def run(conf):
            s = TpuSession(conf)
            return QUERIES[3](load_tables(s, sf=0.002)).collect()
        cpu = run({"spark.rapids.sql.enabled": "false"})
        tpu = run({**MESH_CONF,
                   "spark.sql.autoBroadcastJoinThreshold": "-1"})
        assert_rows_equal(cpu, tpu, ignore_order=True, approx_float=True)


class TestShuffledHashJoin:
    """Single-chip partitioned join: exchange insertion bounds the build
    side per partition (VERDICT item 3)."""

    CONF = {"spark.rapids.sql.tpu.join.partitioned.threshold": "0",
            "spark.sql.autoBroadcastJoinThreshold": "-1",
            # small reader batches: the right side spans multiple batches,
            # so the whole-build path would need one giant batch
            "spark.rapids.sql.reader.batchSizeRows": "256"}

    def test_plans_shuffled_join(self):
        s = TpuSession(self.CONF)
        a = gen_df(s, seed=30, n=500, k=T.IntegerType, v=T.LongType)
        b = gen_df(s, seed=31, n=500, k=T.IntegerType, w=T.LongType)
        txt = _plan_str(s, a.join(b, on="k"))
        assert "TpuShuffledHashJoinExec" in txt
        assert txt.count("TpuShuffleExchangeExec") == 2

    @pytest.mark.parametrize("how", ["inner", "left", "left_semi",
                                     "left_anti"])
    def test_right_side_exceeds_one_batch(self, how):
        def q(s):
            a = gen_df(s, seed=32, n=1500, k=T.IntegerType, v=T.LongType)
            b = gen_df(s, seed=33, n=2000, k=T.IntegerType, w=T.LongType)
            return a.join(b, on="k", how=how)
        assert_tpu_and_cpu_are_equal(q, conf=self.CONF)

    def test_skewed_keys_and_empty_partitions(self):
        def q(s):
            import random
            rng = random.Random(34)
            # few distinct keys: most partitions empty, some heavy
            a = s.from_pydict(
                {"k": [rng.choice([1, 2, 3]) for _ in range(1000)],
                 "v": list(range(1000))})
            b = s.from_pydict(
                {"k": [rng.choice([2, 3, 4]) for _ in range(1000)],
                 "w": list(range(1000))})
            return a.join(b, on="k")
        assert_tpu_and_cpu_are_equal(q, conf=self.CONF)

    def test_join_condition_through_exchanges(self):
        def q(s):
            a = gen_df(s, seed=36, n=800, k=T.IntegerType, v=T.LongType)
            b = gen_df(s, seed=37, n=800, k=T.IntegerType, w=T.LongType)
            return a.join(b, on=(a["k"] == b["k"]) & (col("v") < col("w")),
                          how="inner")
        assert_tpu_and_cpu_are_equal(q, conf=self.CONF)
