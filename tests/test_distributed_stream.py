"""Streaming SPMD input staging (VERDICT r3 item 4).

Distributed aggregate/join must consume an input LARGER than one staged
batch without a single host-side concat: small reader batches + a small
`spark.rapids.sql.tpu.mesh.inputChunkRows` force multiple chunks through
the mesh — aggregates merge a mesh-resident partial state per chunk,
joins stream probe chunks against a resident build side — and results
must match the CPU oracle.  Reference analogue: partial/final agg pairs
and shuffled joins stream batches through the shuffle, never holding a
whole table (rapids/aggregate.scala Partial/Final +
GpuShuffledHashJoinExec.scala:83-87).
"""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from compare import assert_tpu_and_cpu_are_equal  # noqa: E402
from data_gen import gen_df  # noqa: E402
from spark_rapids_tpu import types as T  # noqa: E402
from spark_rapids_tpu.engine import TpuSession  # noqa: E402
from spark_rapids_tpu.plan.logical import col, functions as f  # noqa: E402

# many reader batches (512-row scans) + 1024-row mesh chunks: a 6000-row
# input streams as ~6 chunks of 2 batches each
STREAM_CONF = {
    "spark.rapids.sql.tpu.mesh.devices": "8",
    "spark.rapids.sql.tpu.mesh.inputChunkRows": "1024",
    "spark.rapids.sql.reader.batchSizeRows": "512",
    "spark.rapids.sql.variableFloatAgg.enabled": "true",
}



def test_streaming_agg_multi_chunk_matches_oracle():
    def q(s):
        df = gen_df(s, seed=11, n=6000, k=T.IntegerType, v=T.LongType,
                    x=T.DoubleType)
        return (df.group_by("k")
                .agg(f.sum(col("v")).alias("sv"),
                     f.count(col("v")).alias("cv"),
                     f.avg(col("x")).alias("ax"),
                     f.min(col("v")).alias("mv"),
                     f.max(col("x")).alias("mx")))
    assert_tpu_and_cpu_are_equal(q, conf=STREAM_CONF)


def test_streaming_agg_string_keys():
    def q(s):
        df = gen_df(s, seed=12, n=4000, k=T.StringType, v=T.LongType)
        return df.group_by("k").agg(f.sum(col("v")).alias("sv"),
                                    f.count(col("v")).alias("c"))
    assert_tpu_and_cpu_are_equal(q, conf=STREAM_CONF)


def test_streaming_agg_many_groups():
    """Group count near the row count: the state cannot compact much, so
    the growing-capacity + shrink path is exercised."""
    def q(s):
        df = gen_df(s, seed=13, n=3000, k=T.LongType, v=T.DoubleType)
        return df.group_by("k").agg(f.sum(col("v")).alias("sv"))
    assert_tpu_and_cpu_are_equal(q, conf=STREAM_CONF)


def test_streaming_join_multi_chunk_matches_oracle():
    conf = {**STREAM_CONF, "spark.sql.autoBroadcastJoinThreshold": "-1"}

    def q(s):
        a = gen_df(s, seed=14, n=5000, k=T.IntegerType, v=T.LongType)
        b = gen_df(s, seed=15, n=600, k=T.IntegerType, w=T.DoubleType)
        return a.join(b, on="k")
    assert_tpu_and_cpu_are_equal(q, conf=conf)


def test_streaming_left_join_and_semi():
    conf = {**STREAM_CONF, "spark.sql.autoBroadcastJoinThreshold": "-1"}

    def left(s):
        a = gen_df(s, seed=16, n=4000, k=T.IntegerType, v=T.LongType)
        b = gen_df(s, seed=17, n=300, k=T.IntegerType, w=T.DoubleType)
        return a.join(b, on="k", how="left")

    def semi(s):
        a = gen_df(s, seed=18, n=4000, k=T.IntegerType, v=T.LongType)
        b = gen_df(s, seed=19, n=300, k=T.IntegerType, w=T.DoubleType)
        return a.join(b, on="k", how="left_semi")
    assert_tpu_and_cpu_are_equal(left, conf=conf)
    assert_tpu_and_cpu_are_equal(semi, conf=conf)


def test_streaming_agg_then_join_query():
    """Composed query: distributed agg feeding a distributed join, both
    streaming."""
    conf = {**STREAM_CONF, "spark.sql.autoBroadcastJoinThreshold": "-1"}

    def q(s):
        fact = gen_df(s, seed=20, n=5000, k=T.IntegerType, v=T.DoubleType)
        dim = gen_df(s, seed=21, n=400, k=T.IntegerType, w=T.LongType)
        pre = dim.group_by("k").agg(f.sum(col("w")).alias("tw"))
        return (fact.join(pre, on="k")
                .group_by("k")
                .agg(f.sum(col("v")).alias("sv"),
                     f.max(col("tw")).alias("mw")))
    assert_tpu_and_cpu_are_equal(q, conf=conf)


def test_streaming_empty_input():
    def q(s):
        df = gen_df(s, seed=22, n=100, k=T.IntegerType, v=T.LongType)
        return (df.filter(col("v") < col("v"))  # empty
                .group_by("k").agg(f.sum(col("v")).alias("sv")))
    assert_tpu_and_cpu_are_equal(q, conf=STREAM_CONF)


def test_one_chunk_path_unchanged():
    """Input smaller than one chunk: the streaming driver degenerates to
    the one-shot path (single partial + finalize)."""
    def q(s):
        df = gen_df(s, seed=23, n=500, k=T.IntegerType, v=T.LongType)
        return df.group_by("k").agg(f.sum(col("v")).alias("sv"))
    assert_tpu_and_cpu_are_equal(
        q, conf={**STREAM_CONF,
                 "spark.rapids.sql.reader.batchSizeRows": "100000",
                 "spark.rapids.sql.tpu.mesh.inputChunkRows": "1048576"})


@pytest.mark.slow
def test_streaming_agg_large_input_slow_tier():
    """Slow tier: input far larger than one chunk capacity (200k rows in
    ~12 chunks) with a mixed group cardinality, plus a streamed join on
    top — the 'input larger than one batch capacity without a host-side
    concat' criterion."""
    conf = {
        "spark.rapids.sql.tpu.mesh.devices": "8",
        "spark.rapids.sql.tpu.mesh.inputChunkRows": "16384",
        "spark.rapids.sql.reader.batchSizeRows": "8192",
        "spark.rapids.sql.variableFloatAgg.enabled": "true",
        "spark.sql.autoBroadcastJoinThreshold": "-1",
    }

    def q(s):
        fact = gen_df(s, seed=31, n=200_000, k=T.IntegerType,
                      v=T.DoubleType, g=T.LongType)
        dim = gen_df(s, seed=32, n=2000, k=T.IntegerType, w=T.LongType)
        return (fact.join(dim, on="k")
                .group_by("k")
                .agg(f.sum(col("v")).alias("sv"),
                     f.count(col("g")).alias("cg")))
    assert_tpu_and_cpu_are_equal(q, conf=conf)
