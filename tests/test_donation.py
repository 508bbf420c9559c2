"""Buffer-donation safety tier (ISSUE 11).

A donated input buffer is DELETED by XLA after the dispatch, so the
whole correctness story is "never donate a batch anything else still
owns".  Coverage:

  * bit-for-bit parity donation ON vs OFF across every column dtype
    (the kill switch `spark.rapids.sql.tpu.donation.enabled=false` is
    the oracle), with donated-buffer counts proving the ON run donated;
  * stage retry / split-and-retry after an injected RetryOOM still works
    (a retry checkpoint pins the input, flipping later attempts to the
    copying executable);
  * a batch with two consumers is never donated: scan-cache re-serves
    (second query + self-join) and spillable registration both pin;
  * the dynamic duplicate-leaf veto (one Column projected twice);
  * donation through the exchange-bucketing fused program and the
    aggregate whole-stage absorption.

Runs in the `donation` ci.sh tier.
"""
from __future__ import annotations

import pyarrow as pa
import pytest

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.mem import donation
from spark_rapids_tpu.plan.logical import col, functions as F
from spark_rapids_tpu.utils import faults

from compare import assert_rows_equal
from data_gen import gen_table

pytestmark = pytest.mark.donation

# donation needs the memory-scan cache OFF to fire on in-memory scans
# (cached batches are pinned — re-served to later queries by design)
NO_CACHE = {"spark.rapids.sql.tpu.memoryScanCache.enabled": "false"}
DONATION_OFF = {"spark.rapids.sql.tpu.donation.enabled": "false"}


def _run(build_query, conf=None):
    s = TpuSession(dict(conf or {}))
    return build_query(s).collect(), s


def _donation_on_vs_off(build_query, conf=None, expect_donated=True, **kw):
    base = dict(NO_CACHE)
    base.update(conf or {})
    off = dict(base)
    off.update(DONATION_OFF)
    before = donation.stats()["donated_buffers"]
    on_rows, s_on = _run(build_query, base)
    donated = donation.stats()["donated_buffers"] - before
    off_rows, _ = _run(build_query, off)
    assert_rows_equal(off_rows, on_rows, **kw)
    if expect_donated:
        assert donated > 0, "donation never fired on the ON run"
    return on_rows, s_on, donated


ALL_DTYPES = [T.IntegerType, T.LongType, T.ShortType, T.ByteType,
              T.DoubleType, T.FloatType, T.BooleanType, T.StringType,
              T.DateType, T.TimestampType]


@pytest.mark.parametrize("dtype", ALL_DTYPES, ids=lambda d: d.name)
def test_donation_bitforbit_every_dtype(dtype):
    """Nullable columns of every supported dtype flow through donated
    fused-stage dispatches bit-for-bit vs the kill switch."""
    data, schema = gen_table(seed=17, n=300, sel=(T.LongType, False),
                             v=dtype)

    def q(s):
        df = s.from_pydict(data, schema)
        return (df.filter(col("sel") % 3 != 0)
                .select(col("v"), (col("sel") * 2).alias("s2"))
                .filter(col("s2") % 5 != 1))

    _donation_on_vs_off(q, ignore_order=False, approx_float=False)


def test_donated_counts_surface_in_metrics():
    def q(s):
        df = s.from_pydict({"a": list(range(4000))})
        return df.filter(col("a") % 2 == 0).select((col("a") + 1).alias("x"))
    _rows, s, donated = _donation_on_vs_off(q, ignore_order=False)
    agg = s.last_execution.aggregate()
    assert agg.get("numDonatedBuffers", 0) > 0, agg
    assert donated >= agg["numDonatedBuffers"]


def test_kill_switch_zeroes_donation():
    def q(s):
        df = s.from_pydict({"a": list(range(2000))})
        return df.filter(col("a") > 5).select((col("a") * 3).alias("x"))
    conf = dict(NO_CACHE)
    conf.update(DONATION_OFF)
    before = donation.stats()["donated_buffers"]
    _run(q, conf)
    assert donation.stats()["donated_buffers"] == before


# --------------------------------------------------------------------------
# retry safety: checkpointed inputs are excluded from donation
# --------------------------------------------------------------------------

def _fused_query(extra=None):
    faults.INJECTOR.reset()
    conf = dict(NO_CACHE)
    conf.update(extra or {})
    s = TpuSession(conf)
    n = 400
    df = s.from_pydict({"a": list(range(n)),
                        "b": [float(i % 13) for i in range(n)]})
    out = (df.filter(col("a") % 3 != 0)
           .select((col("a") * 2).alias("x"), col("b"))
           .filter(col("b") < 11.0)
           .collect())
    return sorted(out), s


def test_retry_after_oom_with_donation_on():
    """An injected RetryOOM at every reserve site: the retry ladder
    (spill-retry, split-and-retry, de-fuse) must still produce identical
    results with donation enabled — the first failure's checkpoint pins
    the batch, so re-invocations never see a donated input."""
    baseline, _ = _fused_query()
    n_ops = faults.INJECTOR.oom_ops
    assert "wholeStage" in dict(faults.INJECTOR.site_counts)
    for ordinal in range(1, n_ops + 1):
        out, _ = _fused_query({"spark.rapids.tpu.test.injectOom":
                               str(ordinal)})
        assert out == baseline, f"ordinal {ordinal} changed the result"


def test_split_retry_with_donation_on():
    baseline, _ = _fused_query()
    out, s = _fused_query({
        "spark.rapids.tpu.test.injectOom": "1x3",
        "spark.rapids.memory.tpu.retry.maxRetries": "1"})
    assert out == baseline
    agg = s.last_execution.aggregate()
    assert sum(v for k, v in agg.items() if k.endswith("Retries")) >= 1


def test_checkpoint_pins_batch():
    """Unit: registering a batch as a spillable buffer (what a retry
    checkpoint does) pins it against donation."""
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.types import Schema, StructField
    s = TpuSession(NO_CACHE)
    schema = Schema([StructField("a", T.LongType)])
    batch = ColumnarBatch.from_pydict({"a": [1, 2, 3]}, schema)
    assert donation.donatable(batch)
    s.runtime.device_store.add_batch(batch, site="checkpoint")
    assert donation.is_pinned(batch)
    assert not donation.donatable(batch)


# --------------------------------------------------------------------------
# multi-consumer batches are never donated
# --------------------------------------------------------------------------

def test_cached_scan_batches_never_donated():
    """With the memory-scan cache ON, a second query re-serves the SAME
    batch objects — they are pinned at creation, so both queries answer
    identically and nothing is donated."""
    s = TpuSession()  # cache on (default)
    df = s.from_pydict({"a": list(range(3000))})
    q = df.filter(col("a") % 2 == 0).select((col("a") + 1).alias("x"))
    before = donation.stats()["donated_buffers"]
    r1 = q.collect()
    r2 = q.collect()
    assert r1 == r2
    assert donation.stats()["donated_buffers"] == before


def test_self_join_double_consumer():
    """Both sides of a self-join consume the same cached scan batches;
    results must match the donation-off run exactly (nothing donated
    from the shared scan)."""
    def q(s):
        d = s.from_pydict({"k": [i % 7 for i in range(200)],
                           "v": list(range(200))})
        left = d.filter(col("v") >= 0)
        right = d.filter(col("v") % 2 == 0)
        return left.join(right, on="k")
    # cache ON here: the shared table is the double-consumer shape
    on_rows, _ = _run(q, {})
    off_rows, _ = _run(q, DONATION_OFF)
    assert sorted(on_rows) == sorted(off_rows)


def test_duplicate_leaf_veto():
    """A batch whose leaf list repeats one array (a Column reused in two
    slots) must refuse donation — one buffer cannot be donated twice."""
    from spark_rapids_tpu.columnar import Column, ColumnarBatch
    from spark_rapids_tpu.types import Schema, StructField
    c = Column(jnp.arange(8, dtype=jnp.int64), jnp.ones(8, jnp.bool_),
               T.LongType)
    schema = Schema([StructField("a", T.LongType),
                     StructField("b", T.LongType)])
    batch = ColumnarBatch([c, c], jnp.ones(8, jnp.bool_), schema)
    assert not donation.donatable(batch)
    c2 = Column(jnp.arange(8, dtype=jnp.int64), jnp.ones(8, jnp.bool_),
                T.LongType)
    ok = ColumnarBatch([c, c2], jnp.arange(8, dtype=jnp.int32) < 8, schema)
    # distinct arrays everywhere -> donatable (sel is its own array)
    assert donation.donatable(ok)


# --------------------------------------------------------------------------
# the other fused dispatch sites
# --------------------------------------------------------------------------

def test_exchange_bucketing_donation():
    def q(s):
        df = s.from_pydict({"k": [i % 5 for i in range(500)],
                            "v": [float(i) for i in range(500)]})
        return (df.filter(col("v") >= 0)
                .select(col("k"), (col("v") * 2).alias("w"))
                .repartition(4, col("k")))
    _donation_on_vs_off(q)


def test_agg_absorption_donation():
    def q(s):
        df = s.from_pydict({"k": [i % 5 for i in range(500)],
                            "v": [float(i % 23) for i in range(500)]})
        return (df.filter(col("v") < 21)
                .select(col("k"), (col("v") + 1.0).alias("w"))
                .group_by(col("k"))
                .agg(F.sum(col("w")).alias("sw"), F.count(col("w"))
                     .alias("c"))
                .order_by(col("k")))
    _donation_on_vs_off(q, ignore_order=False, approx_float=True)


# --------------------------------------------------------------------------
# ISSUE 12: the consumed() registry + the de-fuse ladder donation guard
# --------------------------------------------------------------------------

def test_consumed_registry_tracks_donated_batches():
    """record_donated_dispatch over a batch OBJECT marks it consumed, and
    a consumed batch can never be donated again (its leaves are aliased
    into a compiled program's outputs — they no longer exist)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.mem import donation
    donation.reset_for_tests()
    batch = ColumnarBatch.from_arrow(
        pa.table({"a": pa.array([1.0, 2.0, 3.0, 4.0])}))
    assert not donation.consumed(batch)
    assert donation.donatable(batch)
    n = donation.record_donated_dispatch(batch)
    assert n >= 1
    assert donation.consumed(batch)
    assert not donation.donatable(batch), \
        "a consumed batch must never be donated a second time"
    # an int count (the aggregate whole-stage path) marks nothing
    other = ColumnarBatch.from_arrow(pa.table({"a": pa.array([1.0])}))
    donation.record_donated_dispatch(3)
    assert not donation.consumed(other)
    assert donation.stats()["live_consumed"] >= 1
    del batch
    import gc
    gc.collect()
    assert donation.stats()["live_consumed"] == 0, \
        "the consumed registry must not keep dead batches alive"


def test_retry_aborts_instead_of_rereading_donated_input():
    """TPU008 regression (the de-fuse ladder's error path): an attempt
    that fails AFTER donating its input must make the retry ladder
    terminal — re-dispatching, splitting, or CPU-falling-back on the
    batch would read freed device buffers.  with_retry must raise
    RetryExhausted after ONE attempt, without retrying or splitting."""
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.mem import donation
    from spark_rapids_tpu.mem.retry import (RetryExhausted, RetryOOM,
                                            with_retry)
    donation.reset_for_tests()
    batch = ColumnarBatch.from_arrow(
        pa.table({"a": pa.array([1.0, 2.0, 3.0, 4.0])}))
    calls = []

    def attempt(b):
        calls.append(b)
        # the dispatch donated the input's buffers, then failed
        donation.record_donated_dispatch(b)
        raise RetryOOM("device OOM mid-dispatch", nbytes=128)

    splits = []

    def split(b):
        splits.append(b)
        return None

    with pytest.raises(RetryExhausted, match="donat"):
        with_retry(attempt, [batch], split=split, max_retries=3)
    assert len(calls) == 1, \
        "a donated input must not be re-dispatched by the retry loop"
    assert splits == [], \
        "a donated input must not be handed to the splitter"


def test_retry_still_retries_undonated_inputs():
    """Control for the guard above: the same failure WITHOUT a donation
    retries normally."""
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.mem import donation
    from spark_rapids_tpu.mem.retry import RetryOOM, with_retry
    donation.reset_for_tests()
    batch = ColumnarBatch.from_arrow(
        pa.table({"a": pa.array([1.0, 2.0])}))
    calls = []

    def attempt(b):
        calls.append(b)
        if len(calls) == 1:
            raise RetryOOM("transient", nbytes=64)
        return b

    out = with_retry(attempt, [batch], max_retries=2)
    assert len(calls) == 2 and out == [batch]
