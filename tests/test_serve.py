"""Serving tier (ISSUE 10): concurrent scheduler, admission control,
per-query budgets, and the parameterized plan cache.

Coverage:
  * plan-cache normalization: literal variants share one key, structural
    / dtype / conf changes do not; lifted parameters keep scan pushdown;
  * bit-for-bit: submitted queries (plan cache ON, parameters threaded)
    equal their blocking collect() runs across literal variants — and a
    variant re-submission compiles ZERO new kernels/stages;
  * scheduler: priority pop order + admission-budget skipping (unit),
    queue-capacity rejection with a deterministically-blocked worker,
    N queries racing to completion;
  * fault injection under concurrency: injectOom sweeps while queries
    race, every result bit-for-bit vs its serial fault-free run;
  * per-query budgets: an over-budget query spills ITSELF (ledger spill
    records' owner never crosses the stamping query's trace id) and
    still answers correctly through the retry ladder;
  * semaphoreWaitTime lands on the ACQUIRING query's metrics, not a
    global; concurrent queries' journals stay un-interleaved;
  * compile-cache satellite: re-pointable path + test reset hook.
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.plan.logical import col, functions as F, lit
from spark_rapids_tpu.serve.plan_cache import (PlanCache, extract_parameters,
                                               plan_cache_key)
from spark_rapids_tpu.serve.scheduler import AdmissionRejected
from spark_rapids_tpu.utils import kernel_cache as KC

pytestmark = pytest.mark.serve

N_ROWS = 40_000


def _table():
    rng = np.random.RandomState(7)
    return pa.table({
        "a": rng.uniform(0.0, 100.0, N_ROWS),
        "b": rng.randint(0, 50, N_ROWS).astype(np.int64),
        "c": rng.uniform(-1.0, 1.0, N_ROWS),
    })


_TABLE = _table()


def _session(extra=None):
    conf = {"spark.rapids.sql.variableFloatAgg.enabled": "true"}
    conf.update(extra or {})
    return TpuSession(conf)


def _q_agg(df, cut, k, scale):
    """q1-shaped: filter with literal bounds -> projected arithmetic with
    a literal -> grouped agg -> sort."""
    return (df.filter((col("a") > cut) & (col("b") < k))
            .select((col("a") * lit(scale)).alias("x"), col("b"))
            .group_by(col("b"))
            .agg(F.sum(col("x")).alias("sx"), F.count(lit(1)).alias("n"))
            .order_by("b"))


def _q_rowlocal(df, lo, hi):
    """Pure row-local (no aggregate): exercises the TpuWholeStageExec /
    RowLocalExec parameter-threaded dispatch paths."""
    return (df.filter((col("a") >= lo) & (col("a") <= hi))
            .select((col("a") + lit(1.5)).alias("x"),
                    (col("c") * lit(-2.0)).alias("y"), col("b")))


# --------------------------------------------------------------------------
# plan cache: normalization + keys
# --------------------------------------------------------------------------

def test_extract_parameters_lifts_literals():
    s = _session()
    df = _q_agg(s.from_arrow(_TABLE), 10.0, 40, 2.0)
    normalized, values = extract_parameters(df.plan)
    # cut, k, scale are lifted; count(lit(1)) (inside the agg) is NOT
    assert 10.0 in values and 40 in values and 2.0 in values
    assert 1 not in values


def test_literal_variants_share_a_key():
    s = _session()
    df1 = _q_agg(s.from_arrow(_TABLE), 10.0, 40, 2.0)
    df2 = _q_agg(s.from_arrow(_TABLE), 55.0, 20, 7.0)
    n1, v1 = extract_parameters(df1.plan)
    n2, v2 = extract_parameters(df2.plan)
    assert v1 != v2
    assert plan_cache_key(n1, s.conf) == plan_cache_key(n2, s.conf)


def test_key_invalidation_structure_dtype_conf():
    s = _session()
    df = s.from_arrow(_TABLE)
    base = plan_cache_key(
        extract_parameters(_q_agg(df, 10.0, 40, 2.0).plan)[0], s.conf)
    # a different plan SHAPE
    other = plan_cache_key(
        extract_parameters(_q_rowlocal(df, 1.0, 2.0).plan)[0], s.conf)
    assert other != base
    # a literal whose inferred dtype changes (int -> long)
    long_lit = plan_cache_key(
        extract_parameters(_q_agg(df, 10.0, 2 ** 40, 2.0).plan)[0], s.conf)
    assert long_lit != base
    # a conf change
    s2 = _session({"spark.rapids.sql.tpu.fusion.maxOpsPerStage": "8"})
    conf_changed = plan_cache_key(
        extract_parameters(_q_agg(df, 10.0, 40, 2.0).plan)[0], s2.conf)
    assert conf_changed != base


def test_plan_cache_lru_and_stats():
    s = _session()
    df = s.from_arrow(_TABLE)
    cache = PlanCache(max_entries=1)
    _n, _v, hit = cache.lookup(_q_agg(df, 1.0, 2, 3.0).plan, s.conf)
    assert not hit
    _n, _v, hit = cache.lookup(_q_agg(df, 9.0, 8, 7.0).plan, s.conf)
    assert hit
    # a second SHAPE evicts the first (max_entries=1)
    cache.lookup(_q_rowlocal(df, 0.0, 1.0).plan, s.conf)
    _n, _v, hit = cache.lookup(_q_agg(df, 1.0, 2, 3.0).plan, s.conf)
    assert not hit
    st = cache.stats()
    assert st["entries"] == 1 and st["hits"] == 1 and st["misses"] == 3
    assert st["params_lifted"] > 0


def test_parameterized_predicates_still_push_down():
    """Lifted literals keep concrete values inline, so footer-statistic
    pushdown still extracts usable (col, op, value) predicates."""
    from spark_rapids_tpu.plan.pushdown import extract_predicates
    s = _session()
    df = s.from_arrow(_TABLE).filter((col("a") > 12.5) & (col("b") < 9))
    normalized, values = extract_parameters(df.plan)
    assert values == [12.5, 9]
    preds = extract_predicates(normalized.condition)
    assert ("a", "GreaterThan", 12.5) in preds
    assert ("b", "LessThan", 9) in preds


# --------------------------------------------------------------------------
# submitted execution: correctness + compile reuse
# --------------------------------------------------------------------------

def test_submit_matches_collect_across_variants():
    s = _session()
    try:
        df = s.from_arrow(_TABLE)
        variants = [(10.0, 40, 2.0), (55.0, 20, 7.0)]
        for i, (cut, k, scale) in enumerate(variants):
            expected = _q_agg(df, cut, k, scale).to_arrow()
            # like with like: 40 and 20 groups are both clean for the
            # bucket update, under collect()'s kernel key, which carries
            # its literals, and under submit()'s shared one
            fut = s.submit(_q_agg(df, cut, k, scale))
            assert fut.result(300).equals(expected)
            assert fut.plan_cache == ("miss" if i == 0 else "hit")
            assert fut.n_params >= 3
            assert fut.queue_seconds is not None
            assert fut.query_id is not None
    finally:
        s.shutdown_serving()


def test_variant_resubmission_compiles_nothing_new():
    """The acceptance teeth: after the cold submission, a literal-variant
    re-submission builds ZERO new jitted kernels and ZERO new whole-stage
    executables — it re-binds values into the cached compiled programs."""
    s = _session()
    try:
        df = s.from_arrow(_TABLE)
        s.submit(_q_agg(df, 10.0, 40, 2.0)).result(300)
        s.submit(_q_rowlocal(df, 5.0, 80.0)).result(300)
        before = KC.stats()
        r1 = s.submit(_q_agg(df, 66.0, 11, 5.5)).result(300)
        r2 = s.submit(_q_rowlocal(df, 30.0, 31.5)).result(300)
        after = KC.stats()
        assert after["builds"] == before["builds"]
        assert after["stage_compiles"] == before["stage_compiles"]
        # and the warm path actually ran through the caches
        assert after["kernel_hits"] + after["stage_hits"] > \
            before["kernel_hits"] + before["stage_hits"]
        # sanity: the warm results are still right (r1 took the bucket
        # program, as collect() does)
        assert r1.equals(_q_agg(df, 66.0, 11, 5.5).to_arrow())
        assert r2.equals(_q_rowlocal(df, 30.0, 31.5).to_arrow())
    finally:
        s.shutdown_serving()


def test_latched_key_answers_differ_from_collect_in_float_order_only():
    """The cross-path drift, by name: once a 1,100-group variant (more
    than a bucket state holds) has latched the shape's kernel key dirty,
    submit() answers a 20-group variant through the sort program (a
    group's doubles summed in row order) while collect(), whose key
    carries its literals, takes the bucket update's dense form (summed as
    a tree).  Keys, counts and order are exact; the sums agree to 1e-12
    (variableFloatAgg, on in `_session`)."""
    s = _session()
    try:
        rng = np.random.RandomState(11)
        df = s.from_arrow(_TABLE.set_column(
            1, "b", pa.array(rng.randint(0, 1100, N_ROWS).astype(np.int64))))
        s.submit(_q_agg(df, 10.0, 1100, 2.0)).result(300)
        dense0 = s.query_metrics_total.get("aggDenseBatches", 0)
        got = s.submit(_q_agg(df, 55.0, 20, 7.0)).result(300)
        assert s.query_metrics_total.get("aggDenseBatches", 0) == dense0
        want = _q_agg(df, 55.0, 20, 7.0).to_arrow()
        assert s.query_metrics_total.get("aggDenseBatches", 0) > dense0
        assert got.schema == want.schema
        assert got["b"].equals(want["b"]) and got["n"].equals(want["n"])
        np.testing.assert_allclose(got["sx"].to_numpy(),
                                   want["sx"].to_numpy(), rtol=1e-12, atol=0)
    finally:
        s.shutdown_serving()


def test_rollup_expand_variant_reuses_programs():
    """Expand (rollup) literals ride the parameter-threaded Expand path."""
    s = _session()
    try:
        df = s.from_arrow(_TABLE)

        def q(thresh):
            return (df.filter(col("a") > thresh)
                    .select(col("b"), (col("c") + lit(2.0)).alias("x"))
                    .rollup(col("b")).agg(F.sum(col("x")).alias("sx"))
                    .order_by("b"))
        expected1 = q(30.0).to_arrow()
        f1 = s.submit(q(30.0))
        assert f1.result(300).equals(expected1)
        before = KC.stats()
        f2 = s.submit(q(71.0))
        r2 = f2.result(300)
        assert f2.plan_cache == "hit"
        after = KC.stats()  # snapshot BEFORE the baked-literal oracle run
        assert after["builds"] == before["builds"]
        assert after["stage_compiles"] == before["stage_compiles"]
        assert r2.equals(q(71.0).to_arrow())
    finally:
        s.shutdown_serving()


def test_unparameterized_positions_stay_correct():
    """Literals in positions the normalizer does NOT lift (Substring
    lengths, In lists, limits) still execute correctly through submit —
    they key the plan instead of parameterizing it."""
    s = _session()
    try:
        df = s.from_arrow(_TABLE)
        q1 = df.filter(col("b").isin([1, 2, 3])).limit(17)
        expected = q1.to_arrow()
        assert s.submit(q1).result(300).equals(expected)
    finally:
        s.shutdown_serving()


# --------------------------------------------------------------------------
# scheduler: priority + admission
# --------------------------------------------------------------------------

def test_priority_pop_and_admission_skip_unit():
    """Heap discipline without timing races: higher priority first, FIFO
    within a priority, and an over-budget item is SKIPPED while something
    cheaper runs — but admitted when nothing is in flight."""
    import heapq

    from spark_rapids_tpu.serve.scheduler import QueryFuture, _Item
    s = _session()
    try:
        s.submit(s.from_arrow(_TABLE).limit(1)).result(300)  # build sched
        sched = s.scheduler
        with sched._lock:
            assert sched._pop_admissible_locked() is None
            def item(pri, need):
                return _Item(None, pri, need, QueryFuture(pri, need))
            sched._seq += 1
            heapq.heappush(sched._queue, (-0, sched._seq, item(0, 10)))
            sched._seq += 1
            heapq.heappush(sched._queue, (-5, sched._seq,
                                          item(5, 10 ** 18)))  # huge need
            sched._seq += 1
            heapq.heappush(sched._queue, (-5, sched._seq, item(5, 20)))
            # something in flight: the huge-need head is skipped, the
            # equal-priority later item wins, then the low-priority one
            sched._running = 1
            sched._inflight_need = 0
            first = sched._pop_admissible_locked()
            assert first.priority == 5 and first.need == 20
            second = sched._pop_admissible_locked()
            assert second.priority == 0
            # nothing in flight: the huge item is admitted for progress
            sched._running = 0
            third = sched._pop_admissible_locked()
            assert third.need == 10 ** 18
            sched._running = 0
            sched._inflight_need = 0
    finally:
        s.shutdown_serving()


def test_queue_capacity_rejection():
    s = _session({"spark.rapids.sql.tpu.serve.maxConcurrentQueries": "1",
                  "spark.rapids.sql.tpu.serve.queue.capacity": "1"})
    try:
        df = s.from_arrow(_TABLE)
        gate = threading.Event()
        release = threading.Event()
        orig = s._collect_physical

        def blocking(physical, out_schema, **kw):
            gate.set()
            assert release.wait(30)
            return orig(physical, out_schema, **kw)

        s._collect_physical = blocking
        try:
            f1 = s.submit(df.limit(3))
            assert gate.wait(30)  # worker is now parked inside query 1
            f2 = s.submit(df.limit(4))          # fills the queue
            with pytest.raises(AdmissionRejected):
                s.submit(df.limit(5))           # over capacity
        finally:
            release.set()
        assert f1.result(300).num_rows == 3
        assert f2.result(300).num_rows == 4
        assert s.scheduler.rejected == 1
        pool = s.runtime.pool_stats()
        assert pool.get("numAdmissionRejections", 0) == 1
        assert pool.get("numAdmitted", 0) >= 2
        assert pool.get("queueTime", 0) > 0
    finally:
        s._collect_physical = orig
        s.shutdown_serving()


def test_concurrent_queries_all_correct():
    """A mixed bag racing over 4 workers — every result bit-for-bit
    identical to its SERIAL run.  The serial oracles run through a
    1-worker scheduler (the parameterized path), so the comparison
    isolates concurrency — and costs no per-variant baked recompiles
    (param-vs-baked equivalence is test_submit_matches_collect's job)."""
    variants = [(5.0 + 10.0 * i, 45 - i, 1.0 + i) for i in range(8)]
    serial = _session({"spark.rapids.sql.tpu.serve.maxConcurrentQueries":
                       "1"})
    try:
        df0 = serial.from_arrow(_TABLE)
        expected = [s_fut.result(300) for s_fut in
                    [serial.submit(_q_agg(df0, *v)) for v in variants]]
    finally:
        serial.shutdown_serving()
    s = _session({"spark.rapids.sql.tpu.serve.maxConcurrentQueries": "4",
                  "spark.rapids.sql.concurrentTpuTasks": "4"})
    try:
        df = s.from_arrow(_TABLE)
        futs = [s.submit(_q_agg(df, *v), priority=i % 3)
                for i, v in enumerate(variants)]
        for fut, exp in zip(futs, expected):
            assert fut.result(300).equals(exp)
        st = s.scheduler.stats()
        assert st["completed"] == 8 and st["failed"] == 0
        assert st["plan_cache"]["hits"] >= 7
    finally:
        s.shutdown_serving()


# --------------------------------------------------------------------------
# fault injection under concurrency
# --------------------------------------------------------------------------

def test_join_condition_param_in_exchange_keys():
    """Regression: a guard-lifted join-condition literal lands in the
    exchange's hash-partition keys; the fused bucketing program's
    value-free key must carry the KEY parameters in its traced binding
    too, or variant 2 replays variant 1's baked partition hash and
    silently drops matches."""
    s = _session({
        # force the shuffled-hash-join path (no broadcast) so the join
        # keys drive real hash exchanges over fused chains
        "spark.sql.autoBroadcastJoinThreshold": "-1",
        "spark.rapids.sql.tpu.join.partitioned.threshold": "0",
        "spark.rapids.sql.tpu.shuffle.partitions": "4",
    })
    try:
        left = s.from_arrow(pa.table(
            {"a": np.arange(2000, dtype=np.int64) % 97,
             "v": np.arange(2000, dtype=np.float64)}))
        right = s.from_arrow(pa.table(
            {"b": np.arange(2000, dtype=np.int64) % 97,
             "w": np.arange(2000, dtype=np.float64) * 0.5}))

        def q(offset):
            lf = left.filter(col("v") >= 0.0)   # row-local chain under
            rf = right.filter(col("w") >= 0.0)  # the exchange -> fuses
            return (lf.join(rf, on=(col("a") + lit(offset)) == col("b"))
                    .group_by(col("a"))
                    .agg(F.count(lit(1)).alias("n"))
                    .order_by("a"))

        for off in (1, 3):
            expected = q(off).to_arrow()
            assert s.submit(q(off)).result(300).equals(expected), off
    finally:
        s.shutdown_serving()


def test_shutdown_resolves_queued_futures():
    """A queued-but-never-admitted future must resolve with an error on
    shutdown, not hang a consumer blocked in result() forever."""
    s = _session({"spark.rapids.sql.tpu.serve.maxConcurrentQueries": "1"})
    df = s.from_arrow(_TABLE)
    gate, release = threading.Event(), threading.Event()
    orig = s._collect_physical

    def blocking(physical, out_schema, **kw):
        gate.set()
        assert release.wait(30)
        return orig(physical, out_schema, **kw)

    s._collect_physical = blocking
    try:
        running = s.submit(df.limit(1))
        assert gate.wait(30)
        queued = s.submit(df.limit(2))
        release.set()
        s.shutdown_serving()
        assert running.result(300).num_rows == 1  # in-flight finishes
        assert queued.cancelled
        with pytest.raises(RuntimeError, match="shut down"):
            queued.result(10)
    finally:
        release.set()
        s._collect_physical = orig
        s.shutdown_serving()


def test_oom_injection_while_racing_bit_for_bit():
    """injectOom fires at global reserve ordinals while 4 queries race;
    whichever query absorbs the fault must recover (spill-retry / split /
    CPU fallback) and EVERY result must equal its serial fault-free run."""
    variants = [(10.0, 40, 2.0), (35.0, 30, 3.0), (60.0, 20, 4.0),
                (85.0, 10, 5.0)]
    serial = _session({"spark.rapids.sql.tpu.serve.maxConcurrentQueries":
                       "1"})
    try:
        df0 = serial.from_arrow(_TABLE)
        expected = [f.result(300) for f in
                    [serial.submit(_q_agg(df0, *v)) for v in variants]]
    finally:
        serial.shutdown_serving()

    s = _session({"spark.rapids.sql.tpu.serve.maxConcurrentQueries": "4",
                  "spark.rapids.sql.concurrentTpuTasks": "4",
                  "spark.rapids.tpu.test.injectOom":
                      "1x2,4x2,7x2,10x2,13x2"})
    try:
        df = s.from_arrow(_TABLE)
        futs = [s.submit(_q_agg(df, *v)) for v in variants]
        for fut, exp in zip(futs, expected):
            assert fut.result(300).equals(exp)
    finally:
        s.shutdown_serving()


def test_net_fault_injection_under_submit():
    """A shuffling query (repartition) under injectNetFault still answers
    correctly through the serving path."""
    serial = _session()
    expected = (serial.from_arrow(_TABLE).repartition(4, col("b"))
                .group_by(col("b")).agg(F.count(lit(1)).alias("n"))
                .order_by("b").to_arrow())
    s = _session({"spark.rapids.tpu.test.injectNetFault": "1,3"})
    try:
        q = (s.from_arrow(_TABLE).repartition(4, col("b"))
             .group_by(col("b")).agg(F.count(lit(1)).alias("n"))
             .order_by("b"))
        assert s.submit(q).result(300).equals(expected)
    finally:
        s.shutdown_serving()


# --------------------------------------------------------------------------
# per-query budgets
# --------------------------------------------------------------------------

def test_budget_confines_spill_causality(tmp_path):
    """Two budgeted queries race; every ledger spill record stamped with
    an owner belongs to the query whose trace context stamped it — cause
    chains never cross query ids — and results stay bit-for-bit."""
    def q_sort(df, cut):
        # sort reserves device staging (site "sort") and with_retry
        # checkpoints its inputs as owned spillable buffers — the shapes
        # a budget actually bites on (a fully-absorbed tiny agg never
        # allocates at all)
        return (df.filter(col("a") > cut)
                .select(col("a"), col("b"), col("c"))
                .order_by(col("a").desc(), "b"))

    serial = _session({"spark.rapids.sql.tpu.serve.maxConcurrentQueries":
                       "1"})
    try:
        df0 = serial.from_arrow(_TABLE)
        expected = [serial.submit(q_sort(df0, 10.0)).result(300),
                    serial.submit(q_sort(df0, 55.0)).result(300)]
    finally:
        serial.shutdown_serving()

    jdir = str(tmp_path / "journal")
    s = _session({
        "spark.rapids.sql.tpu.serve.maxConcurrentQueries": "2",
        "spark.rapids.sql.concurrentTpuTasks": "2",
        # budget far below the sort's working set: the first reserve
        # trips it with nothing of the query's own yet spillable, later
        # ones spill its checkpoints
        "spark.rapids.sql.tpu.serve.queryBudgetBytes": str(256 << 10),
        "spark.rapids.sql.tpu.metrics.journal.dir": jdir,
    })
    try:
        df = s.from_arrow(_TABLE)
        futs = [s.submit(q_sort(df, 10.0)), s.submit(q_sort(df, 55.0))]
        for fut, exp in zip(futs, expected):
            assert fut.result(300).equals(exp)
        pool = s.runtime.pool_stats()
        assert pool.get("numBudgetOoms", 0) > 0
        checked = 0
        for fname in os.listdir(jdir):
            with open(os.path.join(jdir, fname)) as f:
                for line in f:
                    rec = json.loads(line)
                    if rec.get("kind") != "mem":
                        continue
                    owner = rec.get("owner") or rec.get("budget_owner")
                    q = rec.get("q")
                    if owner is not None and q is not None:
                        assert owner == q, rec
                        checked += 1
        assert checked > 0  # the confinement assertion actually ran
    finally:
        s.shutdown_serving()


def test_owner_accounting_balanced_through_spill_roundtrip():
    """Regression: synchronous_spill's victim removal must decrement the
    per-owner byte accounting exactly like untrack() (an unbalanced pop
    inflates owner_size forever: budgets would over-spill, then
    permanently OOM, and _owner_sizes would leak an entry per query)."""
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.mem.runtime import TpuRuntime
    rt = TpuRuntime(TpuConf({}, use_env=False),
                    pool_limit_bytes=1 << 30)
    with rt.ledger.query_scope("qX"):
        batch = ColumnarBatch.from_arrow(_TABLE.slice(0, 1024))
        bid = rt.add_batch(batch)
        buf_size = batch.device_size_bytes()
        assert rt.device_store.owner_size("qX") == buf_size
        del batch
        assert rt.device_store.synchronous_spill(0, owner="qX") > 0
        assert rt.device_store.owner_size("qX") == 0
        rt.get_batch(bid)  # unspill: re-promotion re-tracks the owner
        cur = rt.catalog.acquire(bid)
        try:
            assert rt.device_store.owner_size("qX") == cur.size_bytes > 0
        finally:
            rt.catalog.release(cur)
        rt.free_batch(bid)
        assert rt.device_store.owner_size("qX") == 0
        assert rt.device_store._owner_sizes == {}


# --------------------------------------------------------------------------
# satellites: semaphore attribution, journal routing, compile cache
# --------------------------------------------------------------------------

def test_semaphore_wait_attributed_to_acquirer():
    from spark_rapids_tpu.metrics.registry import Metrics
    from spark_rapids_tpu.mem.semaphore import TpuSemaphore
    sem = TpuSemaphore(1, metrics=Metrics())
    holder_m, waiter_m = Metrics(), Metrics()
    holding = threading.Event()
    done = threading.Event()

    def holder():
        with sem.held(task_id=1, metrics=holder_m):
            holding.set()
            done.wait(10)

    def waiter():
        holding.wait(10)
        with sem.held(task_id=2, metrics=waiter_m):
            pass

    t1 = threading.Thread(target=holder)
    t2 = threading.Thread(target=waiter)
    t1.start()
    t2.start()
    holding.wait(10)
    time.sleep(0.15)
    done.set()
    t1.join(10)
    t2.join(10)
    assert waiter_m.snapshot().get("semaphoreWaitTime", 0) >= 0.1
    # the HOLDER never blocked: a global timer would have charged it too
    assert holder_m.snapshot().get("semaphoreWaitTime", 0) == 0
    assert sem.metrics.snapshot().get("semaphoreWaitTime", 0) == 0


def test_concurrent_journals_stay_per_query(tmp_path):
    """Each racing query's journal holds exactly its own query span and
    sched record; deep-layer events never land in a neighbor's file."""
    from spark_rapids_tpu.metrics.journal import validate_events
    jdir = str(tmp_path / "j")
    s = _session({"spark.rapids.sql.tpu.serve.maxConcurrentQueries": "3",
                  "spark.rapids.sql.concurrentTpuTasks": "3",
                  "spark.rapids.sql.tpu.metrics.journal.dir": jdir})
    try:
        df = s.from_arrow(_TABLE)
        futs = [s.submit(_q_agg(df, 10.0 + i, 40 - i, 2.0)) for i in
                range(3)]
        for f in futs:
            f.result(300)
        files = [f for f in os.listdir(jdir) if f.startswith("query-")]
        assert len(files) == 3
        for fname in files:
            with open(os.path.join(jdir, fname)) as f:
                events = [json.loads(ln) for ln in f if ln.strip()]
            assert validate_events(events) == []
            qspans = [e for e in events
                      if e.get("kind") == "query" and e.get("ev") == "B"]
            assert len(qspans) == 1
            expect_q = qspans[0]["name"].replace("query-", "q")
            scheds = [e for e in events if e.get("kind") == "sched"]
            assert len(scheds) == 1
            assert scheds[0]["plan_cache"] in ("hit", "miss")
            # every trace-stamped record in this file is THIS query's
            for e in events:
                if "q" in e and e.get("kind") in ("mem", "sched"):
                    assert e["q"] == expect_q, e
    finally:
        s.shutdown_serving()


def test_compile_cache_repoint_and_reset(tmp_path, monkeypatch):
    from spark_rapids_tpu.utils import compile_cache as CC
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    CC.reset_for_tests()
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    try:
        assert CC.enable_compilation_cache(a, force=True)
        assert CC.active_cache_dir() == a
        # idempotent for the same path
        assert not CC.enable_compilation_cache(a, force=True)
        # REPOINTABLE: a conf change takes effect in-process (the old
        # module global latched the first path forever)
        assert CC.enable_compilation_cache(b, force=True)
        assert CC.active_cache_dir() == b
        import jax
        assert jax.config.jax_compilation_cache_dir == b
        # no path given: the fixed directory inside the checkout
        assert CC.enable_compilation_cache(force=True)
        assert CC.active_cache_dir() == CC.DEFAULT_CACHE_DIR
        assert os.path.dirname(CC.DEFAULT_CACHE_DIR) == os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        # backend gate still holds without force on a CPU process
        CC.reset_for_tests()
        assert not CC.enable_compilation_cache(a, force=False)
        assert CC.active_cache_dir() is None
    finally:
        CC.reset_for_tests()


@pytest.mark.parametrize("backend,force", [("tpu", False), ("cpu", True)])
def test_compile_cache_gate_asks_the_backend(tmp_path, monkeypatch,
                                             backend, force):
    """A machine with a chip sets neither jax_platforms nor JAX_PLATFORMS:
    the gate must ask the backend in use, not the environment."""
    import jax
    from spark_rapids_tpu.utils import compile_cache as CC
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    CC.reset_for_tests()
    try:
        assert CC.enable_compilation_cache(str(tmp_path), force=force)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        CC.reset_for_tests()


def test_compile_cache_leaves_env_dir_to_jax(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself; the helper
    never re-points the directory, whatever path the conf carries."""
    import jax
    from spark_rapids_tpu.utils import compile_cache as CC
    CC.reset_for_tests()
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    try:
        assert CC.enable_compilation_cache(str(tmp_path / "conf"),
                                           force=True)
        assert CC.active_cache_dir() == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        CC.reset_for_tests()


def test_scheduler_observability_block():
    from spark_rapids_tpu.metrics.export import session_observability
    s = _session()
    try:
        df = s.from_arrow(_TABLE)
        s.submit(_q_rowlocal(df, 5.0, 50.0)).result(300)
        obs = session_observability(s)
        sched = obs.get("scheduler")
        assert sched is not None
        assert sched["admitted"] >= 1 and sched["completed"] >= 1
        assert "plan_cache" in sched
        assert sched["planCacheHits"] + sched["planCacheMisses"] >= 1
    finally:
        s.shutdown_serving()


# --------------------------------------------------------------------------
# ISSUE 12 (tpulint TPU009) regressions: shared-state fixes under the
# scheduler's worker-thread concurrency
# --------------------------------------------------------------------------

def test_kernel_cache_counters_exact_under_concurrency():
    """record_dispatch/record_donated are read-modify-writes on a module
    dict; before ISSUE 12 they ran unlocked and concurrent serving
    threads lost increments (the tests assert them exactly)."""
    from spark_rapids_tpu.utils import kernel_cache as kc
    base = kc.stats()["dispatches"]
    base_don = kc.stats()["donated_buffers"]
    n_threads, per = 8, 2000

    def hammer():
        for _ in range(per):
            kc.record_dispatch()
            kc.record_donated(1)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert kc.stats()["dispatches"] - base == n_threads * per
    assert kc.stats()["donated_buffers"] - base_don == n_threads * per


def test_param_bindings_are_thread_isolated():
    """The plan-cache parameter binding rides a thread-local: one worker
    thread's binding must be invisible to its neighbors (pre-ISSUE-12
    the lazily-built local could be LOST in an init race)."""
    from spark_rapids_tpu.ops import expressions as E
    seen = {}
    installed = threading.Event()
    release = threading.Event()

    def binder():
        tls = E._param_tls()
        tls.values = {0: "mine"}
        installed.set()
        release.wait(5)
        seen["binder"] = E.current_param(0)
        tls.values = None

    def observer():
        installed.wait(5)
        seen["observer"] = E.current_param(0)
        release.set()

    ts = [threading.Thread(target=binder),
          threading.Thread(target=observer)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert seen["binder"] == "mine"
    assert seen["observer"] is None


def test_row_offset_and_input_file_are_thread_local():
    """Concurrent queries publish different row offsets / input files on
    their own worker threads; a shared module slot (the pre-ISSUE-12
    shape) handed one query's value to another's trace."""
    from spark_rapids_tpu.ops import expressions as E
    results = {}
    barrier = threading.Barrier(2, timeout=5)

    def worker(tag, path):
        def probe(b):
            barrier.wait()      # both threads are mid-eval together
            time.sleep(0.02)
            return E.current_input_file()[0]
        E.set_input_file(path, 0, 100)
        try:
            results[tag] = E.eval_with_row_offset(probe, None, tag)
        finally:
            E.clear_input_file()

    ts = [threading.Thread(target=worker, args=("a", "/data/a.parquet")),
          threading.Thread(target=worker, args=("b", "/data/b.parquet"))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert results == {"a": "/data/a.parquet", "b": "/data/b.parquet"}


def test_codec_instances_race_free():
    """resolve_codec builds codec instances (which own side pools)
    exactly once per name, even under concurrent first touch."""
    from spark_rapids_tpu.compress import codec as C
    C._INSTANCES.pop("none", None)
    got = []

    def resolve():
        got.append(C.resolve_codec("none"))

    ts = [threading.Thread(target=resolve) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len({id(c) for c in got}) == 1


def test_parquet_pools_single_instance_under_concurrency():
    from spark_rapids_tpu.io import parquet_device as P
    with P._POOL_INIT_LOCK:
        pass  # the lock exists and is free
    P._DECOMP_POOL = None
    got = []

    def touch():
        got.append(P._decomp_pool())

    ts = [threading.Thread(target=touch) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len({id(p) for p in got}) == 1
