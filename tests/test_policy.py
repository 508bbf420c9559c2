"""Data-movement policy engine tier (ISSUE 18, policy/).

Coverage:
  * kill switch: policy ON == policy OFF bit-for-bit across every
    supported dtype, and under genuine memory pressure (the cascade
    slice) where victim scoring is live on every spill round;
  * fault injection: injectOom at every reserve site of a pressured
    slice query with the policy engaged — results identical to the
    fault-free baseline at every ordinal;
  * victim scoring: a consumed (dead) shuffle partition spills before a
    still-to-be-read one even when the deterministic baseline order says
    otherwise; `spill_candidates` is the stable ordering both rank over;
  * proactive unspill: charged to (and budget-bounded by) the OWNING
    query — a tiny serve budget skips the prefetch without ever touching
    another query's buffers; the headroom floor keeps the prefetch from
    pushing the pool toward eviction; hits/waste are counted;
  * flow control: the serve window's stall is bounded (a stalled reducer
    back-pressures, never deadlocks) and the fetch side completes under
    a degenerate window while feeding the consumption rate;
  * codec re-selection: a wire-bound exchange flips the advised codec,
    the advice is per-shuffle + session-sticky, and an advised fetch
    round-trips the PR 5 negotiation path bit-for-bit with compressed
    bytes actually crossing the loopback wire;
  * observability: victim/unspill decisions replay from journal shards
    alone (`--memory` policy section) and the counters land in
    session_observability.
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import ColumnarBatch
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.mem import StorageTier, TpuRuntime
from spark_rapids_tpu.metrics import names as MN
from spark_rapids_tpu.metrics.timeline import load_journal_dir
from spark_rapids_tpu.plan.logical import col, functions as F, lit
from spark_rapids_tpu.policy import (CodecAdvisor, FlowController,
                                     MovementPolicy)
from spark_rapids_tpu.shuffle import LoopbackTransport, ShuffleEnv
from spark_rapids_tpu.types import (DoubleType, LongType, Schema,
                                    StringType, StructField)
from spark_rapids_tpu.utils import faults

from data_gen import gen_table

pytestmark = pytest.mark.policy

POLICY_OFF = {"spark.rapids.sql.tpu.policy.enabled": "false"}

# keeps the lazy policy thread out of unit tests: ticks are driven
# synchronously so every assertion sees a deterministic state
NO_THREAD = {"spark.rapids.sql.tpu.policy.unspill.intervalMs": "0"}

# the spill-cascade slice (same shape test_memledger runs): pool budget
# far below the working set, so victim selection runs on every reserve
# round
_CASCADE_CONF = {
    "spark.rapids.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.memory.tpu.poolSizeBytes": str(2 << 20),
    "spark.rapids.memory.host.spillStorageSize": str(1 << 20),
    "spark.rapids.sql.batchSizeBytes": str(512 << 10),
    "spark.rapids.sql.reader.batchSizeRows": "16384",
    "spark.sql.autoBroadcastJoinThreshold": "-1",
    "spark.rapids.sql.tpu.join.partitioned.threshold": "1",
    "spark.rapids.sql.tpu.shuffle.partitions": "8",
}


def _slice_query(s, n=60_000):
    fact = s.from_pydict({"k": [i % 7 for i in range(n)],
                          "v": [float(i) for i in range(n)],
                          "q": [i % 3 for i in range(n)]})
    dim = s.from_pydict({"k": list(range(7)),
                         "name": [f"g{j}" for j in range(7)]})
    return (fact.join(dim, on="k").filter(col("q") < 2)
            .group_by(col("name"))
            .agg(F.sum(col("v")).alias("sv"), F.count(lit(1)).alias("c"))
            .order_by(col("name")).collect())


def make_batch(n=200, seed=0):
    rng = np.random.RandomState(seed)
    schema = Schema([StructField("k", LongType),
                     StructField("v", DoubleType)])
    return ColumnarBatch.from_pydict(
        {"k": rng.randint(-100, 100, n).tolist(),
         "v": rng.uniform(-5, 5, n).tolist()}, schema,
        capacity=max(1024, n))


def _runtime(pool=8 << 20, host=8 << 20, extra=None, tmpdir=None):
    conf = TpuConf({"spark.rapids.memory.host.spillStorageSize": host,
                    **NO_THREAD, **(extra or {})})
    return TpuRuntime(conf, pool_limit_bytes=pool,
                      spill_dir=tmpdir)


# --------------------------------------------------------------------------
# kill switch: policy ON == policy OFF bit-for-bit
# --------------------------------------------------------------------------

ALL_DTYPES = [T.IntegerType, T.LongType, T.ShortType, T.ByteType,
              T.DoubleType, T.FloatType, T.BooleanType, T.StringType,
              T.DateType, T.TimestampType]


def _assert_bit_equal(a, b, label):
    """Bit-for-bit table equality: float columns compare by BIT PATTERN
    (NaN payloads and signed zeros included — Arrow's `equals` treats
    NaN as unequal), everything else by Arrow equality."""
    import pyarrow as pa
    import pyarrow.compute as pc
    assert a.schema.equals(b.schema), label
    assert a.num_rows == b.num_rows, label
    for i, name in enumerate(a.column_names):
        ca = a.column(i).combine_chunks()
        cb = b.column(i).combine_chunks()
        if pa.types.is_floating(ca.type):
            assert pc.is_null(ca).equals(pc.is_null(cb)), (label, name)
            na = np.asarray(ca.fill_null(0.0))
            nb = np.asarray(cb.fill_null(0.0))
            view = np.uint64 if na.dtype == np.float64 else np.uint32
            assert np.array_equal(na.view(view), nb.view(view)), \
                (label, name)
        else:
            assert ca.equals(cb), (label, name)


@pytest.mark.parametrize("dtype", ALL_DTYPES, ids=lambda d: d.name)
def test_policy_on_off_bit_for_bit_every_dtype(dtype):
    """Nullable columns of every supported dtype cross a hash exchange
    identically with the policy engine on and off."""
    data, schema = gen_table(seed=11, n=400, k=(T.LongType, False),
                             v=dtype)
    base = {"spark.rapids.sql.tpu.shuffle.partitions": "4"}

    def q(extra):
        s = TpuSession({**base, **extra})
        return (s.from_pydict(data, schema)
                .repartition(4, col("k")).to_arrow())

    _assert_bit_equal(q({}), q(POLICY_OFF), dtype.name)


def test_policy_on_off_bit_for_bit_under_pressure():
    """The cascade slice — where victim scoring decides every spill
    round — answers identically with the policy on and off, and the ON
    run proves the scorer actually ran."""
    s_on = TpuSession(dict(_CASCADE_CONF))
    rows_on = _slice_query(s_on)
    s_off = TpuSession({**_CASCADE_CONF, **POLICY_OFF})
    rows_off = _slice_query(s_off)
    assert rows_on == rows_off
    on_stats = s_on.runtime.pool_stats()
    assert on_stats.get(MN.NUM_POLICY_VICTIM_PICKS, 0) > 0, \
        "pressure run never engaged policy victim selection"
    assert s_off.runtime.pool_stats().get(
        MN.NUM_POLICY_VICTIM_PICKS, 0) == 0, \
        "kill switch left the victim scorer live"


# --------------------------------------------------------------------------
# fault injection: injectOom x policy at every reserve site
# --------------------------------------------------------------------------

# test_retry's slice conf plus a pool small enough that the policy
# victim path runs INSIDE the injected-OOM recovery rounds
_OOM_CONF = {
    "spark.rapids.sql.tpu.wholeStage.enabled": "false",
    "spark.rapids.sql.tpu.join.partitioned.threshold": "1",
    "spark.sql.autoBroadcastJoinThreshold": "-1",
    "spark.rapids.sql.tpu.shuffle.partitions": "4",
    "spark.rapids.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.memory.tpu.poolSizeBytes": str(96 << 10),
    "spark.rapids.memory.host.spillStorageSize": str(64 << 10),
    # the proactive-unspill thread would add background reserve ops and
    # make the per-run reserve-op count nondeterministic; victim scoring
    # (the policy surface under test here) stays fully live
    **NO_THREAD,
}


def _oom_slice(extra_conf=None, n=400):
    faults.INJECTOR.reset()
    conf = dict(_OOM_CONF)
    conf.update(extra_conf or {})
    s = TpuSession(conf)
    fact = s.from_pydict({"k": [i % 7 for i in range(n)],
                          "v": [float(i) for i in range(n)],
                          "q": [i % 3 for i in range(n)]})
    dim = s.from_pydict({"k": list(range(7)),
                         "name": [f"g{j}" for j in range(7)]})
    return (fact.join(dim, on="k").filter(col("q") < 2)
            .group_by(col("name"))
            .agg(F.sum(col("v")).alias("sv"), F.count(lit(1)).alias("c"))
            .order_by(col("name")).collect())


def test_oom_injection_every_reserve_site_with_policy():
    """With the policy engine live on a pressured slice, an injected OOM
    at EVERY reserve ordinal still answers bit-for-bit."""
    baseline = _oom_slice()
    n_ops = faults.INJECTOR.oom_ops
    assert n_ops > 5, "query exposed too few reserve sites"
    for ordinal in range(1, n_ops + 1):
        out = _oom_slice({"spark.rapids.tpu.test.injectOom":
                          str(ordinal)})
        assert out == baseline, f"ordinal {ordinal} changed the result"
        assert faults.INJECTOR.injected_log, \
            f"ordinal {ordinal} never fired"


# --------------------------------------------------------------------------
# victim scoring: next-use order beats the baseline when it knows more
# --------------------------------------------------------------------------

def test_spill_candidates_stable_deterministic_order():
    """The ordering API both the baseline and the scorer rank over:
    (spill_priority, id) ascending, unreferenced only, owner-confined
    when asked."""
    rt = _runtime()
    with rt.ledger.query_scope("qA"):
        a = rt.add_batch(make_batch(seed=1))
    with rt.ledger.query_scope("qB"):
        b = rt.add_batch(make_batch(seed=2))
        c = rt.add_batch(make_batch(seed=3))
    assert rt.device_store.spill_candidates() == sorted([a, b, c])
    assert rt.device_store.spill_candidates(owner="qB") == sorted([b, c])
    # a referenced buffer is not a candidate
    buf = rt.catalog.acquire(b)
    try:
        assert rt.device_store.spill_candidates() == sorted([a, c])
    finally:
        rt.catalog.release(buf)
    assert rt.device_store.spill_candidates() == sorted([a, b, c])


def test_dead_partition_spills_before_future_one():
    """A consumed shuffle partition's buffer (score 0) is evicted before
    a still-to-be-read one, even though the deterministic baseline
    (spill_priority, id) would pick the OLDER buffer first."""
    rt = _runtime()
    pol = rt.policy
    b_future = make_batch(seed=4)
    b_dead = make_batch(seed=5)
    id_future = rt.add_batch(b_future)   # lower id: baseline's pick
    id_dead = rt.add_batch(b_dead)
    pol.note_shuffle_buffer(id_future, 9, 1,
                            b_future.device_size_bytes())
    pol.note_shuffle_buffer(id_dead, 9, 0, b_dead.device_size_bytes())
    pol.begin_shuffle_read(9, [0, 1])
    pol.partition_consumed(9, 0)         # partition 0 is dead
    # spill exactly one buffer's worth
    target = rt.device_store.current_size - b_dead.device_size_bytes()
    rt.device_store.synchronous_spill(max(0, target))
    assert rt.catalog.lookup_tier(id_dead) != StorageTier.DEVICE
    assert rt.catalog.lookup_tier(id_future) == StorageTier.DEVICE
    stats = rt.pool_stats()
    assert stats.get(MN.NUM_POLICY_VICTIM_PICKS, 0) >= 1
    assert stats.get(MN.NUM_POLICY_VICTIM_OVERRIDES, 0) >= 1, \
        "the policy pick should have overridden the baseline order"


def test_early_release_frees_partition_at_final_consumption():
    """An exclusive read declaring per-partition consumption counts
    frees a partition's map buffers at its FINAL planned consumption —
    no spill write, bytes straight back to the pool.  A partition
    planned for two consumptions (skew slice re-read) survives the
    first."""
    rt = _runtime()
    pol = rt.policy
    b0, b1 = make_batch(seed=20), make_batch(seed=21)
    id0, id1 = rt.add_batch(b0), rt.add_batch(b1)
    pol.note_shuffle_buffer(id0, 31, 0, b0.device_size_bytes())
    pol.note_shuffle_buffer(id1, 31, 1, b1.device_size_bytes())
    pol.begin_shuffle_read(31, [0, 1], counts={0: 1, 1: 2},
                           exclusive=True)
    pol.partition_consumed(31, 0)
    with pytest.raises(KeyError):
        rt.catalog.lookup_tier(id0)  # freed outright
    pol.partition_consumed(31, 1)    # first of two planned reads
    assert rt.catalog.lookup_tier(id1) == StorageTier.DEVICE
    pol.partition_consumed(31, 1)    # final read: now releasable
    with pytest.raises(KeyError):
        rt.catalog.lookup_tier(id1)
    assert rt.pool_stats().get(MN.NUM_POLICY_EARLY_RELEASES, 0) == 2


def test_early_release_never_fires_without_exclusivity():
    """A read that is NOT the shuffle's only consumer (cluster mode: a
    peer or a speculative re-read may still fetch the block) keeps every
    buffer resident through consumption; so does the earlyRelease kill
    switch."""
    for extra, exclusive in (
            (None, False),   # shared read: counts ignored
            ({"spark.rapids.sql.tpu.policy.earlyRelease.enabled":
              "false"}, True)):  # knob off: exclusive read still keeps
        rt = _runtime(extra=extra)
        pol = rt.policy
        b = make_batch(seed=22)
        bid = rt.add_batch(b)
        pol.note_shuffle_buffer(bid, 33, 0, b.device_size_bytes())
        pol.begin_shuffle_read(33, [0], counts={0: 1},
                               exclusive=exclusive)
        pol.partition_consumed(33, 0)
        assert rt.catalog.lookup_tier(bid) == StorageTier.DEVICE
        assert rt.pool_stats().get(MN.NUM_POLICY_EARLY_RELEASES, 0) == 0


def test_unknown_buffers_degrade_to_baseline_order():
    """With no shuffle knowledge every score is the neutral 1.0 and the
    pick is EXACTLY the baseline (spill_priority, id) head."""
    rt = _runtime()
    ids = [rt.add_batch(make_batch(seed=s)) for s in (6, 7, 8)]
    one = make_batch(seed=6).device_size_bytes()
    rt.device_store.synchronous_spill(rt.device_store.current_size - one)
    spilled = [b for b in ids
               if rt.catalog.lookup_tier(b) != StorageTier.DEVICE]
    assert spilled == sorted(ids)[:len(spilled)], \
        "neutral scores must preserve the deterministic baseline order"
    assert rt.pool_stats().get(MN.NUM_POLICY_VICTIM_OVERRIDES, 0) == 0


# --------------------------------------------------------------------------
# proactive unspill: budget-confined, headroom-bounded prefetch
# --------------------------------------------------------------------------

def test_proactive_unspill_charged_to_owner():
    rt = _runtime(pool=8 << 20)
    pol = rt.policy
    b = make_batch(seed=9)
    size = b.device_size_bytes()
    with rt.ledger.query_scope("qA"):
        bid = rt.add_batch(b)
    pol.note_shuffle_buffer(bid, 3, 0, size)
    rt.device_store.synchronous_spill(0)
    assert rt.catalog.lookup_tier(bid) == StorageTier.HOST
    pol.begin_shuffle_read(3, [0])
    assert pol.tick(rt) == 1
    assert rt.catalog.lookup_tier(bid) == StorageTier.DEVICE
    # ownership survived the round trip: the prefetch was charged to qA
    assert rt.device_store.owner_size("qA") >= size
    assert rt.pool_stats().get(MN.NUM_PROACTIVE_UNSPILLS, 0) == 1
    # reading the prefetched buffer is a hit
    rt.get_batch(bid)
    assert rt.pool_stats().get(MN.NUM_PREFETCH_HITS, 0) == 1


def test_prefetch_skips_below_headroom_floor():
    """The prefetch is opportunistic: when re-admitting would eat into
    the headroom floor it simply does not happen."""
    rt = _runtime(pool=8 << 20,
                  extra={"spark.rapids.sql.tpu.policy.unspill."
                         "headroomFraction": "1.0"})
    pol = rt.policy
    b = make_batch(seed=10)
    with rt.ledger.query_scope("qA"):
        bid = rt.add_batch(b)
    pol.note_shuffle_buffer(bid, 4, 0, b.device_size_bytes())
    rt.device_store.synchronous_spill(0)
    pol.begin_shuffle_read(4, [0])
    assert pol.tick(rt) == 0
    assert rt.catalog.lookup_tier(bid) == StorageTier.HOST
    assert rt.pool_stats().get(MN.NUM_PROACTIVE_UNSPILLS, 0) == 0


def test_prefetch_budget_confined_never_touches_neighbors():
    """A 1-byte serve budget rejects the owner's prefetch reservation;
    the skip is quiet and the OTHER query's device buffers are never
    victimized to make room."""
    rt = _runtime(pool=8 << 20,
                  extra={"spark.rapids.sql.tpu.serve.queryBudgetBytes":
                         "1"})
    pol = rt.policy
    b_a = make_batch(seed=11)
    with rt.ledger.query_scope("qA"):
        bid_a = rt.add_batch(b_a)
    with rt.ledger.query_scope("qB"):
        bid_b = rt.add_batch(make_batch(seed=12))
    pol.note_shuffle_buffer(bid_a, 5, 0, b_a.device_size_bytes())
    # spill ONLY qA's buffer, then declare its upcoming read
    rt.device_store.synchronous_spill(0, owner="qA")
    assert rt.catalog.lookup_tier(bid_a) == StorageTier.HOST
    assert rt.catalog.lookup_tier(bid_b) == StorageTier.DEVICE
    pol.begin_shuffle_read(5, [0])
    assert pol.tick(rt) == 0, "over-budget prefetch must skip, not raise"
    assert rt.catalog.lookup_tier(bid_a) == StorageTier.HOST
    assert rt.catalog.lookup_tier(bid_b) == StorageTier.DEVICE, \
        "prefetch budget enforcement spilled a NEIGHBOR query's buffer"
    assert rt.pool_stats().get(MN.NUM_PROACTIVE_UNSPILLS, 0) == 0


def test_policy_off_runtime_has_no_hooks_live():
    rt = _runtime(extra=POLICY_OFF)
    pol = rt.policy
    assert not pol.wants_victim_scoring()
    assert pol.flow_controller() is None
    assert pol.wire_codec(1) is None
    bid = rt.add_batch(make_batch(seed=13))
    pol.note_shuffle_buffer(bid, 1, 0, 100)
    pol.begin_shuffle_read(1, [0])
    rt.device_store.synchronous_spill(0)
    assert pol.tick(rt) == 0
    stats = rt.pool_stats()
    for m in (MN.NUM_POLICY_VICTIM_PICKS, MN.NUM_PROACTIVE_UNSPILLS):
        assert stats.get(m, 0) == 0


# --------------------------------------------------------------------------
# flow control: bounded stalls, no deadlock
# --------------------------------------------------------------------------

def test_flow_window_tracks_consumption_rate():
    fc = FlowController(min_window_bytes=1 << 10, horizon_s=0.5,
                        max_stall_s=0.05)
    assert fc.window_bytes() == 1 << 10  # no evidence: the floor
    for _ in range(4):
        fc.on_consumed(1 << 20)
    assert fc.rate_bytes_per_s() > 0
    assert fc.window_bytes() > 1 << 10


def test_fetch_window_clamps_to_device_headroom():
    """The fetch admission window is pool-aware: with a headroom
    provider attached it never exceeds present device headroom (down to
    1 byte — serial fetch under a full pool), while the serve-side
    window keeps its rate floor untouched."""
    free = [1 << 20]
    fc = FlowController(min_window_bytes=64 << 10, horizon_s=0.2,
                        max_stall_s=0.05, headroom=lambda: free[0])
    assert fc.fetch_window_bytes() == 64 << 10  # ample headroom: floor
    free[0] = 4096
    assert fc.fetch_window_bytes() == 4096      # clamped below floor
    assert fc.window_bytes() == 64 << 10        # serve side unclamped
    free[0] = 0
    assert fc.fetch_window_bytes() == 1         # serial, never zero
    nofloor = FlowController(min_window_bytes=64 << 10, horizon_s=0.2,
                             max_stall_s=0.05)
    assert nofloor.fetch_window_bytes() == 64 << 10  # no provider


def test_serve_stall_is_bounded_and_deadlock_free():
    """With in-flight bytes over the window and NO consumer progress the
    serve stalls at most maxServeStallMs and then proceeds — soft
    backpressure can never wedge the server."""
    fc = FlowController(min_window_bytes=1024, horizon_s=0.2,
                        max_stall_s=0.2)
    assert fc.serve_acquire(1, 2048) is False  # first: nothing in flight
    t0 = time.monotonic()
    stalled = fc.serve_acquire(2, 4096)        # over window: must stall
    dt = time.monotonic() - t0
    assert stalled is True
    assert 0.1 <= dt < 2.0, f"stall not bounded: {dt}s"
    assert fc.serve_inflight_bytes() == 2048 + 4096
    assert fc.serve_release(1) == 2048
    assert fc.serve_release(2) == 4096
    assert fc.serve_release(2) == 0            # balanced: second ack free
    assert fc.serve_inflight_bytes() == 0


def test_consumption_releases_a_stalled_serve_early():
    import threading
    fc = FlowController(min_window_bytes=1, horizon_s=10.0,
                        max_stall_s=5.0)
    fc.serve_acquire(1, 1 << 20)
    done = []

    def _second():
        fc.serve_acquire(2, 1 << 20)
        done.append(time.monotonic())

    t = threading.Thread(target=_second)
    t0 = time.monotonic()
    t.start()
    time.sleep(0.1)
    # reduce-side evidence: rate jumps, the window swallows the stall
    fc.on_consumed(64 << 20)
    t.join(timeout=5.0)
    assert done, "stalled serve never released"
    assert done[0] - t0 < 4.0, "consumption evidence did not wake it"


def _shuffle_env(extra=None, transport=None, executor_id="exec-0"):
    conf = TpuConf({"spark.rapids.shuffle.deviceResident.enabled": True,
                    **NO_THREAD, **(extra or {})})
    rt = TpuRuntime(conf, pool_limit_bytes=64 << 20)
    return ShuffleEnv(rt, conf, executor_id, transport)


def test_async_fetch_completes_under_degenerate_window():
    """A 1-byte flow window (stalled reducer, no rate evidence) still
    drains every partition — the oversized-batch-alone admission rule is
    preserved under flow control."""
    env = _shuffle_env()
    sid, want = 41, {}
    for p in range(4):
        b = make_batch(seed=20 + p)
        env.write_partition(sid, 0, p, b)
        want[p] = sorted(b.to_pylist())
    from spark_rapids_tpu.shuffle.fetch import AsyncFetchIterator
    fc = FlowController(min_window_bytes=1, horizon_s=0.2,
                        max_stall_s=0.05)
    got = {}
    for rid, batch in AsyncFetchIterator(env, sid, range(4), flow=fc):
        time.sleep(0.01)  # deliberately slow reducer
        got.setdefault(rid, []).extend(batch.to_pylist())
    assert {p: sorted(r) for p, r in got.items()} == want
    assert fc.rate_bytes_per_s() > 0, \
        "the consumer loop never fed the flow controller"


def test_env_async_fetch_rides_the_policy_flow_controller():
    env = _shuffle_env()
    sid = 42
    b = make_batch(seed=30)
    env.write_partition(sid, 0, 0, b)
    got = [r for _rid, batch in env.fetch_partitions_async(sid, [0])
           for r in batch.to_pylist()]
    assert sorted(got) == sorted(b.to_pylist())
    fc = env.runtime.policy.flow_controller()
    assert fc is not None and fc.rate_bytes_per_s() > 0


# --------------------------------------------------------------------------
# codec re-selection: wire evidence -> negotiation round trip
# --------------------------------------------------------------------------

# a wire peak so low ANY observed exchange is wire-bound
_WIRE_BOUND = {"spark.rapids.sql.tpu.roofline.peakWireGBs": "0.000001"}


def test_codec_advisor_triggers_and_sticks():
    adv = CodecAdvisor(TpuConf(_WIRE_BOUND))
    assert adv.wire_codec(5) is None
    assert adv.observe_exchange(5, 64 << 20, 1.0) is True
    assert adv.wire_codec(5) == "lz4"
    assert adv.wire_codec(99) == "lz4", "advice must be session-sticky"
    assert adv.observe_exchange(5, 64 << 20, 1.0) is False  # not fresh
    adv.shuffle_released(5)
    assert adv.wire_codec(5) == "lz4"  # sticky survives the release


def test_codec_advisor_needs_volume_and_wire_bound_evidence():
    adv = CodecAdvisor(TpuConf(_WIRE_BOUND))
    # below minExchangeBytes: no advice no matter the utilization
    assert adv.observe_exchange(1, 1 << 20, 0.001) is False
    # high peak: utilization below the bound fraction
    fast = CodecAdvisor(TpuConf(
        {"spark.rapids.sql.tpu.roofline.peakWireGBs": "1000000"}))
    assert fast.observe_exchange(2, 64 << 20, 1.0) is False
    assert adv.wire_codec(1) is None and fast.wire_codec(2) is None


def test_codec_advisor_wire_peak_defaults_to_one_gbs():
    assert CodecAdvisor(TpuConf()).wire_peak == 1e9
    adv = CodecAdvisor(TpuConf())
    # 64 MiB in 0.1 s is 0.67 GB/s: past half the default peak
    assert adv.observe_exchange(3, 64 << 20, 0.1) is True
    assert adv.observe_exchange(4, 64 << 20, 1.0) is False


def test_codec_reselection_round_trips_negotiation():
    """An advised fetch negotiates the candidate codec end to end over
    the loopback wire: rows bit-for-bit, compressed bytes counted on the
    reader's runtime metrics."""
    wire = LoopbackTransport(pool_size=1 << 20, chunk_size=1 << 14)
    small = {"spark.rapids.shuffle.compression.minSizeBytes": "64"}
    writer = _shuffle_env(extra=small, transport=wire,
                          executor_id="exec-A")
    reader = _shuffle_env(extra={**_WIRE_BOUND, **small},
                          transport=wire, executor_id="exec-B")
    b = make_batch(seed=31, n=2000)
    want = b.to_pylist()
    sid = 77
    writer.write_partition(sid, 0, 1, b)
    pol = reader.runtime.policy
    # wire evidence arrives (as exec/exchange.py would feed it)
    assert pol.codec.observe_exchange(sid, 64 << 20, 1.0)
    assert pol.wire_codec(sid) == "lz4"
    got = [r for p in reader.fetch_partition(sid, 1,
                                             remote_peers=["exec-A"])
           for r in p.to_pylist()]
    assert got == want
    rstats = reader.runtime.pool_stats()
    assert rstats.get(MN.COMPRESSED_SHUFFLE_BYTES_READ, 0) > 0, \
        "advised fetch never pulled compressed bytes over the wire"


def test_unadvised_fetch_stays_raw():
    wire = LoopbackTransport(pool_size=1 << 20, chunk_size=1 << 14)
    writer = _shuffle_env(transport=wire, executor_id="exec-A")
    reader = _shuffle_env(transport=wire, executor_id="exec-B")
    b = make_batch(seed=32)
    sid = 78
    writer.write_partition(sid, 0, 0, b)
    got = [r for p in reader.fetch_partition(sid, 0,
                                             remote_peers=["exec-A"])
           for r in p.to_pylist()]
    assert got == b.to_pylist()
    assert reader.runtime.pool_stats().get(
        MN.COMPRESSED_SHUFFLE_BYTES_READ, 0) == 0


# --------------------------------------------------------------------------
# observability: journal replay + session counters + gauges
# --------------------------------------------------------------------------

def test_memory_cli_replays_policy_decisions(tmp_path):
    """The --memory analyzer reconstructs the policy's decision stream
    from journal shards ALONE (no live process)."""
    from spark_rapids_tpu.metrics import memledger as ML
    jdir = str(tmp_path / f"journal_{time.monotonic_ns()}")
    conf = dict(_CASCADE_CONF,
                **{"spark.rapids.sql.tpu.metrics.journal.dir": jdir})
    s = TpuSession(conf)
    _slice_query(s)
    assert s.runtime.pool_stats().get(MN.NUM_POLICY_VICTIM_PICKS, 0) > 0
    out = ML.analyze_shards(load_journal_dir(jdir))
    polrep = out.get("policy") or {}
    assert polrep.get("victims", 0) > 0, polrep
    text = ML.render(out)
    assert "policy decisions:" in text
    assert "scored picks" in text


def test_session_observability_carries_policy_counters():
    from spark_rapids_tpu.metrics.export import session_observability
    s = TpuSession(dict(_CASCADE_CONF))
    _slice_query(s)
    obs = session_observability(s)
    assert obs["numPolicyVictimPicks"] > 0
    for key in ("numPolicyVictimOverrides", "numProactiveUnspills",
                "numPrefetchHits", "numPrefetchWasted",
                "numBackpressureStalls", "numCodecReselections"):
        assert key in obs, key


def test_policy_gauges_are_registered_telemetry_series():
    rt = _runtime()
    g = rt.policy.gauges()
    assert set(g) == {"policy_tracked_buffers",
                      "policy_prefetch_pending",
                      "policy_flow_window_bytes"}
    assert set(g) <= set(MN.TELEMETRY_GAUGES)
