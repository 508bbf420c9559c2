"""Mesh-native ICI shuffle exchange tier (ISSUE 14).

The generic `TpuShuffleExchangeExec` lowers its map phase into jitted
`shard_map` collectives when the exchange runs over a device mesh
(shuffle/mesh_exchange.py).  This tier pins down the tier-parity
contract:

  * mesh vs socket bit-for-bit across hash / round_robin / single
    partitioning, every supported dtype (nullable + var-length strings),
    multi-batch children, and fused whole-stage chains;
  * AQE-on == AQE-off on both tiers, with IDENTICAL map-output
    statistics (rows, bytes, per-map slices) wherever the exchange ran —
    every adaptive rule must see the same numbers;
  * injectOom at every collective reserve site leaves results identical;
    full exhaustion DE-LOWERS to the socket tier (socket_fallbacks
    counted) and still matches the socket tier under the same fault;
  * the kill switch `spark.rapids.sql.tpu.shuffle.ici.enabled=false`
    makes the socket path byte-identical to a mesh-less session.

The conftest provisions 8 virtual CPU devices, so 4-device meshes run
in tier-1 without hardware.
"""
from __future__ import annotations

import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.exec.base import ExecContext
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.plan.logical import col, functions as F
from spark_rapids_tpu.utils import faults

from data_gen import gen_table

pytestmark = pytest.mark.mesh

MESH = {"spark.rapids.sql.tpu.mesh.devices": "4"}
ICI_OFF = {"spark.rapids.sql.tpu.shuffle.ici.enabled": "false"}
# small reader batches force MULTI-batch children: several map tasks per
# exchange, so map-id alignment across tiers is actually exercised
MULTI = {"spark.rapids.sql.reader.batchSizeRows": "256"}


def _assert_bit_equal(a, b, label):
    """Bit-for-bit table equality: float columns compare by BIT PATTERN
    (NaN payloads and signed zeros included — Arrow's `equals` treats
    NaN as unequal, which would let a value-mangling tier pass OR fail
    spuriously), everything else by Arrow equality."""
    import numpy as np
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


def _table(n=1500, seed=3):
    return {"k": [(i * 17) % 11 for i in range(n)],
            "v": [float(i) * 0.25 - 7.0 for i in range(n)],
            "s": [f"s{i % 29}" * (1 + i % 3) for i in range(n)]}


def _tiers(build, extra=None, check_counters=True):
    """Run `build(session) -> DataFrame` on the mesh tier, the
    kill-switched socket tier, and a mesh-less session; assert all three
    collect bit-for-bit and the tier counters tell the true story.
    Returns (mesh_session, mesh_table)."""
    def run(conf):
        s = TpuSession(conf)
        return s, build(s).to_arrow()
    conf = {**MESH, **(extra or {})}
    s_mesh, t_mesh = run(conf)
    _s_off, t_off = run({**conf, **ICI_OFF})
    _s_none, t_none = run({k: v for k, v in (extra or {}).items()})
    _assert_bit_equal(t_mesh, t_off, "mesh tier vs socket tier")
    _assert_bit_equal(t_mesh, t_none, "mesh plan vs mesh-less plan")
    if check_counters:
        from spark_rapids_tpu.metrics.export import session_observability
        obs = session_observability(s_mesh)
        assert obs["ici_exchanges"] > 0, obs
        assert obs["socket_fallbacks"] == 0, obs
        obs_off = session_observability(_s_off)
        assert obs_off["ici_exchanges"] == 0, obs_off
    return s_mesh, t_mesh


# --------------------------------------------------------------------------
# planning: the lowering decision is the planner's
# --------------------------------------------------------------------------

def test_distribute_stamps_ici_mesh_on_generic_exchanges():
    s = TpuSession(MESH)
    df = s.from_pydict(_table()).repartition(4, col("k"))
    phys = df.physical_plan()

    def find(n):
        if isinstance(n, TpuShuffleExchangeExec):
            return n
        for c in n.children:
            r = find(c)
            if r is not None:
                return r
        return None

    ex = find(phys)
    assert ex is not None, phys.tree_string()
    assert ex.ici_mesh is not None
    assert ex.ici_mesh.shape["data"] == 4
    # mesh-less plans carry no stamp
    ex2 = find(TpuSession().from_pydict(_table())
               .repartition(4, col("k")).physical_plan())
    assert ex2.ici_mesh is None


def test_range_exchange_never_lowers():
    """Range partitioning needs the bounds-sampling pass over the
    materialized child — it must stay on the socket tier even on a
    mesh (and global sort results stay identical)."""
    def q(s):
        return s.from_pydict(_table()).repartition_by_range(
            4, col("k"), col("v"))
    s_mesh, _ = _tiers(q, check_counters=False)
    from spark_rapids_tpu.metrics.export import session_observability
    assert session_observability(s_mesh)["ici_exchanges"] == 0


# --------------------------------------------------------------------------
# tier parity: partitioning modes, dtypes, fused chains
# --------------------------------------------------------------------------

def test_hash_exchange_parity_multibatch():
    _tiers(lambda s: s.from_pydict(_table()).repartition(4, col("k")),
           extra=MULTI)


def test_round_robin_exchange_parity_multibatch():
    _tiers(lambda s: s.from_pydict(_table()).repartition(8), extra=MULTI)


def test_single_partition_exchange_parity():
    _tiers(lambda s: s.from_pydict(_table()).repartition(1))


def test_partitions_neither_multiple_nor_divisor_of_mesh():
    """num_partitions (5) and mesh size (4) share no structure: the
    block owner mapping must still route every partition correctly."""
    _tiers(lambda s: s.from_pydict(_table()).repartition(5, col("k")),
           extra=MULTI)


ALL_DTYPES = [T.IntegerType, T.LongType, T.ShortType, T.ByteType,
              T.DoubleType, T.FloatType, T.BooleanType, T.StringType,
              T.DateType, T.TimestampType]


@pytest.mark.parametrize("dtype", ALL_DTYPES, ids=lambda d: d.name)
def test_exchange_parity_every_dtype(dtype):
    """Nullable columns of every supported dtype (var-length strings
    included) cross the collective bit-for-bit."""
    data, schema = gen_table(seed=7, n=400, k=(T.LongType, False),
                             v=dtype)

    def q(s):
        return s.from_pydict(data, schema).repartition(4, col("k"))

    _tiers(q, extra=MULTI)


def test_fused_chain_joins_the_collective():
    """A whole-stage chain under the exchange traces INTO the collective
    program (chain + partition ids + all-to-all, one compiled program) —
    and still matches the socket tier and fusion-off."""
    def q(s):
        df = s.from_pydict(_table())
        return (df.filter(col("v") > -5.0)
                .select(col("k"), (col("v") * 2.0).alias("w"), col("s"))
                .repartition(4, col("k")))

    s_mesh, t_mesh = _tiers(q, extra=MULTI)
    assert s_mesh.query_metrics_total.get("numFusedStages", 0) > 0
    s_nofuse = TpuSession({**MESH, **MULTI,
                           "spark.rapids.sql.tpu.fusion.enabled": "false"})
    _assert_bit_equal(q(s_nofuse).to_arrow(), t_mesh, "fusion off")


def test_full_join_exchange_pair_rides_mesh():
    """FULL joins stay single-chip on a mesh plan (distribute excludes
    them), so their planner-inserted exchange pair is exactly the
    generic-exchange case the lowering exists for."""
    def q(s):
        left = s.from_pydict({"k": [i % 9 for i in range(600)],
                              "v": [float(i) for i in range(600)]})
        right = s.from_pydict({"k": list(range(0, 18, 2)),
                               "name": [f"g{i}" for i in range(9)]})
        return (left.join(right, on="k", how="full")
                .order_by(col("k"), col("v"), col("name")))

    _tiers(q, extra={"spark.rapids.sql.tpu.join.partitioned.threshold":
                     "0",
                     "spark.sql.autoBroadcastJoinThreshold": "-1",
                     "spark.rapids.sql.tpu.shuffle.partitions": "4"},
           check_counters=False)


# --------------------------------------------------------------------------
# AQE: identical map statistics on both tiers
# --------------------------------------------------------------------------

def _materialized_handle(ici: bool, mode: str, n_parts: int = 5):
    conf = {**MESH, **MULTI}
    if not ici:
        conf.update(ICI_OFF)
    s = TpuSession(conf)
    df = s.from_pydict(_table())
    df = (df.repartition(n_parts, col("k")) if mode == "hash"
          else df.repartition(n_parts))
    phys = df.physical_plan()

    def find(n):
        if isinstance(n, TpuShuffleExchangeExec):
            return n
        return next((r for c in n.children
                     if (r := find(c)) is not None), None)

    ex = find(phys)
    assert ex is not None
    from spark_rapids_tpu.mem.runtime import TpuRuntime
    ctx = ExecContext(conf=s.conf, runtime=TpuRuntime(s.conf))
    return ex, ex.materialize(ctx)


@pytest.mark.parametrize("mode", ["hash", "round_robin"])
def test_map_stats_identical_across_tiers(mode):
    _, h_mesh = _materialized_handle(True, mode)
    _, h_sock = _materialized_handle(False, mode)
    assert getattr(h_mesh, "is_mesh", False)
    assert not getattr(h_sock, "is_mesh", False)
    a, b = h_mesh.stats(), h_sock.stats()
    assert a.rows_by_partition == b.rows_by_partition
    assert a.bytes_by_partition == b.bytes_by_partition
    assert a.map_bytes_by_partition == b.map_bytes_by_partition
    assert a.num_map_tasks == b.num_map_tasks
    assert a.num_map_tasks > 1, "child was single-batch; weak test"


def test_skew_slice_map_range_reads_match():
    """The AQE skew rule reads one partition restricted to a map-id
    range — both tiers must serve identical slices."""
    ex_m, h_mesh = _materialized_handle(True, "hash")
    ex_s, h_sock = _materialized_handle(False, "hash")
    n_maps = h_mesh.stats().num_map_tasks
    assert n_maps >= 2

    def rows(batches):
        out = []
        for b in batches:
            tb = b.to_arrow()
            out.extend(zip(*[tb.column(i).to_pylist()
                             for i in range(tb.num_columns)]))
        return out

    for p in range(h_mesh.num_partitions):
        for rng in (None, (0, 1), (1, n_maps)):
            assert rows(h_mesh.fetch(p, map_range=rng)) == \
                rows(h_sock.fetch(p, map_range=rng)), (p, rng)


def test_aqe_on_equals_aqe_off_on_both_tiers():
    """Coalesce fires over the mesh handle's device-side statistics and
    the result matches every other tier/AQE combination bit-for-bit."""
    def q(s):
        return (s.from_pydict(_table())
                .repartition(16, col("k"))
                .select(col("k"), (col("v") + 1.0).alias("v1")))

    outs = {}
    sessions = {}
    for ici in (True, False):
        for aqe in (True, False):
            conf = {**MESH, **MULTI,
                    "spark.rapids.sql.tpu.adaptive.enabled":
                        str(aqe).lower(),
                    "spark.rapids.sql.tpu.adaptive."
                    "advisoryPartitionSizeBytes": "1m",
                    "spark.rapids.sql.tpu.metrics.level": "DEBUG"}
            if not ici:
                conf.update(ICI_OFF)
            s = TpuSession(conf)
            outs[(ici, aqe)] = q(s).to_arrow()
            sessions[(ici, aqe)] = s
    base = outs[(False, False)]
    for k, t in outs.items():
        assert t.equals(base), f"{k} diverged"
    # the coalesce rule actually fired on the MESH tier's statistics
    assert sessions[(True, True)].query_metrics_total.get(
        "numCoalescedPartitions", 0) > 0
    # and the mesh map stage was journaled as the ici tier
    ev = [e for e in sessions[(True, True)].last_execution.journal.events()
          if e["kind"] == "stage" and e["name"] == "mapStage"]
    assert ev and all(e.get("tier") == "ici" for e in ev), ev


# --------------------------------------------------------------------------
# memory pressure: the collective re-enters the standard ladder
# --------------------------------------------------------------------------

def _mesh_query(extra=None):
    faults.INJECTOR.reset()
    conf = {**MESH, **MULTI}
    conf.update(extra or {})
    s = TpuSession(conf)
    out = (s.from_pydict(_table())
           .repartition(4, col("k"))
           .select(col("k"), col("v"), col("s"))
           .collect())
    return s, out


def test_inject_oom_every_collective_reserve_site_identical():
    _s, baseline = _mesh_query()
    n_ops = faults.INJECTOR.oom_ops
    sites = dict(faults.INJECTOR.site_counts)
    assert "exchange.collective" in sites, sites
    for ordinal in range(1, n_ops + 1):
        _s, out = _mesh_query({"spark.rapids.tpu.test.injectOom":
                               str(ordinal)})
        assert out == baseline, f"ordinal {ordinal} changed the result"
        assert faults.INJECTOR.injected_log, \
            f"ordinal {ordinal} never fired"


def test_collective_split_and_retry_identical():
    """A multi-failure window forces the row-range split of the map
    batch: split pieces re-run the collective under the SAME map id, so
    results AND map statistics stay correct."""
    _s, baseline = _mesh_query()
    s, out = _mesh_query({"spark.rapids.tpu.test.injectOom": "1x3",
                          "spark.rapids.memory.tpu.retry.maxRetries": "1"})
    assert out == baseline
    from spark_rapids_tpu.metrics.export import session_observability
    assert session_observability(s)["ici_exchanges"] > 0


def test_collective_exhaustion_delowers_to_socket_tier():
    """Terminal exhaustion inside the collective must DE-LOWER the
    exchange — counted, and identical to the socket tier under the
    exact same fault."""
    fault = {"spark.rapids.tpu.test.injectOom": "1x500",
             "spark.rapids.memory.tpu.retry.maxRetries": "0",
             "spark.rapids.memory.tpu.retry.maxSplitDepth": "0"}
    s_mesh, out_mesh = _mesh_query(fault)
    from spark_rapids_tpu.metrics.export import session_observability
    obs = session_observability(s_mesh)
    assert obs["socket_fallbacks"] > 0, obs
    assert obs["ici_exchanges"] == 0, obs
    _s, out_sock = _mesh_query({**fault, **ICI_OFF})
    assert out_mesh == out_sock


# --------------------------------------------------------------------------
# kill switch + observability surfaces
# --------------------------------------------------------------------------

def test_kill_switch_socket_path_byte_identical_to_meshless():
    s_off = TpuSession({**MESH, **MULTI, **ICI_OFF})
    s_none = TpuSession(dict(MULTI))
    q = lambda s: (s.from_pydict(_table())  # noqa: E731
                   .repartition(4, col("k")).to_arrow())
    assert q(s_off).equals(q(s_none))
    from spark_rapids_tpu.metrics.export import session_observability
    obs = session_observability(s_off)
    assert obs["ici_exchanges"] == 0 and obs["socket_fallbacks"] == 0


def test_roofline_ici_resource_and_collective_spans():
    """The lowered exchange declares its movement on the 'ici' roofline
    resource, every collective dispatch is journaled as a `collective`
    span, and the ledger attributes the node against the peakIci conf."""
    conf = {**MESH, **MULTI,
            "spark.rapids.sql.tpu.metrics.level": "DEBUG"}
    s = TpuSession(conf)
    s.from_pydict(_table()).repartition(4, col("k")).collect()
    tot = s.query_metrics_total
    assert tot.get("numIciExchanges", 0) > 0
    assert tot.get("iciBytesMoved", 0) > 0
    assert tot.get("collectiveTime", 0) > 0
    qe = s.last_execution
    spans = [e for e in qe.journal.events()
             if e["kind"] == "collective" and e["ev"] == "B"]
    assert spans, "no collective spans journaled"
    assert all("shuffle" in e and "devices" in e for e in spans)
    rows = qe.roofline_ledger()
    ici_rows = [r for r in rows if "ici" in r["cost"]]
    assert ici_rows, rows
    # peak override flows into the ledger denominators
    from spark_rapids_tpu.metrics.roofline import platform_peaks
    peaks = platform_peaks(conf=s.conf)
    assert "ici" in peaks and peaks["ici"] > 0


def test_coalesced_read_spans_devices():
    """AQE coalesces several tiny partitions into ONE spec; on the mesh
    tier those sub-batches live on DIFFERENT devices (partition p is
    device p's shard), and the coalesced concat must transfer — not
    crash or silently reshard (regression: eager dynamic_update_slice
    rejects mixed committed devices)."""
    data = {"k": [i % 7 for i in range(4000)],
            "v": [float(i) * 0.5 for i in range(4000)]}

    def q(s):
        return (s.from_pydict(data)
                .filter(col("v") > 10.0)
                .repartition(4, col("k"))
                .group_by("k").agg(F.sum(col("v")).alias("sv"))
                .order_by(col("k")))

    conf = {**MESH, "spark.rapids.sql.variableFloatAgg.enabled": "true"}
    s = TpuSession(conf)  # adaptive ON by default: the coalesce fires
    got = q(s).to_arrow()
    oracle = q(TpuSession({"spark.rapids.sql.enabled": "false"})
               ).to_arrow()
    assert got.equals(oracle)
    from spark_rapids_tpu.metrics.export import session_observability
    assert session_observability(s)["ici_exchanges"] > 0


def test_plan_cache_variants_replay_one_collective():
    """Serving-tier literal variants: the plan cache lifts the filter
    literal into a Parameter, which must thread INTO the collective
    program as a traced argument — submission 2 replays submission 1's
    compiled collective (zero new stage compiles) and still computes
    with ITS OWN literal."""
    from spark_rapids_tpu.utils import kernel_cache as KC
    data = {"k": [i % 7 for i in range(4000)],
            "v": [float(i) * 0.5 for i in range(4000)]}
    conf = {**MESH, "spark.rapids.sql.variableFloatAgg.enabled": "true"}
    s = TpuSession(conf)

    def q(sess, thresh):
        return (sess.from_pydict(data)
                .filter(col("v") > thresh)
                .repartition(4, col("k"))
                .group_by("k").agg(F.sum(col("v")).alias("sv"))
                .order_by(col("k")))

    r1 = s.submit(q(s, 10.0)).result()
    before = KC.stats()["stage_compiles"]
    r2 = s.submit(q(s, 500.0)).result()
    compiled = KC.stats()["stage_compiles"] - before
    oracle = TpuSession({"spark.rapids.sql.enabled": "false"})
    assert r1.equals(q(oracle, 10.0).to_arrow())
    assert r2.equals(q(oracle, 500.0).to_arrow())
    assert compiled == 0, \
        f"literal variant re-compiled {compiled} stage programs"
    from spark_rapids_tpu.metrics.export import session_observability
    assert session_observability(s)["ici_exchanges"] >= 2


def test_aggregate_over_exchange_parity():
    """A reduce side consuming the lowered exchange's partitions (the
    exchange feeds a grouped aggregate that stays single-chip because it
    is offset-free but the plan keeps the explicit repartition)."""
    def q(s):
        return (s.from_pydict(_table())
                .repartition(4, col("k"))
                .group_by("k")
                .agg(F.sum(col("v")).alias("sv"),
                     F.count(col("v")).alias("c"))
                .order_by(col("k")))

    _tiers(q, extra={**MULTI,
                     "spark.rapids.sql.variableFloatAgg.enabled": "true"},
           check_counters=False)


def test_stage_executable_is_keyed_by_placement():
    """After a mesh exchange partition i lives on device i.  An AOT
    executable is compiled for its inputs' shardings and rejects the same
    shapes on another device ("compiled for input shardings that
    disagree"), so placement is part of the whole-stage cache key: found
    by the 4-device rehearsal of chip_smoke.py at SF1, where partitions
    0 and 1 first had equal capacities."""
    import jax
    import numpy as np

    from spark_rapids_tpu.utils import kernel_cache as KC
    key = ("placement-regression",)

    def double():
        return lambda v: v * 2
    # kernel_cache names a program after the layer its builder's module
    # belongs to; this one stands in for the mesh exchange's
    double.__module__ = "spark_rapids_tpu.shuffle.mesh_exchange"
    before = KC.stats()["stage_compiles"]
    outs = []
    for d in jax.devices()[:2]:
        x = jax.device_put(np.arange(8.0), d)
        fn = KC.stage_executable(key, double, (x,),
                                 name="placement")
        outs.append(fn(x))
    assert [next(iter(o.devices())).id for o in outs] == \
        [d.id for d in jax.devices()[:2]]
    assert KC.stats()["stage_compiles"] == before + 2
    # same device again: a hit, not a third compile
    x = jax.device_put(np.arange(8.0), jax.devices()[1])
    KC.stage_executable(key, double, (x,),
                        name="placement")(x)
    assert KC.stats()["stage_compiles"] == before + 2
