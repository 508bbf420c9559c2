"""Data-movement counters, the Prometheus exposition and the serving
SLO histograms.

Coverage:
  * the four movement counters (h2dBytes, d2hBytes, wireBytes,
    iciBytesMoved): at every site that counts one, the node's counter
    equals the bytes its batches' metadata says moved; ESSENTIAL records
    none of them;
  * prometheus round-trip property: random label values (quotes,
    backslashes, newlines, braces) and the serve histogram exposition
    (`_bucket`/`_sum`/`_count`) parse back exactly;
  * serving SLO histograms: deterministic percentiles, scheduler phase
    observation per priority class, fairness visibility through
    cluster_snapshot/prometheus_serve_dump, and the spill phase
    attributed to the query that spilled.
"""
from __future__ import annotations

import random
import string

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar import ColumnarBatch
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.exec import broadcast as BC
from spark_rapids_tpu.exec.base import CpuExec, ExecContext, TpuExec
from spark_rapids_tpu.exec.basic import (DeviceToHostExec, HostToDeviceExec,
                                         TpuScanMemoryExec)
from spark_rapids_tpu.exec.broadcast import TpuBroadcastExchangeExec
from spark_rapids_tpu.exec.distributed import (TpuDistributedAggregateExec,
                                               TpuDistributedJoinExec,
                                               TpuDistributedSortExec)
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.io.scan import TpuFileScanExec
from spark_rapids_tpu.metrics import names as MN
from spark_rapids_tpu.metrics.export import (_sample, parse_prometheus,
                                             prom_name,
                                             prometheus_serve_dump)
from spark_rapids_tpu.metrics.slo import (BUCKET_BOUNDS, PhaseHistogram,
                                          SloTracker)
from spark_rapids_tpu.parallel import distributed as PD
from spark_rapids_tpu.plan.logical import col, functions as F
from spark_rapids_tpu.types import Schema, StructField, from_arrow

pytestmark = pytest.mark.movement

N_ROWS = 40_000
D_1994, D_1995, D_19980902 = 8766, 9131, 10471
MOVEMENT = (MN.H2D_BYTES, MN.D2H_BYTES, MN.WIRE_BYTES, MN.ICI_BYTES_MOVED)
NO_SCAN_CACHE = {"spark.rapids.sql.tpu.memoryScanCache.enabled": "false"}
MESH4 = {"spark.rapids.sql.tpu.mesh.devices": "4"}


def _lineitem(n=N_ROWS):
    rng = np.random.RandomState(42)
    return pa.table({
        "l_extendedprice": rng.uniform(900.0, 105000.0, n),
        "l_discount": rng.choice(np.arange(0.0, 0.11, 0.01), n),
        "l_quantity": rng.randint(1, 51, n).astype(np.float64),
        "l_shipdate": rng.randint(8035, 10592, n).astype(np.int64),
        "l_returnflag": np.array(["A", "N", "R"])[rng.randint(0, 3, n)],
        "l_linestatus": np.array(["F", "O"])[rng.randint(0, 2, n)],
        "l_tax": np.round(rng.uniform(0.0, 0.08, n), 2),
    })


_TABLE = _lineitem()


def _session(extra=None):
    conf = {"spark.rapids.sql.variableFloatAgg.enabled": "true"}
    conf.update(extra or {})
    return TpuSession(conf)


def _q6(df):
    return (df.filter((col("l_shipdate") >= D_1994)
                      & (col("l_shipdate") < D_1995)
                      & (col("l_discount") >= 0.05)
                      & (col("l_discount") <= 0.07)
                      & (col("l_quantity") < 24))
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


# --------------------------------------------------------------------------
# data-movement counters, one case a site that counts one
# --------------------------------------------------------------------------

def _schema_of(table) -> Schema:
    return Schema([StructField(f.name, from_arrow(f.type))
                   for f in table.schema])


_SMALL = _TABLE.slice(0, 3000)
_SMALL_SCHEMA = _schema_of(_SMALL)


class _DeviceSource(TpuExec):
    """Yields the device batches it was given."""

    def __init__(self, batches):
        super().__init__()
        self.batches = batches

    @property
    def schema(self):
        return _SMALL_SCHEMA

    def execute(self, ctx):
        yield from self.batches


class _HostSource(CpuExec):
    """Yields the arrow tables it was given."""

    def __init__(self, tables):
        super().__init__()
        self.tables = tables

    @property
    def schema(self):
        return _SMALL_SCHEMA

    def execute_cpu(self, ctx):
        yield from self.tables


def _nbytes(batches) -> int:
    return sum(b.device_size_bytes() for b in batches)


def _counters(node) -> dict:
    vals = node.metrics.snapshot()
    return {k: vals.get(k, 0) for k in MOVEMENT}


def _only(nodes, cls):
    [node] = [n for n in nodes if isinstance(n, cls)]
    return node


def _spy_yields(monkeypatch, cls, method):
    """Sum, per instance, the metadata size of every device batch the
    instance's `method` yields (a pair's second item for partitions)."""
    seen = {}
    orig = getattr(cls, method)

    def spied(self, *args, **kwargs):
        for item in orig(self, *args, **kwargs):
            b = item[1] if isinstance(item, tuple) else item
            if b is not None:
                seen[id(self)] = seen.get(id(self), 0) \
                    + b.device_size_bytes()
            yield item
    monkeypatch.setattr(cls, method, spied)
    return seen


def _memory_scan(monkeypatch, tmp_path):
    node = TpuScanMemoryExec(_SMALL, _SMALL_SCHEMA)
    out = list(node.execute(ExecContext(TpuConf({
        **NO_SCAN_CACHE, "spark.rapids.sql.reader.batchSizeRows": "1024"}))))
    assert len(out) == 3
    return node, {MN.H2D_BYTES: _nbytes(out)}


def _parquet_scan(monkeypatch, tmp_path):
    path = tmp_path / "lineitem.parquet"
    pq.write_table(_SMALL, path, row_group_size=1000)
    seen = _spy_yields(monkeypatch, TpuFileScanExec, "execute")
    s = _session()
    s.read.parquet(str(path)).filter(col("l_quantity") < 24).collect()
    node = _only(s.last_execution.nodes, TpuFileScanExec)
    return node, {MN.H2D_BYTES: seen[id(node)]}


def _host_to_device(monkeypatch, tmp_path):
    node = HostToDeviceExec(_HostSource([_SMALL.slice(0, 1000),
                                         _SMALL.slice(1000)]))
    out = list(node.execute(ExecContext(TpuConf())))
    return node, {MN.H2D_BYTES: _nbytes(out)}


def _device_to_host(monkeypatch, tmp_path):
    batches = [ColumnarBatch.from_arrow(_SMALL.slice(0, 1000)),
               ColumnarBatch.from_arrow(_SMALL.slice(1000))]
    node = DeviceToHostExec(_DeviceSource(batches))
    assert sum(t.num_rows for t in node.execute_cpu(
        ExecContext(TpuConf()))) == _SMALL.num_rows
    return node, {MN.D2H_BYTES: _nbytes(batches)}


def _broadcast(monkeypatch, tmp_path):
    shipped = []
    orig = BC.batch_to_host

    def spied(batch):
        leaves, meta = orig(batch)
        shipped.append(meta.size_bytes)
        return leaves, meta
    monkeypatch.setattr(BC, "batch_to_host", spied)
    s = _session()
    dim = s.from_pydict({"l_returnflag": ["A", "N", "R"], "w": [1, 2, 3]})
    s.from_arrow(_SMALL).join(dim, on="l_returnflag").collect()
    node = _only(s.last_execution.nodes, TpuBroadcastExchangeExec)
    [nbytes] = shipped
    return node, {MN.D2H_BYTES: nbytes, MN.WIRE_BYTES: nbytes}


def _socket_exchange(monkeypatch, extra):
    """The socket-tier exchange: its map side left the device and went on
    the wire (its map statistics' bytes), its read side came off the wire
    and back up the link (the partition batches it yields)."""
    read = _spy_yields(monkeypatch, TpuShuffleExchangeExec,
                       "execute_partitions")
    s = _session({"spark.rapids.sql.reader.batchSizeRows": "1024",
                  **extra})
    s.from_arrow(_SMALL).repartition(4, col("l_returnflag")).collect()
    node = _only(s.last_execution.nodes, TpuShuffleExchangeExec)
    written = node.metrics.snapshot()[MN.MAP_OUTPUT_BYTES]
    return node, {MN.D2H_BYTES: written, MN.H2D_BYTES: read[id(node)],
                  MN.WIRE_BYTES: written + read[id(node)]}


def _exchange_map(monkeypatch, tmp_path):
    return _socket_exchange(monkeypatch, {})


def _exchange_read(monkeypatch, tmp_path):
    # two in-process executors: reads cross the loopback wire
    return _socket_exchange(
        monkeypatch, {"spark.rapids.sql.tpu.cluster.executors": "2"})


def _mesh_exchange(monkeypatch, tmp_path):
    s = _session({**MESH4, "spark.rapids.sql.reader.batchSizeRows": "1024"})
    s.from_arrow(_SMALL).repartition(4, col("l_returnflag")).collect()
    node = _only(s.last_execution.nodes, TpuShuffleExchangeExec)
    vals = node.metrics.snapshot()
    assert vals[MN.NUM_ICI_EXCHANGES] == 1
    return node, {MN.ICI_BYTES_MOVED: vals[MN.MAP_OUTPUT_BYTES]}


def _spmd(monkeypatch, cls, query, extra=None):
    """The SPMD operator's counter against every row exchange's bytes as
    parallel/distributed.exchange_ici_bytes computes them."""
    exchanged = []
    orig = PD.exchange_ici_bytes

    def spied(*args, **kwargs):
        exchanged.append(orig(*args, **kwargs))
        return exchanged[-1]
    monkeypatch.setattr(PD, "exchange_ici_bytes", spied)
    s = _session({**MESH4, **(extra or {})})
    query(s).collect()
    node = _only(s.last_execution.nodes, cls)
    assert exchanged and all(b > 0 for b in exchanged)
    return node, {MN.ICI_BYTES_MOVED: sum(exchanged)}


def _spmd_aggregate(monkeypatch, tmp_path):
    return _spmd(monkeypatch, TpuDistributedAggregateExec,
                 lambda s: s.from_arrow(_SMALL).group_by("l_returnflag")
                 .agg(F.sum(col("l_quantity")).alias("q")))


def _spmd_join(monkeypatch, tmp_path):
    def query(s):
        dim = s.from_pydict({"l_returnflag": ["A", "N", "R"],
                             "w": [1, 2, 3]})
        return s.from_arrow(_SMALL).join(dim, on="l_returnflag")
    return _spmd(monkeypatch, TpuDistributedJoinExec, query,
                 {"spark.sql.autoBroadcastJoinThreshold": "-1"})


def _spmd_sort(monkeypatch, tmp_path):
    return _spmd(monkeypatch, TpuDistributedSortExec,
                 lambda s: s.from_arrow(_SMALL).order_by("l_extendedprice"))


_SITES = {
    "memory_scan_h2d": _memory_scan,
    "parquet_scan_h2d": _parquet_scan,
    "host_to_device_h2d": _host_to_device,
    "device_to_host_d2h": _device_to_host,
    "broadcast_d2h_wire": _broadcast,
    "exchange_map_d2h_wire": _exchange_map,
    "exchange_read_wire_h2d": _exchange_read,
    "mesh_exchange_ici": _mesh_exchange,
    "spmd_aggregate_ici": _spmd_aggregate,
    "spmd_join_ici": _spmd_join,
    "spmd_sort_ici": _spmd_sort,
}


@pytest.mark.parametrize("site", sorted(_SITES))
def test_movement_counters_match_the_bytes_moved(site, monkeypatch,
                                                 tmp_path):
    node, want = _SITES[site](monkeypatch, tmp_path)
    assert all(v > 0 for v in want.values()), want
    # a counter the site does not name stays at zero
    assert _counters(node) == {k: want.get(k, 0) for k in MOVEMENT}


def test_essential_level_records_no_cost_metrics():
    s = _session({"spark.rapids.sql.tpu.metrics.level": "ESSENTIAL",
                  **NO_SCAN_CACHE})
    df = s.from_arrow(_TABLE)
    _q6(df).collect()
    for node in s.last_execution.nodes:
        assert not any(_counters(node).values()), node.name


# --------------------------------------------------------------------------
# prometheus round-trip property
# --------------------------------------------------------------------------

_NASTY = '"\\{}\n,=x '


def test_parse_prometheus_roundtrip_property():
    rng = random.Random(1234)
    for _ in range(200):
        labels = {}
        for _k in range(rng.randint(0, 4)):
            name = "l" + "".join(rng.choices(string.ascii_lowercase, k=4))
            value = "".join(rng.choices(_NASTY + string.ascii_letters,
                                        k=rng.randint(0, 12)))
            labels[name] = value
        value = rng.choice([0.0, 1.5, -3.25, 1e18, 7])
        line = _sample("spark_rapids_tpu_test_total", labels,
                       value) if labels else \
            f"spark_rapids_tpu_test_total {float(value):g}"
        parsed = parse_prometheus(line)
        assert len(parsed) == 1
        (name, got_labels), got_value = next(iter(parsed.items()))
        assert name == "spark_rapids_tpu_test_total"
        assert dict(got_labels) == labels
        assert got_value == pytest.approx(float(value))


def test_parse_prometheus_rejects_malformed():
    for bad in ('metric{a="b} 1', "metric 1 2 3", "metric{a=b} 1",
                'metric{a="b"} notanumber', '{x="y"} 1'):
        with pytest.raises(ValueError):
            parse_prometheus(bad)
    # comments and blank lines are fine
    assert parse_prometheus("# HELP x y\n\n# TYPE x counter\n") == {}


def test_prometheus_histogram_dump_roundtrip():
    tracker = SloTracker()
    rng = random.Random(7)
    observed = {}
    for _ in range(300):
        phase = rng.choice(("queue", "execute", "total"))
        prio = rng.choice(("0", "5"))
        tracker.observe(phase, prio, rng.uniform(0, 10))
        observed[(phase, prio)] = observed.get((phase, prio), 0) + 1

    class _FakeSched:
        slo = tracker

        def fairness_snapshot(self):
            return {"queue_depth_by_priority": {0: 2},
                    "admitted_by_priority": {0: 5, 5: 9},
                    "rejected_by_priority": {5: 1}}

    text = prometheus_serve_dump(_FakeSched())
    parsed = parse_prometheus(text)
    # every histogram's _count equals what we observed, and the +Inf
    # bucket equals the count (cumulative exposition invariant)
    for (phase, prio), n in observed.items():
        labels = frozenset({("phase", phase), ("priority", prio)})
        count = parsed[("spark_rapids_tpu_serve_phase_seconds_count",
                        labels)]
        assert count == n
        inf = parsed[("spark_rapids_tpu_serve_phase_seconds_bucket",
                      frozenset(set(labels) | {("le", "+Inf")}))]
        assert inf == n
        # buckets are monotonically non-decreasing in le order
        buckets = sorted(
            ((float(dict(k[1])["le"]) if dict(k[1])["le"] != "+Inf"
              else float("inf")), v)
            for k, v in parsed.items()
            if k[0].endswith("_bucket") and dict(k[1]).get("phase") ==
            phase and dict(k[1]).get("priority") == prio)
        assert all(b1[1] <= b2[1]
                   for b1, b2 in zip(buckets, buckets[1:]))
    assert parsed[("spark_rapids_tpu_serve_admitted_total",
                   frozenset({("priority", "5")}))] == 9
    assert parsed[("spark_rapids_tpu_serve_admission_rejections_total",
                   frozenset({("priority", "5")}))] == 1


def test_query_prometheus_dump_includes_cost_metrics_and_parses():
    s = _session(NO_SCAN_CACHE)
    df = s.from_arrow(_TABLE)
    _q6(df).collect()
    parsed = parse_prometheus(s.last_execution.prometheus())
    [scan] = [n for n in s.last_execution.nodes
              if isinstance(n, TpuScanMemoryExec)]
    uploaded = scan.metrics.snapshot()[MN.H2D_BYTES]
    assert uploaded > 0
    assert parsed[(prom_name(MN.H2D_BYTES), frozenset({
        ("query", str(s.last_execution.query_id)),
        ("node", str(scan._node_id)), ("op", "TpuScanMemoryExec")}))] \
        == pytest.approx(uploaded, rel=1e-5)  # samples print as %g


# --------------------------------------------------------------------------
# SLO histograms + scheduler phases + fairness visibility
# --------------------------------------------------------------------------

def test_phase_histogram_percentiles_deterministic():
    h = PhaseHistogram()
    assert h.percentile(0.5) is None
    for v in (0.001, 0.002, 0.004, 0.008, 0.016, 0.032, 0.064, 0.128,
              0.256, 0.512):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 10
    assert snap["sum_s"] == pytest.approx(1.023, abs=1e-6)
    assert snap["max_s"] == pytest.approx(0.512)
    # p50 lands around the 5th/6th observation's bucket (~0.016-0.032),
    # p99 in the top bucket's range
    assert 0.004 <= snap["p50_s"] <= 0.064
    assert 0.256 <= snap["p99_s"] <= 0.512 + 1e-9
    # out-of-range huge value goes to the +Inf bucket, percentile capped
    h2 = PhaseHistogram()
    h2.observe(BUCKET_BOUNDS[-1] * 10)
    assert h2.percentile(0.99) <= h2.max


def test_scheduler_populates_slo_and_fairness():
    s = _session()
    df = s.from_arrow(_TABLE)
    futs = [s.submit(_q6(df), priority=(5 if i % 2 else 0))
            for i in range(4)]
    for f in futs:
        f.result(300)
    sched = s.scheduler
    stats = sched.stats()
    try:
        slo = stats["slo"]
        for phase in ("queue", "plan", "execute", "total"):
            assert phase in slo, slo.keys()
            assert sum(rec["count"] for rec in slo[phase].values()) == 4
        assert set(slo["total"].keys()) == {"0", "5"}
        # phase fields landed on the futures (engine fills them)
        for f in futs:
            assert f.exec_seconds is not None and f.exec_seconds > 0
            assert f.compile_seconds is not None
            assert f.spill_seconds is not None
        fair = stats["fairness"]
        assert fair["admitted_by_priority"] == {0: 2, 5: 2}
        assert fair["rejected_by_priority"] == {}
        # prometheus exposition of the same numbers parses
        parsed = parse_prometheus(sched.prometheus())
        assert parsed[("spark_rapids_tpu_serve_admitted_total",
                       frozenset({("priority", "0")}))] == 2
        assert any(k[0] == "spark_rapids_tpu_serve_phase_seconds_bucket"
                   for k in parsed)
    finally:
        s.shutdown_serving()


def test_cluster_snapshot_carries_serve_block():
    from spark_rapids_tpu.metrics.export import (cluster_snapshot,
                                                 prometheus_cluster_dump)
    s = _session({"spark.rapids.sql.tpu.cluster.executors": "2"})
    df = s.from_arrow(_TABLE)
    s.submit(_q6(df)).result(300)
    try:
        cluster = s.cluster
        assert cluster is not None
        snap = cluster_snapshot(cluster, scheduler=s.scheduler)
        assert "_serve" in snap
        assert snap["_serve"]["admitted_by_priority"] == {0: 1}
        # executors still report their transport/pool blocks
        workers = [k for k in snap if k != "_serve"]
        assert len(workers) >= 2
        for w in workers:
            assert "pool" in snap[w]
        text = prometheus_cluster_dump(cluster, scheduler=s.scheduler)
        parsed = parse_prometheus(text)
        assert parsed[("spark_rapids_tpu_serve_admitted_total",
                       frozenset({("priority", "0")}))] == 1
    finally:
        s.shutdown_serving()


def test_session_observability_carries_slo_block():
    from spark_rapids_tpu.metrics.export import session_observability
    s = _session()
    df = s.from_arrow(_TABLE)
    s.submit(_q6(df)).result(300)
    try:
        obs = session_observability(s)
        assert "scheduler" in obs
        assert "slo" in obs["scheduler"]
        assert "fairness" in obs["scheduler"]
    finally:
        s.shutdown_serving()


# --------------------------------------------------------------------------
# the spill phase
# --------------------------------------------------------------------------

def test_spill_phase_attributed_to_the_spilling_query_only():
    # the 'spill' phase comes from the query's OWN memory scope, not a
    # delta window over the SHARED runtime spillTime metric — a later
    # (or concurrent) query that never spilled must report 0 even
    # though the runtime's cumulative spillTime is already nonzero
    n = 120_000
    s = _session({
        "spark.rapids.memory.tpu.poolSizeBytes": str(2 << 20),
        "spark.rapids.memory.host.spillStorageSize": str(1 << 20),
        "spark.rapids.sql.batchSizeBytes": str(512 << 10),
        "spark.rapids.sql.reader.batchSizeRows": "16384",
        "spark.rapids.sql.tpu.memoryScanCache.enabled": "false",
        "spark.rapids.sql.tpu.serve.maxConcurrentQueries": "1",
        # keep the pressure scenario: the policy's early release frees
        # consumed shuffle partitions and this workload then fits the
        # 2MB pool without a single spill — which is the behavior under
        # test HERE, not the attribution
        "spark.rapids.sql.tpu.policy.earlyRelease.enabled": "false",
    })
    heavy_df = s.from_pydict({"v": [float(i % 977) for i in range(n)]})
    light_df = s.from_pydict({"x": [1.0, 2.0, 3.0]})
    try:
        heavy = s.submit(heavy_df.order_by(col("v")))
        heavy.result(600)
        pool = s.runtime.pool_stats()
        assert pool.get(MN.OOM_SPILL_RETRIES, 0) > 0, \
            "workload did not spill; shrink the pool"
        assert pool.get(MN.SPILL_TIME, 0.0) > 0
        assert heavy.spill_seconds is not None and heavy.spill_seconds > 0
        light = s.submit(light_df.agg(F.sum(col("x")).alias("s")))
        light.result(300)
        assert light.spill_seconds == 0.0, light.spill_seconds
    finally:
        s.shutdown_serving()


def test_spill_time_metric_registered_and_phase_shaped():
    # spillTime is catalog-registered as a MODERATE timer and feeds the
    # 'spill' SLO phase; a no-spill query records zero
    spec = MN.METRICS[MN.SPILL_TIME]
    assert spec.kind == MN.TIMER and spec.level == MN.MODERATE
    s = _session()
    df = s.from_arrow(_TABLE)
    s.submit(_q6(df)).result(300)
    try:
        slo = s.scheduler.stats()["slo"]
        assert "spill" in slo
        rec = next(iter(slo["spill"].values()))
        assert rec["count"] == 1
    finally:
        s.shutdown_serving()
