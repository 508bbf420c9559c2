"""Metrics subsystem tier: registry gating, batched lazy fold, the
metric-name lint, per-operator metrics vs the CPU oracle, journal schema
round-trip, and Prometheus export parsing (ISSUE 2 satellites)."""
import glob
import os

import jax.numpy as jnp
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.metrics import names as N
from spark_rapids_tpu.metrics import registry as R
from spark_rapids_tpu.metrics.export import (parse_prometheus,
                                             prometheus_dump)
from spark_rapids_tpu.metrics.journal import (EventJournal, read_journal,
                                              validate_events)
from spark_rapids_tpu.plan.logical import col, functions as F, lit

pytestmark = pytest.mark.observability

# streaming (non-whole-stage) partitioned join + grouped agg + global sort:
# every operator executes its own path, so per-operator metrics are live
_SLICE_CONF = {
    "spark.rapids.sql.tpu.wholeStage.enabled": "false",
    "spark.rapids.sql.tpu.join.partitioned.threshold": "1",
    "spark.sql.autoBroadcastJoinThreshold": "-1",
    "spark.rapids.sql.tpu.shuffle.partitions": "4",
    "spark.rapids.sql.variableFloatAgg.enabled": "true",
}


def _slice_session(extra=None):
    conf = dict(_SLICE_CONF)
    conf.update(extra or {})
    s = TpuSession(conf)
    n = 300
    fact = s.from_pydict({"k": [i % 5 for i in range(n)],
                          "v": [float(i) for i in range(n)],
                          "q": [i % 3 for i in range(n)]})
    dim = s.from_pydict({"k": list(range(5)),
                         "name": [f"g{j}" for j in range(5)]})
    df = (fact.join(dim, on="k")
          .filter(col("q") < 2)
          .group_by(col("name"))
          .agg(F.sum(col("v")).alias("sv"),
               F.count(lit(1)).alias("c"))
          .order_by(col("name")))
    return s, df


# --------------------------------------------------------------------------
# registry unit tier
# --------------------------------------------------------------------------

def test_level_gating_drops_higher_levels():
    m = R.Metrics(level=N.ESSENTIAL)
    m.add(N.NUM_OUTPUT_ROWS, 5)          # ESSENTIAL: kept
    m.add(N.TOTAL_TIME, 1.0)             # MODERATE: dropped
    m.set_max(N.PEAK_DEV_MEMORY, 100)    # DEBUG: dropped
    with m.timer(N.SORT_TIME):           # MODERATE: no-op timer
        pass
    assert m.values == {N.NUM_OUTPUT_ROWS: 5}


def test_debug_sync_gated_and_counted():
    before = R.DEVICE_SYNCS.count
    m = R.Metrics(level=N.MODERATE)
    m.add_sync(N.NUM_OUTPUT_ROWS, lambda: 1 / 0)  # thunk must NOT run
    assert R.DEVICE_SYNCS.count == before
    m.configure(N.DEBUG)
    m.add_sync(N.NUM_OUTPUT_ROWS, lambda: 7)
    assert R.DEVICE_SYNCS.count == before + 1
    assert m.values[N.NUM_OUTPUT_ROWS] == 7


def test_set_max_keeps_high_water_mark():
    m = R.Metrics(level=N.DEBUG)
    m.set_max(N.PEAK_DEV_MEMORY, 10)
    m.set_max(N.PEAK_DEV_MEMORY, 5)
    m.set_max(N.PEAK_DEV_MEMORY, 20)
    assert m.values[N.PEAK_DEV_MEMORY] == 20


def test_lazy_fold_batches_device_scalars():
    """add_lazy scalars (mixed names/dtypes) fold to exact sums and drain
    the pending lists; folding twice must not double-count."""
    m = R.Metrics(level=N.MODERATE)
    for i in range(10):
        m.add_lazy(N.NUM_OUTPUT_ROWS, jnp.sum(jnp.ones(i + 1, jnp.int32)))
    m.add_lazy(N.DATA_SIZE, jnp.asarray(256, jnp.int64))
    m.add(N.NUM_OUTPUT_ROWS, 1)  # eager adds coexist with lazy
    v1 = dict(m.values)
    assert v1[N.NUM_OUTPUT_ROWS] == 1 + sum(range(1, 11))
    assert v1[N.DATA_SIZE] == 256
    assert dict(m.values) == v1  # idempotent re-read
    assert all(not p for p in m._lazy.values())


def test_unregistered_name_recorded_but_flagged():
    m = R.Metrics(level=N.ESSENTIAL)
    m.add("numOutputRow", 1)  # the classic typo
    assert m.values["numOutputRow"] == 1
    assert "numOutputRow" in R.UNREGISTERED_SEEN
    R.UNREGISTERED_SEEN.discard("numOutputRow")


def test_parse_level():
    assert R.parse_level("essential") == N.ESSENTIAL
    assert R.parse_level("DEBUG") == N.DEBUG
    with pytest.raises(ValueError):
        R.parse_level("verbose")


# --------------------------------------------------------------------------
# metric-name lint (satellite: typo'd keys fail here, not in prod)
# --------------------------------------------------------------------------

def test_every_emitted_metric_name_is_registered():
    # migrated to the tpulint framework (TPU004): AST-based, so wrapped
    # calls and journal kinds are covered too; `python -m
    # spark_rapids_tpu.metrics --lint` delegates to the same pass
    import os

    import spark_rapids_tpu
    from spark_rapids_tpu.lint.core import lint_paths
    from spark_rapids_tpu.lint.passes.contracts import ContractsPass
    pkg = os.path.dirname(spark_rapids_tpu.__file__)
    cp = ContractsPass()
    result = lint_paths(paths=[pkg], passes=[cp])
    # floor = a sanity check that the scanner still finds literal-name
    # sites at all (PR-3 unified the exchange read paths, dropping one
    # duplicated "exchangeFetch" retry-block site)
    assert cp.emission_sites >= 18, \
        "lint scanner found suspiciously few emission sites"
    assert not result.findings, \
        f"metric/journal contract findings: {result.findings}"


def test_no_unregistered_names_after_query_slice():
    R.UNREGISTERED_SEEN.clear()
    _s, df = _slice_session()
    df.collect()
    assert R.UNREGISTERED_SEEN == set(), \
        f"operators emitted unregistered metric names: {R.UNREGISTERED_SEEN}"


# --------------------------------------------------------------------------
# per-operator metrics vs the CPU oracle (join+agg+sort slice)
# --------------------------------------------------------------------------

def test_operator_metrics_match_cpu_oracle():
    s, df = _slice_session()
    rows = df.collect()
    oracle_s, oracle_df = _slice_session(
        {"spark.rapids.sql.enabled": "false"})
    oracle = oracle_df.collect()
    assert rows == oracle
    qe = s.last_execution
    by_op = {}
    for rec in qe.node_metrics():
        by_op.setdefault(rec["op"], []).append(rec["metrics"])
    # exact row counts where the oracle pins them
    root = qe.node_metrics()[0]
    assert root["op"] == "DeviceToHostExec"
    assert root["metrics"][N.NUM_OUTPUT_ROWS] == len(oracle)
    sort_rows = sum(m.get(N.NUM_OUTPUT_ROWS, 0)
                    for m in by_op["TpuSortExec"])
    assert sort_rows == len(oracle)
    agg_rows = sum(m.get(N.NUM_OUTPUT_ROWS, 0)
                   for m in by_op["TpuHashAggregateExec"])
    assert agg_rows == len(oracle)
    # timers positive at MODERATE (the default level)
    assert sum(m.get(N.SORT_TIME, 0) for m in by_op["TpuSortExec"]) > 0
    assert sum(m.get(N.COMPUTE_AGG_TIME, 0)
               for m in by_op["TpuHashAggregateExec"]) > 0
    # DEBUG-only metrics absent at MODERATE
    for recs in by_op.values():
        for m in recs:
            assert N.PEAK_DEV_MEMORY not in m


def test_debug_metrics_absent_at_essential():
    s, df = _slice_session(
        {"spark.rapids.sql.tpu.metrics.level": "ESSENTIAL"})
    df.collect()
    for rec in s.last_execution.node_metrics():
        for name in rec["metrics"]:
            spec = N.METRICS.get(name)
            assert spec is not None and spec.level == N.ESSENTIAL, \
                f"{name} leaked through the ESSENTIAL gate on {rec['op']}"


# --------------------------------------------------------------------------
# journal schema round-trip
# --------------------------------------------------------------------------

def test_journal_roundtrip_file(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = EventJournal(path, query_id=1)
    q = j.begin("query", "query-1")
    with j.span("operator", "TpuSortExec", parent=q, node=1):
        j.instant("retry", "sort", action="retry", attempt=1)
    j.instant("metric", "TpuSortExec", parent=q, node=1,
              metrics={"numOutputRows": 3})
    j.end(q)
    j.close()
    events = read_journal(path)
    assert events == j.events()
    assert validate_events(events) == []
    kinds = [e["kind"] for e in events]
    assert kinds == ["query", "operator", "retry", "operator", "metric",
                     "query"]
    # parent links resolve to earlier span ids
    op_b = events[1]
    assert op_b["parent"] == events[0]["id"]


def test_journal_dangling_span_closed_on_close():
    j = EventJournal()
    j.begin("operator", "leaky")
    j.close()
    events = j.events()
    assert events[-1]["ev"] == "E" and events[-1].get("dangling")
    assert validate_events(events) == []


def test_journal_dir_conf_writes_file(tmp_path):
    jdir = str(tmp_path / "journals")
    s, df = _slice_session(
        {C.METRICS_JOURNAL_DIR.key: jdir})
    df.collect()
    files = glob.glob(os.path.join(jdir, "query-*.jsonl"))
    assert len(files) == 1
    events = read_journal(files[0])
    assert validate_events(events) == []
    # file journals open with a wall-clock anchor record so driver query
    # spans align with worker trace shards offline (metrics/timeline.py)
    assert events[0]["ev"] == "A"
    assert events[0]["wall_ns"] > 0 and events[0]["mono_ns"] > 0
    spans = [e for e in events if e["ev"] != "A"]
    assert spans[0]["kind"] == "query" and spans[0]["ev"] == "B"
    assert any(e["kind"] == "operator" for e in spans)


# --------------------------------------------------------------------------
# Prometheus export
# --------------------------------------------------------------------------

def test_prometheus_dump_parses_and_matches_metrics():
    s, df = _slice_session()
    rows = df.collect()
    qe = s.last_execution
    text = prometheus_dump(qe)
    parsed = parse_prometheus(text)
    assert parsed, "empty prometheus dump"
    # root numOutputRows sample agrees with the collected row count
    root_key = ("spark_rapids_tpu_num_output_rows",
                frozenset([("query", str(qe.query_id)), ("node", "0"),
                           ("op", "DeviceToHostExec")]))
    assert parsed[root_key] == len(rows)
    # timers exported in seconds with the _seconds suffix, typed gauge
    assert any(k[0].endswith("_seconds") for k in parsed)
    for line in text.splitlines():
        if line.startswith("# TYPE") and "_seconds" in line:
            assert line.endswith("gauge")


def test_prometheus_label_escaping():
    from spark_rapids_tpu.metrics.export import _sample
    line = _sample("m", {"op": 'a"b\\c'}, 1.0)
    assert line == 'm{op="a\\"b\\\\c"} 1'


# --------------------------------------------------------------------------
# cluster-wide aggregation (in-process rpc-shaped path)
# --------------------------------------------------------------------------

def test_cluster_snapshot_in_process():
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.metrics.export import (cluster_snapshot,
                                                 prometheus_cluster_dump)
    from spark_rapids_tpu.plugin import TpuCluster
    cluster = TpuCluster(TpuConf({C.CLUSTER_EXECUTORS.key: "2"}))
    try:
        snap = cluster_snapshot(cluster)
        assert sorted(snap) == ["exec-0", "exec-1"]
        for rec in snap.values():
            assert rec["pool"]["pool_limit"] > 0
        text = prometheus_cluster_dump(cluster)
        parsed = parse_prometheus(text)
        assert ("spark_rapids_tpu_pool_limit",
                frozenset([("executor", "exec-0")])) in parsed
    finally:
        cluster.shutdown()


def test_proc_cluster_pool_stats_rpc():
    """pool_stats crosses the control RPC (the cluster half of the
    monitoring story); spawns one real CPU worker process."""
    from spark_rapids_tpu.cluster import ProcCluster
    cluster = ProcCluster(1, cpu=True)
    try:
        snap = cluster.observability_snapshot()
        assert snap["exec-0"]["pool"]["pool_limit"] > 0
        assert "bytes_sent" in snap["exec-0"]["transport"] or \
            snap["exec-0"]["transport"] == {}
        stats = cluster.pool_stats()
        assert stats["exec-0"]["device_used"] >= 0
    finally:
        cluster.shutdown()


# --------------------------------------------------------------------------
# trace emitter
# --------------------------------------------------------------------------

def test_chrome_trace_from_journal(tmp_path):
    import json
    from spark_rapids_tpu.utils.tracing import write_chrome_trace
    j = EventJournal()
    q = j.begin("query", "query-9")
    with j.span("operator", "TpuSortExec", parent=q):
        j.instant("spill", "oomSpill", spilled_bytes=123)
    j.end(q)
    j.close()
    path = write_chrome_trace(j.events(), str(tmp_path / "t.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    phases = [e["ph"] for e in evs if e["ph"] != "M"]
    assert phases == ["B", "B", "i", "E", "E"]
    by_ph = [e for e in evs if e["ph"] == "i"]
    assert by_ph[0]["args"]["spilled_bytes"] == 123


def test_bench_observability_shape():
    """The session's observability rollup: keys present and
    integer-valued."""
    from spark_rapids_tpu.metrics.export import session_observability
    s, df = _slice_session()
    df.collect()
    obs = session_observability(s)
    for key in ("numCpuFallbacks", "retries", "splits", "spill_bytes",
                "wire_bytes_sent", "wire_bytes_received", "queries"):
        assert key in obs and isinstance(obs[key], int), key
    assert obs["queries"] >= 1


def test_lazy_fold_takes_scalars_from_several_devices():
    """After a mesh exchange partition i's row count lives on device i;
    the fold must not stack scalars across devices (found by the
    4-device rehearsal of chip_smoke.py at SF1: the fold raised
    "incompatible devices" and the query's metrics were lost)."""
    import jax
    import numpy as np

    from spark_rapids_tpu.metrics import names as MN
    from spark_rapids_tpu.metrics.registry import Metrics
    m = Metrics()
    for i, d in enumerate(jax.devices()[:4]):
        m.add_lazy(MN.NUM_OUTPUT_ROWS, jax.device_put(np.int32(10 + i), d))
    m.add_lazy(MN.NUM_OUTPUT_ROWS, jax.numpy.int32(5))
    assert m.values[MN.NUM_OUTPUT_ROWS] == 10 + 11 + 12 + 13 + 5
