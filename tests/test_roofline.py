"""Roofline-attribution profiler (ISSUE 13).

Coverage:
  * roofline model units: platform peaks + conf overrides, attribution
    math (bottleneck = argmax lower-bound, utilization), expression
    flop estimates, span self-time extraction;
  * cost-declaration coverage: the q1/q6 representative shapes produce
    a ledger naming a bottleneck resource for EVERY plan node, live and
    offline (`python -m spark_rapids_tpu.metrics roofline`);
  * profile-tree invariants: op-row attributed bytes never exceed the
    parent whole-stage declaration; every node carrying a cost
    declaration appears in the ledger with a non-host bottleneck;
  * prometheus round-trip property: random label values (quotes,
    backslashes, newlines, braces) and the serve histogram exposition
    (`_bucket`/`_sum`/`_count`) parse back exactly;
  * serving SLO histograms: deterministic percentiles, scheduler phase
    observation per priority class, fairness visibility through
    cluster_snapshot/prometheus_serve_dump;
  * profiler overhead: cost accounting + ledger build ON vs the
    costAccounting kill switch on the q1 shape, asserted under a
    GENEROUS ceiling (the honest <5% target is recorded by the bench
    profile stage; a shared 1-core CI host jitters more than 2%).
"""
from __future__ import annotations

import json
import os
import random
import string
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.metrics import names as MN
from spark_rapids_tpu.metrics import roofline as RL
from spark_rapids_tpu.metrics.export import (_sample, parse_prometheus,
                                             prometheus_serve_dump)
from spark_rapids_tpu.metrics.slo import (BUCKET_BOUNDS, PhaseHistogram,
                                          SloTracker)
from spark_rapids_tpu.plan.logical import col, functions as F, lit

pytestmark = pytest.mark.roofline

N_ROWS = 40_000
D_1994, D_1995, D_19980902 = 8766, 9131, 10471


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


def _q1(df):
    disc = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (df.filter(col("l_shipdate") <= D_19980902)
            .group_by(col("l_returnflag"), col("l_linestatus"))
            .agg(F.sum(col("l_quantity")).alias("sum_qty"),
                 F.sum(disc).alias("sum_disc_price"),
                 F.avg(col("l_discount")).alias("avg_disc"),
                 F.count(lit(1)).alias("count_order"))
            .order_by("l_returnflag", "l_linestatus"))


# --------------------------------------------------------------------------
# model units
# --------------------------------------------------------------------------

def test_platform_peaks_defaults_and_conf_override():
    cpu = RL.platform_peaks("cpu")
    tpu = RL.platform_peaks("TPU v5 lite")
    assert set(RL.RESOURCES) <= set(cpu) and set(RL.RESOURCES) <= set(tpu)
    assert tpu["hbm"] == pytest.approx(819e9)
    assert tpu["flops"] == pytest.approx(197e12)
    # a device that is not in the table is an error, not a default
    with pytest.raises(KeyError, match="TPU v9"):
        RL.platform_peaks("TPU v9")
    s = _session({"spark.rapids.sql.tpu.roofline.peakHbmGBs": "123.5",
                  "spark.rapids.sql.tpu.roofline.peakWireGBs": "2.5"})
    over = RL.platform_peaks("cpu", conf=s.conf)
    assert over["hbm"] == pytest.approx(123.5e9)
    assert over["wire"] == pytest.approx(2.5e9)
    assert over["h2d"] == cpu["h2d"]  # untouched resources keep defaults


def test_attribute_bottleneck_and_utilization():
    peaks = {"hbm": 100e9, "h2d": 10e9, "wire": 1e9, "flops": 50e9,
             "d2h": 10e9}
    # 1 GB over hbm (0.01s lb), 0.05 GB over h2d (0.005s lb)
    att = RL.attribute({"hbm": 1e9, "h2d": 0.05e9}, seconds=0.1,
                       peaks=peaks)
    assert att["bottleneck"] == "hbm"
    assert att["utilization"] == pytest.approx(0.1)
    assert att["achieved"]["hbm"] == pytest.approx(1e10)
    # no declaration at all -> host-bound, no utilization
    empty = RL.attribute({}, seconds=0.5, peaks=peaks)
    assert empty["bottleneck"] == RL.HOST
    assert empty["utilization"] is None
    # unmeasured node still names its bottleneck from the declaration
    unmeasured = RL.attribute({"wire": 1e6}, seconds=None, peaks=peaks)
    assert unmeasured["bottleneck"] == "wire"
    assert unmeasured["utilization"] is None


def test_estimate_expr_flops_counts_interior_nodes():
    e = (col("l_extendedprice") * (lit(1.0) - col("l_discount")))
    from spark_rapids_tpu.plan.overrides import PlanMeta
    # logical ColumnExpr trees also expose .children; count directly
    n = RL.estimate_expr_flops([e])
    assert n >= 2  # Multiply + Subtract at minimum
    assert RL.estimate_expr_flops([]) == 0


def test_node_span_self_time_subtracts_children():
    # parent span [0, 100ns] with a child operator span [10, 60ns]:
    # parent self = 50ns, child self = 50ns
    events = [
        {"ts": 0, "ev": "B", "kind": "operator", "name": "p", "id": 1,
         "parent": None, "node": 0},
        {"ts": 10, "ev": "B", "kind": "operator", "name": "c", "id": 2,
         "parent": 1, "node": 1},
        {"ts": 60, "ev": "E", "kind": "operator", "name": "c", "id": 3,
         "parent": 1, "span": 2},
        {"ts": 100, "ev": "E", "kind": "operator", "name": "p", "id": 4,
         "parent": None, "span": 1},
    ]
    out = RL.node_span_seconds(events)
    assert out[0] == pytest.approx(50e-9)
    assert out[1] == pytest.approx(50e-9)


# --------------------------------------------------------------------------
# cost-declaration coverage: every plan node of q1/q6 names a bottleneck
# --------------------------------------------------------------------------

@pytest.mark.parametrize("build", [_q1, _q6], ids=["q1", "q6"])
def test_ledger_names_bottleneck_for_every_plan_node(build, tmp_path):
    s = _session({"spark.rapids.sql.tpu.metrics.journal.dir":
                  str(tmp_path)})
    df = s.from_arrow(_TABLE)
    build(df).collect()
    qe = s.last_execution
    ledger = qe.roofline_ledger()
    assert len(ledger) == len(qe.nodes)
    valid = set(RL.RESOURCES) | {RL.HOST}
    for row in ledger:
        assert row["bottleneck"] in valid, row
    # the heavy nodes are attributed to a real resource, not host
    real = [r for r in ledger if r["bottleneck"] != RL.HOST]
    assert real, ledger
    # measured seconds joined from the journal's operator spans
    assert any(r["seconds"] for r in ledger)
    # at least one node reports achieved-vs-peak utilization
    assert any(r["utilization_pct"] is not None for r in ledger)


def test_explain_with_metrics_carries_roofline_annotations():
    s = _session()
    df = s.from_arrow(_TABLE)
    _q6(df).collect()
    text = s.last_execution.explain_with_metrics()
    assert "-bound" in text
    # the kill switch removes the annotation, nothing else
    s2 = _session({"spark.rapids.sql.tpu.roofline.enabled": "false"})
    _q6(s2.from_arrow(_TABLE)).collect()
    assert "-bound" not in s2.last_execution.explain_with_metrics()


def test_offline_roofline_cli_matches_live_ledger(tmp_path):
    jdir = str(tmp_path / "journal")
    s = _session({"spark.rapids.sql.tpu.metrics.journal.dir": jdir})
    df = s.from_arrow(_TABLE)
    _q1(df).collect()
    live = {r["node"]: r for r in s.last_execution.roofline_ledger(
        RL.platform_peaks("cpu"))}
    # offline reconstruction from the journal file alone
    from spark_rapids_tpu.metrics.timeline import load_journal_dir
    shards = [sh for sh in load_journal_dir(jdir)
              if sh.get("base") == "driver"]
    assert shards
    rows = RL.ledger_from_events(shards[0]["events"],
                                 RL.platform_peaks("cpu"))
    offline = {r["node"]: r for r in rows}
    # every offline node matches the live bottleneck; offline may lack
    # never-executed nodes (absorbed stages have no spans/metrics)
    assert offline
    for nid, row in offline.items():
        if nid in live and live[nid]["bottleneck"] != RL.HOST:
            assert row["bottleneck"] == live[nid]["bottleneck"], nid
    # the CLI renders the same report and exits 0
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    proc = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.metrics", "roofline",
         jdir, "--device-kind", "cpu", "--json"],
        capture_output=True, text=True, env=env, timeout=180)
    assert proc.returncode == 0, proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["queries"] and rep["queries"][0]["ledger"]
    # usage errors exit 2
    proc2 = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.metrics", "roofline"],
        capture_output=True, text=True, env=env, timeout=60)
    assert proc2.returncode == 2


def test_whole_stage_cost_journal_event():
    from spark_rapids_tpu.metrics.journal import validate_events
    from spark_rapids_tpu.utils import kernel_cache as KC
    KC.clear_stage_executables()
    s = _session({"spark.rapids.sql.tpu.metrics.level": "DEBUG",
                  # keep the whole-stage node executing (not absorbed):
                  # a projection ending the plan keeps the stage the root
                  "spark.rapids.sql.reader.batchSizeRows":
                  str(N_ROWS // 4)})
    df = s.from_arrow(_TABLE)
    (df.filter(col("l_shipdate") <= D_19980902)
       .select((col("l_extendedprice") * col("l_discount")).alias("x"))
       .collect())
    events = s.last_execution.journal.events()
    assert validate_events(events) == []
    costs = [e for e in events if e["kind"] == "cost"]
    assert costs, "whole-stage executed without a cost declaration"
    for e in costs:
        assert e["source"] in ("hlo", "est")
        assert e["hbm_bytes"] > 0
        assert e["flops"] >= 0


# --------------------------------------------------------------------------
# profile-tree invariants
# --------------------------------------------------------------------------

def test_op_rows_never_exceed_stage_declaration():
    from spark_rapids_tpu.exec.whole_stage import TpuWholeStageExec
    s = _session({"spark.rapids.sql.reader.batchSizeRows":
                  str(N_ROWS // 4)})
    df = s.from_arrow(_TABLE)
    (df.filter(col("l_shipdate") <= D_19980902)
       .select((col("l_extendedprice") * col("l_discount")).alias("x"))
       .collect())
    stages = [n for n in s.last_execution.nodes
              if isinstance(n, TpuWholeStageExec)]
    assert stages, "no whole-stage node executed"
    for st in stages:
        stage_vals = st.metrics.snapshot()
        rows = st.op_rows()  # folds the lazy attribution
        for mk in RL.ALL_COST_METRICS:
            total = stage_vals.get(mk, 0)
            attributed = sum(m.snapshot().get(mk, 0) for _d, m in rows)
            assert attributed <= total + 1e-6, (mk, attributed, total)
            if total > 0:
                # the split actually attributes (floor-rounded shares)
                assert attributed > 0, (mk, stage_vals)


def test_every_cost_declaring_node_lands_in_ledger():
    s = _session()
    df = s.from_arrow(_TABLE)
    _q1(df).collect()
    qe = s.last_execution
    ledger = {r["node"]: r for r in qe.roofline_ledger()}
    for node in qe.nodes:
        vals = node.metrics.snapshot()
        declared = RL.cost_from_metrics(vals)
        assert node._node_id in ledger
        if declared:
            row = ledger[node._node_id]
            assert row["bottleneck"] != RL.HOST
            assert row["cost"], row


def test_cost_accounting_kill_switch_is_total():
    s = _session({"spark.rapids.sql.tpu.roofline.costAccounting"
                  ".enabled": "false"})
    df = s.from_arrow(_TABLE)
    _q6(df).collect()
    qe = s.last_execution
    for node in qe.nodes:
        vals = node.metrics.snapshot()
        for mk in RL.ALL_COST_METRICS:
            assert vals.get(mk, 0) == 0, (node.name, mk)
    assert all(r["bottleneck"] == RL.HOST
               for r in qe.roofline_ledger())


def test_essential_level_records_no_cost_metrics():
    s = _session({"spark.rapids.sql.tpu.metrics.level": "ESSENTIAL"})
    df = s.from_arrow(_TABLE)
    _q6(df).collect()
    for node in s.last_execution.nodes:
        vals = node.metrics.snapshot()
        for mk in RL.ALL_COST_METRICS:
            assert vals.get(mk, 0) == 0, (node.name, mk)


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
    s = _session()
    df = s.from_arrow(_TABLE)
    _q6(df).collect()
    text = s.last_execution.prometheus()
    parsed = parse_prometheus(text)
    assert any(k[0] == "spark_rapids_tpu_hbm_bytes_written"
               for k in parsed)
    assert any(k[0] == "spark_rapids_tpu_est_flops" for k in parsed)


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
# profiler overhead (generous ceiling; the bench records the <5% target)
# --------------------------------------------------------------------------

def test_profiler_overhead_under_generous_ceiling():
    def warmed(extra):
        df = _session(extra).from_arrow(_TABLE)
        _q1(df).collect()  # warm: compiles + scan cache
        return df

    def timed(df):
        t0 = time.perf_counter()
        _q1(df).collect()
        return time.perf_counter() - t0

    df_off = warmed({"spark.rapids.sql.tpu.roofline.costAccounting"
                     ".enabled": "false",
                     "spark.rapids.sql.tpu.roofline.enabled": "false"})
    df_on = warmed({})
    # alternate the two sides and keep each side's quietest run: a burst
    # of load on the host (six workers share it) then falls on both, not
    # on whichever side happened to be measured during it
    pairs = [(timed(df_off), timed(df_on)) for _ in range(40)]
    off, on = (min(side) for side in zip(*pairs))
    overhead = (on - off) / off if off > 0 else 0.0
    # target <2% (BENCH_PROFILE.json records the honest number; this
    # assertion uses a generous ceiling so shared-host jitter cannot
    # flake the tier)
    assert overhead < 0.25, f"profiler overhead {overhead:.1%}"


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
