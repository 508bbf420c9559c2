"""End-to-end observability acceptance (ISSUE 2): one multi-operator query
at each metrics level, asserting

  * ESSENTIAL adds no per-batch device syncs (the DEVICE_SYNCS counter
    stays flat across execution);
  * DEBUG produces a journal whose operator spans cover EVERY plan node;
  * the rendered EXPLAIN-with-metrics tree, the Prometheus dump, and the
    journal's final metric events agree on numOutputRows and on the
    retry/spill counts of an OOM-injected run.
"""
import re

import pytest

from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.metrics import names as N
from spark_rapids_tpu.metrics import registry as R
from spark_rapids_tpu.metrics.export import parse_prometheus
from spark_rapids_tpu.metrics.journal import validate_events
from spark_rapids_tpu.plan.logical import col, functions as F, lit
from spark_rapids_tpu.utils import faults

pytestmark = pytest.mark.observability

# streaming partitioned join + filter + grouped agg + global sort — every
# operator layer executes its own path (same shape as test_retry's slice)
_BASE_CONF = {
    "spark.rapids.sql.tpu.wholeStage.enabled": "false",
    "spark.rapids.sql.tpu.join.partitioned.threshold": "1",
    "spark.sql.autoBroadcastJoinThreshold": "-1",
    "spark.rapids.sql.tpu.shuffle.partitions": "4",
    "spark.rapids.sql.variableFloatAgg.enabled": "true",
}


def _run_slice(level, extra=None):
    conf = dict(_BASE_CONF)
    conf["spark.rapids.sql.tpu.metrics.level"] = level
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
    rows = df.collect()
    return s, rows


def test_essential_no_per_batch_device_syncs():
    before = R.DEVICE_SYNCS.count
    s, rows = _run_slice("ESSENTIAL")
    assert R.DEVICE_SYNCS.count == before, \
        "ESSENTIAL level forced a per-batch device sync"
    assert len(rows) == 5
    # below DEBUG with no journal dir, a journal exists ONLY as the
    # in-memory mirror feeding the flight-recorder ring (metrics/ring.py)
    # — never a file
    from spark_rapids_tpu.metrics.ring import get_telemetry
    if get_telemetry() is None:
        assert s.last_execution.journal is None
    else:
        assert s.last_execution.journal is None \
            or s.last_execution.journal.path is None


def test_moderate_no_per_batch_device_syncs_but_timers():
    before = R.DEVICE_SYNCS.count
    s, rows = _run_slice("MODERATE")
    assert R.DEVICE_SYNCS.count == before
    timers = [name for rec in s.last_execution.node_metrics()
              for name, spec in
              ((n, N.METRICS.get(n)) for n in rec["metrics"])
              if spec is not None and spec.kind == N.TIMER]
    assert timers, "MODERATE level recorded no timers"


def test_debug_journal_covers_every_plan_node_and_syncs():
    before = R.DEVICE_SYNCS.count
    s, rows = _run_slice("DEBUG")
    assert R.DEVICE_SYNCS.count > before, \
        "DEBUG level should resolve per-batch counts eagerly (syncs)"
    qe = s.last_execution
    events = qe.journal.events()
    assert validate_events(events) == []
    span_nodes = {e["node"] for e in events
                  if e["ev"] == "B" and e["kind"] == "operator"}
    all_nodes = {node._node_id for node in qe.nodes}
    assert span_nodes == all_nodes, \
        f"journal spans missing nodes {sorted(all_nodes - span_nodes)}"


def test_three_surfaces_agree_on_rows_and_retry_spill_counts():
    """EXPLAIN-with-metrics + Prometheus + journal, one OOM-injected DEBUG
    run: all three must report the same numOutputRows per node and the
    same retry/spill totals."""
    faults.INJECTOR.reset()
    try:
        s, rows = _run_slice(
            "DEBUG", {"spark.rapids.tpu.test.injectOom": "3x2"})
    finally:
        faults.INJECTOR.reset()
    qe = s.last_execution
    node_rows = {rec["node"]: rec["metrics"][N.NUM_OUTPUT_ROWS]
                 for rec in qe.node_metrics()
                 if N.NUM_OUTPUT_ROWS in rec["metrics"]}
    assert node_rows, "no node recorded numOutputRows"
    assert node_rows[0] == len(rows)  # root == collected count

    # --- journal: final per-node metric events -----------------------------
    events = qe.journal.events()
    journal_rows = {e["node"]: e["metrics"][N.NUM_OUTPUT_ROWS]
                    for e in events
                    if e["kind"] == "metric" and e.get("node") is not None
                    and N.NUM_OUTPUT_ROWS in e.get("metrics", {})}
    assert journal_rows == node_rows

    # --- prometheus --------------------------------------------------------
    parsed = parse_prometheus(qe.prometheus())
    prom_rows = {}
    for (name, labels), value in parsed.items():
        if name == "spark_rapids_tpu_num_output_rows":
            d = dict(labels)
            if "node" in d:
                prom_rows[int(d["node"])] = value
    assert prom_rows == node_rows

    # --- explain-with-metrics ----------------------------------------------
    # whole-stage fusion adds per-OPERATOR attribution lines under each
    # *(N) stage node (lazily folded stage counts; the ops never dispatch
    # individually so they are not plan nodes) — drop them so the per-NODE
    # comparison stays exact
    text = qe.explain_with_metrics()
    node_lines = [ln for ln in text.splitlines()
                  if not re.match(r"\s*\*\(\d+\) (?!TpuWholeStageExec)", ln)]
    explained = [int(m) for m in
                 re.findall(r"numOutputRows: (\d+)", "\n".join(node_lines))]
    assert sorted(explained) == sorted(int(v) for v in node_rows.values())

    # --- retry/spill counts agree across the three surfaces ----------------
    agg = qe.aggregate()
    retry_total = sum(v for k, v in agg.items() if k.endswith("Retries"))
    assert retry_total >= 1, "injection produced no recorded retries"
    journal_retry_total = 0
    for e in events:
        if e["kind"] == "metric":
            journal_retry_total += sum(
                v for k, v in e.get("metrics", {}).items()
                if k.endswith("Retries"))
    assert journal_retry_total == retry_total
    # the journal's live retry event stream tells the same story
    live_retries = [e for e in events
                    if e["kind"] == "retry" and e["action"] == "retry"]
    assert len(live_retries) == retry_total
    prom_retry_total = sum(
        v for (name, _labels), v in parsed.items()
        if name.endswith("_retries") and name != "spark_rapids_tpu_retries")
    assert prom_retry_total == retry_total
    # spill counters: agree across surfaces (zero here — the injector
    # raises at reserve() without engaging the spill cascade)
    spill = agg.get(N.OOM_SPILL_RETRIES, 0)
    prom_spill = sum(v for (name, _l), v in parsed.items()
                     if name == "spark_rapids_tpu_oom_spill_retries")
    assert prom_spill == spill

    # the retries also appear in the session's observability rollup
    from spark_rapids_tpu.metrics.export import session_observability
    obs = session_observability(s)
    assert obs["retries"] == retry_total


def test_explain_metrics_mode_prints_tree(capsys):
    _s, _rows = _run_slice(
        "MODERATE", {"spark.rapids.sql.explain": "METRICS"})
    err = capsys.readouterr().err
    assert "== Query" in err
    assert "numOutputRows" in err
