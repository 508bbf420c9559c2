"""Metric exporters: Prometheus text format + cluster-wide aggregation.

`prometheus_dump(query_execution)` renders every node metric of an
executed query (plus the runtime pool/retry counters) in the Prometheus
text exposition format, ready to drop behind any textfile collector;
`parse_prometheus` is the inverse the tests round-trip through.

`cluster_snapshot` pulls `transport_counters` and `pool_stats` from every
worker of a running cluster — over the control RPC for the multi-process
`cluster.ProcCluster`, directly for the in-process `plugin.TpuCluster` —
and `prometheus_cluster_dump` renders the union with per-executor labels,
the cluster-wide rollup the reference gets from the Spark metrics sink.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from . import names as N

_PREFIX = "spark_rapids_tpu_"
_CAMEL = re.compile(r"(?<=[a-z0-9])(?=[A-Z])")


def prom_name(metric: str) -> str:
    """camelCase SQLMetric name -> prometheus_snake_case with the
    subsystem prefix; timers gain the conventional _seconds suffix."""
    snake = _CAMEL.sub("_", metric).lower()
    spec = N.METRICS.get(metric)
    if spec is not None and spec.kind == N.TIMER:
        snake += "_seconds"
    return _PREFIX + snake


def _prom_type(metric: str) -> str:
    spec = N.METRICS.get(metric)
    if spec is None:
        return "untyped"
    return "gauge" if spec.kind in (N.GAUGE, N.TIMER) else "counter"


def _escape(v: str) -> str:
    """Prometheus label-value escaping: backslash, quote, and newline
    (the exposition-format spec's full escape set — a label value
    carrying a raw newline would tear the sample line)."""
    return (str(v).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


def _sample(name: str, labels: Dict[str, str], value: float) -> str:
    body = ",".join(f'{k}="{_escape(v)}"' for k, v in sorted(labels.items()))
    return f"{name}{{{body}}} {float(value):g}"


def prometheus_dump(qe) -> str:
    """Prometheus text-format dump of one executed query
    (metrics/query.QueryExecution)."""
    by_metric: Dict[str, List[Tuple[Dict[str, str], float]]] = {}
    qlabel = str(qe.query_id)
    for row in qe.node_metrics():
        labels = {"query": qlabel, "node": str(row["node"]),
                  "op": row["op"]}
        for k, v in row["metrics"].items():
            by_metric.setdefault(k, []).append((labels, v))
    for k, v in qe.runtime_delta().items():
        by_metric.setdefault(k, []).append(
            ({"query": qlabel, "scope": "runtime"}, v))
    # process-wide hygiene counters (TPU006 fix sites, docs/lint.md):
    # cumulative over the process, labeled scope=engine so dashboards
    # can tell a scrape failure from a genuinely idle wire
    from .registry import ENGINE_COUNTERS
    for k, v in ENGINE_COUNTERS.snapshot().items():
        by_metric.setdefault(k, []).append(({"scope": "engine"}, v))
    lines: List[str] = []
    for metric in sorted(by_metric):
        pname = prom_name(metric)
        spec = N.METRICS.get(metric)
        help_text = spec.doc if spec is not None else metric
        lines.append(f"# HELP {pname} {help_text}")
        lines.append(f"# TYPE {pname} {_prom_type(metric)}")
        for labels, value in by_metric[metric]:
            lines.append(_sample(pname, labels, value))
    return "\n".join(lines) + "\n"


_NAME_RE = re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)')
_LABEL_NAME_RE = re.compile(r'[a-zA-Z_][a-zA-Z0-9_]*')


def _unescape(v: str) -> str:
    """Inverse of _escape: a single left-to-right scan, so '\\\\n' stays
    a backslash + n instead of becoming a newline (the ordering bug a
    chained str.replace inverse has)."""
    out = []
    i = 0
    while i < len(v):
        c = v[i]
        if c == "\\" and i + 1 < len(v):
            nxt = v[i + 1]
            if nxt == "n":
                out.append("\n")
            elif nxt in ('"', "\\"):
                out.append(nxt)
            else:  # unknown escape: keep verbatim (prometheus behavior)
                out.append(c)
                out.append(nxt)
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def _parse_labels(line: str, pos: int):
    """Parse a `{k="v",...}` label block starting at line[pos] == '{';
    returns (labels, index past '}').  Quote-aware, so escaped quotes
    and literal '}' INSIDE a label value parse correctly — the cases a
    naive [^}]* regex tears on (histogram le labels are fine either
    way; operator describe() strings with braces are not)."""
    labels = []
    i = pos + 1
    while True:
        while i < len(line) and line[i] in ", ":
            i += 1
        if i < len(line) and line[i] == "}":
            return frozenset(labels), i + 1
        m = _LABEL_NAME_RE.match(line, i)
        if m is None:
            raise ValueError(f"malformed prometheus labels: {line!r}")
        name = m.group(0)
        i = m.end()
        if line[i:i + 2] != '="':
            raise ValueError(f"malformed prometheus labels: {line!r}")
        i += 2
        buf = []
        while i < len(line):
            c = line[i]
            if c == "\\" and i + 1 < len(line):
                buf.append(c + line[i + 1])
                i += 2
                continue
            if c == '"':
                break
            buf.append(c)
            i += 1
        if i >= len(line):
            raise ValueError(f"unterminated label value: {line!r}")
        labels.append((name, _unescape("".join(buf))))
        i += 1  # past the closing quote


def parse_prometheus(text: str) -> Dict[Tuple[str, frozenset], float]:
    """Inverse of prometheus_dump / prometheus_cluster_dump /
    prometheus_serve_dump: {(metric_name, frozenset(label items)):
    value}.  Parses everything the dumps emit — label-less samples,
    histogram `_bucket`/`_sum`/`_count` lines, and escaped label values
    (quotes, backslashes, newlines, braces) — and raises on malformed
    sample lines (the property-style round-trip test's contract)."""
    out: Dict[Tuple[str, frozenset], float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _NAME_RE.match(line)
        if m is None:
            raise ValueError(f"malformed prometheus sample: {line!r}")
        name = m.group(1)
        i = m.end()
        if i < len(line) and line[i] == "{":
            labels, i = _parse_labels(line, i)
        else:
            labels = frozenset()
        value_s = line[i:].strip()
        if not value_s or " " in value_s:
            # a timestamp suffix would be a second token; the dumps
            # never emit one, so treat it as malformed rather than
            # silently misreading the value
            raise ValueError(f"malformed prometheus sample: {line!r}")
        try:
            value = float(value_s)
        except ValueError:
            raise ValueError(f"malformed prometheus sample: {line!r}")
        out[(name, labels)] = value
    return out


# -- cluster-wide aggregation ------------------------------------------------

def cluster_snapshot(cluster, scheduler=None,
                     rpc_timeout: float = 2.0) -> Dict[str, dict]:
    """{executor_id: {"transport": {...}, "pool": {...}}} pulled from every
    worker: over the control RPC for cluster.ProcCluster, in-process for
    plugin.TpuCluster.  With a serving-tier `scheduler` attached, a
    `_serve` entry additionally carries the fair-share observability the
    PR-10 scheduler implements but never exposed: per-priority-class
    queue depth and admission/rejection counters.

    Dead-worker tolerant: each ProcCluster worker is scraped over a
    DEDICATED fresh dial with `rpc_timeout` (the shared control client
    may be wedged behind the very task that killed the worker), and a
    worker that cannot answer yields `{"transport": {}, "pool": {},
    "stale": True}` instead of failing the whole scrape — a snapshot
    taken MID-RECOVERY must report the survivors."""
    out: Dict[str, dict] = {}
    if hasattr(cluster, "workers"):  # cluster.ProcCluster (rpc path)
        from ..shuffle.net import SocketClient
        for w in cluster.workers:
            try:
                client = SocketClient(cluster._transport,
                                      tuple(w.address),
                                      inject_faults=False,
                                      connect_timeout=rpc_timeout)
                try:
                    out[w.executor_id] = {
                        "transport": client.rpc(
                            "transport_counters",
                            _rpc_timeout=rpc_timeout),
                        "pool": client.rpc("pool_stats",
                                           _rpc_timeout=rpc_timeout),
                    }
                finally:
                    client.close()
            except Exception as e:  # noqa: BLE001 — partial beats none
                from .registry import count_swallowed
                count_swallowed("numExportScrapeErrors",
                                "spark_rapids_tpu.metrics",
                                "worker %s scrape failed (%r); marking "
                                "stale", w.executor_id, e)
                out[w.executor_id] = {"transport": {}, "pool": {},
                                      "stale": True}
    elif hasattr(cluster, "executors"):  # plugin.TpuCluster (in-process)
        transport = getattr(cluster, "transport", None)
        shared = dict(getattr(transport, "counters", {}) or {})
        for e in cluster.executors:
            out[e.executor_id] = {
                "transport": shared,  # one loopback wire is shared
                "pool": e.runtime.pool_stats(),
            }
    else:
        raise TypeError(f"not a cluster: {type(cluster).__name__}")
    if scheduler is not None:
        out["_serve"] = scheduler.fairness_snapshot()
    return out


def prometheus_cluster_dump(cluster, scheduler=None,
                            rpc_timeout: float = 2.0) -> str:
    """Cluster rollup in Prometheus text format with executor labels;
    with a `scheduler`, the serving-tier fairness gauges and per-phase
    SLO histograms ride along (prometheus_serve_dump)."""
    snap = cluster_snapshot(cluster, rpc_timeout=rpc_timeout)
    lines: List[str] = []
    emitted_header = set()

    def emit(metric: str, labels: Dict[str, str], value, help_text: str,
             mtype: str):
        pname = _PREFIX + metric
        if pname not in emitted_header:
            lines.append(f"# HELP {pname} {help_text}")
            lines.append(f"# TYPE {pname} {mtype}")
            emitted_header.add(pname)
        lines.append(_sample(pname, labels, value))

    for exec_id in sorted(snap):
        labels = {"executor": exec_id}
        if snap[exec_id].get("stale"):
            # a dead/wedged worker still appears — with stale="true" on
            # its (empty) series and executor_up 0, so one lost worker
            # degrades the scrape instead of killing it
            labels["stale"] = "true"
        emit("executor_up", labels,
             0 if snap[exec_id].get("stale") else 1,
             "1 when the executor answered the scrape rpc within the "
             "timeout, 0 when its series are stale", "gauge")
        for k, v in sorted(snap[exec_id].get("transport", {}).items()):
            emit(k, labels, v,
                 N.TRANSPORT_COUNTERS.get(k, k), "counter")
        for k, v in sorted(snap[exec_id].get("pool", {}).items()):
            if k in N.POOL_GAUGES:
                emit(k, labels, v, N.POOL_GAUGES[k], "gauge")
            else:  # runtime Metrics counters (oomSpillRetries, ...)
                spec = N.METRICS.get(k)
                # prom_name keeps the series name identical to the
                # per-query dump's (same snake-casing, same _seconds
                # suffix on timers) so dashboards key on ONE name
                emit(prom_name(k)[len(_PREFIX):], labels, v,
                     spec.doc if spec else k, _prom_type(k))
    body = "\n".join(lines) + "\n"
    if scheduler is not None:
        body += prometheus_serve_dump(scheduler)
    return body


# -- serving-tier export (scheduler fairness + SLO histograms) ----------------

def prometheus_serve_dump(scheduler) -> str:
    """The serving tier in Prometheus text format: per-priority-class
    queue depth / admitted / rejected (the PR-10 fair-share behavior
    made observable) plus the per-(phase, priority) latency histograms
    in the standard `_bucket`/`_sum`/`_count` exposition, which
    parse_prometheus round-trips."""
    lines: List[str] = []
    fair = scheduler.fairness_snapshot()

    def header(pname, help_text, mtype):
        lines.append(f"# HELP {pname} {help_text}")
        lines.append(f"# TYPE {pname} {mtype}")

    gauges = (
        ("serve_queue_depth", "queue_depth_by_priority", "gauge",
         "queries currently waiting in the scheduler queue"),
        ("serve_admitted_total", "admitted_by_priority", "counter",
         "queries admitted for execution"),
        ("serve_admission_rejections_total", "rejected_by_priority",
         "counter", "submissions rejected at queue capacity"),
    )
    for suffix, field, mtype, help_text in gauges:
        pname = _PREFIX + suffix
        header(pname, help_text + " (per priority class)", mtype)
        by_prio = fair.get(field, {}) or {}
        if not by_prio:
            lines.append(_sample(pname, {"priority": "all"}, 0))
        for prio, v in sorted(by_prio.items()):
            lines.append(_sample(pname, {"priority": str(prio)}, v))

    slo = getattr(scheduler, "slo", None)
    if slo is not None:
        pname = _PREFIX + "serve_phase_seconds"
        header(pname, "per-query phase latency histogram "
               "(queue/plan/compile/execute/spill/total per priority "
               "class; docs/monitoring.md)", "histogram")
        for (phase, prio), h in sorted(slo.histograms().items()):
            labels = {"phase": phase, "priority": prio}
            for le, cum in h.cumulative_buckets():
                lines.append(_sample(pname + "_bucket",
                                     {**labels, "le": le}, cum))
            lines.append(_sample(pname + "_sum", labels, h.sum))
            lines.append(_sample(pname + "_count", labels, h.count))
    return "\n".join(lines) + "\n"


# -- live telemetry endpoint body (metrics/http.py /metrics) ------------------

def prometheus_gauge_dump(values: Dict[str, float],
                          labels: Dict[str, str],
                          include_engine: bool = True) -> str:
    """Current gauge-sampler values (ring.GaugeSampler.latest()) in
    Prometheus text format — the /metrics endpoint body.  Series names
    come from the shared catalog: POOL_GAUGES / TRANSPORT_COUNTERS /
    TELEMETRY_GAUGES keys keep their snake_case names (identical to
    prometheus_cluster_dump's), registered camelCase metrics go through
    prom_name, anything else is snake-cased untyped.  With
    `include_engine`, the process-wide hygiene counters ride along
    (scope=engine), so a scraper sees tap/sample/dump failures in the
    same scrape that would be missing data because of them."""
    lines: List[str] = []

    def header(pname, help_text, mtype):
        lines.append(f"# HELP {pname} {help_text}")
        lines.append(f"# TYPE {pname} {mtype}")

    for k in sorted(values):
        v = values[k]
        if k in N.POOL_GAUGES:
            pname, help_text, mtype = \
                _PREFIX + k, N.POOL_GAUGES[k], "gauge"
        elif k in N.TELEMETRY_GAUGES:
            pname, help_text, mtype = \
                _PREFIX + k, N.TELEMETRY_GAUGES[k], "gauge"
        elif k in N.TRANSPORT_COUNTERS:
            pname, help_text, mtype = \
                _PREFIX + k, N.TRANSPORT_COUNTERS[k], "counter"
        elif k in N.METRICS:
            pname = prom_name(k)
            help_text, mtype = N.METRICS[k].doc, _prom_type(k)
        else:
            pname = _PREFIX + _CAMEL.sub("_", k).lower()
            help_text, mtype = k, "untyped"
        header(pname, help_text, mtype)
        lines.append(_sample(pname, labels, v))
    if include_engine:
        from .registry import ENGINE_COUNTERS
        for k, v in sorted(ENGINE_COUNTERS.snapshot().items()):
            pname = prom_name(k)
            spec = N.METRICS.get(k)
            header(pname, spec.doc if spec else k, _prom_type(k))
            lines.append(_sample(pname, {**labels, "scope": "engine"}, v))
    return "\n".join(lines) + "\n"


# -- bench/session rollup ----------------------------------------------------

def session_observability(session) -> dict:
    """One flat dict of the session's observability counters: CPU
    fallbacks, retry/split totals, spill/pool figures, and wire bytes
    when a cluster is attached."""
    totals = dict(getattr(session, "query_metrics_total", {}) or {})
    out = {
        "numCpuFallbacks": int(totals.get(N.NUM_CPU_FALLBACKS, 0)),
        "retries": int(sum(totals.get(f"{b}Retries", 0)
                           for b in N.RETRY_BLOCKS)),
        "splits": int(sum(totals.get(f"{b}Splits", 0)
                          for b in N.RETRY_BLOCKS)),
        "queries": int(getattr(session, "queries_executed", 0)),
    }
    if session._runtime is not None:
        pool = session.runtime.pool_stats()
        out["oomSpillRetries"] = int(pool.get(N.OOM_SPILL_RETRIES, 0))
        out["oomAllocFailures"] = int(pool.get(N.OOM_ALLOC_FAILURES, 0))
        out["spill_bytes"] = int(pool.get(N.OOM_SPILL_BYTES, 0))
        out["device_used"] = int(pool.get("device_used", 0))
        out["host_spill_used"] = int(pool.get("host_used", 0))
        out["disk_spill_used"] = int(pool.get("disk_used", 0))
        # memory ledger (ISSUE 8): store high-waters + churn signal, so
        # a bench row carries the peak footprint that produced it
        out["device_peak"] = int(pool.get("device_peak", 0))
        out["host_spill_peak"] = int(pool.get("host_peak", 0))
        out["disk_spill_peak"] = int(pool.get("disk_peak", 0))
        out["numBufferRespills"] = int(
            pool.get(N.NUM_BUFFER_RESPILLS, 0))
        out["memLedgerEvents"] = int(pool.get(N.MEM_LEDGER_EVENTS, 0))
        # data-movement policy decisions (ISSUE 18): how often the
        # engine changed a victim, moved bytes ahead of use, stalled a
        # producer, or flipped the wire codec — a bench row with these
        # at zero ran with the policy effectively idle
        out["numPolicyVictimPicks"] = int(
            pool.get(N.NUM_POLICY_VICTIM_PICKS, 0))
        out["numPolicyVictimOverrides"] = int(
            pool.get(N.NUM_POLICY_VICTIM_OVERRIDES, 0))
        out["numPolicyEarlyReleases"] = int(
            pool.get(N.NUM_POLICY_EARLY_RELEASES, 0))
        out["numProactiveUnspills"] = int(
            pool.get(N.NUM_PROACTIVE_UNSPILLS, 0))
        out["numPrefetchHits"] = int(pool.get(N.NUM_PREFETCH_HITS, 0))
        out["numPrefetchWasted"] = int(
            pool.get(N.NUM_PREFETCH_WASTED, 0))
        out["numBackpressureStalls"] = int(
            pool.get(N.NUM_BACKPRESSURE_STALLS, 0))
        out["numCodecReselections"] = int(
            pool.get(N.NUM_CODEC_RESELECTIONS, 0))
    # shuffle tier selection (ISSUE 14): how many exchanges the mesh
    # tier served as jitted ICI collectives vs de-lowered to the socket
    # tier — read from the session transport's counters (shuffle/ici.py)
    rt = session._runtime
    env = getattr(rt, "_shuffle_env", None) if rt is not None else None
    tcounters = getattr(getattr(env, "transport", None), "counters", {}) \
        if env is not None else {}
    out["ici_exchanges"] = int(tcounters.get("ici_exchanges", 0))
    out["socket_fallbacks"] = int(tcounters.get("socket_fallbacks", 0))
    out["numIciExchanges"] = int(totals.get(N.NUM_ICI_EXCHANGES, 0))
    cluster = getattr(session, "_cluster", None) or None
    wire_sent = wire_recv = 0
    if cluster:
        try:
            snap = cluster_snapshot(cluster)
            seen = set()
            for rec in snap.values():
                t = rec.get("transport", {})
                key = id(t) if isinstance(t, dict) else None
                if key in seen:
                    continue  # TpuCluster shares one wire's counters
                seen.add(key)
                wire_sent += int(t.get("bytes_sent", 0))
                wire_recv += int(t.get("bytes_received", 0))
        except Exception as e:  # noqa: BLE001 — observability must not throw
            # report the zeros, but not silently: a dashboard flatline
            # caused by a scrape failure should be distinguishable from
            # a genuinely idle wire
            from .registry import count_swallowed
            count_swallowed("numExportScrapeErrors",
                            "spark_rapids_tpu.metrics",
                            "cluster wire-counter scrape failed (%r); "
                            "reporting 0", e)
    out["wire_bytes_sent"] = wire_sent
    out["wire_bytes_received"] = wire_recv
    # distributed task recovery (ISSUE 15): speculation races, deadline
    # abandonments, wedged-worker evictions and graceful shrinks of an
    # attached ProcCluster — the detect->act half the heartbeat/straggler
    # sensors (PR 7) report into, next to the wire bytes they ride on
    pc = getattr(session, "_proc_cluster", None)
    if pc is not None:
        rec = {"task_retries": int(pc.task_retries),
               "lost_map_outputs": int(pc.lost_map_outputs),
               "worker_shrinks": int(pc.worker_shrinks)}
        rec.update({k: int(v) for k, v in pc.recovery_metrics().items()})
        out["cluster_recovery"] = rec
    # process-wide hygiene counters (TPU006, docs/lint.md): swallowed-
    # failure sites that logged + counted instead of passing silently.
    # Snapshotted AFTER the wire scrape, so a scrape failure's own
    # numExportScrapeErrors bump rides the very payload reporting the
    # zeros.  Driver-process view only — worker-side bumps stay in
    # worker logs.
    from .registry import ENGINE_COUNTERS
    out["engine_counters"] = {k: int(v) for k, v in
                              ENGINE_COUNTERS.snapshot().items()}
    # serving tier (ISSUE 10): scheduler/admission/plan-cache rollup —
    # present only when the session ever ran submit(); the queue/
    # admission METRICS (queueTime, numAdmitted, planCacheHits, ...) live
    # on the runtime Metrics and already ride pool_stats/prometheus
    sched = getattr(session, "_scheduler", None)
    if sched is not None:
        out["scheduler"] = sched.stats()
        if session._runtime is not None:
            pool = session.runtime.pool_stats()
            out["scheduler"]["queue_time_s"] = \
                float(pool.get(N.QUEUE_TIME, 0.0))
            out["scheduler"]["planCacheHits"] = \
                int(pool.get(N.PLAN_CACHE_HITS, 0))
            out["scheduler"]["planCacheMisses"] = \
                int(pool.get(N.PLAN_CACHE_MISSES, 0))
            out["scheduler"]["numBudgetOoms"] = \
                int(pool.get(N.NUM_BUDGET_OOMS, 0))
    return out


def session_adaptive(session) -> dict:
    """Adaptive-execution rollup of the session (rides next to
    session_observability): coalesce/skew/strategy-change counts,
    observed map-output bytes, and stage re-plan latency accumulated
    across the session's queries."""
    totals = dict(getattr(session, "query_metrics_total", {}) or {})
    return {
        "numCoalescedPartitions":
            int(totals.get(N.NUM_COALESCED_PARTITIONS, 0)),
        "numSkewSplits": int(totals.get(N.NUM_SKEW_SPLITS, 0)),
        "numJoinStrategyChanges":
            int(totals.get(N.NUM_JOIN_STRATEGY_CHANGES, 0)),
        "mapOutputBytes": int(totals.get(N.MAP_OUTPUT_BYTES, 0)),
        "replan_time_s": float(totals.get(N.REPLAN_TIME, 0.0)),
        "queries": int(getattr(session, "queries_executed", 0)),
    }
