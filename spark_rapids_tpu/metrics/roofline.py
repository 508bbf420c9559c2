"""Roofline-attribution profiler: the bottleneck-resource ledger.

BENCH_ONCHIP records q6 at ~0.89 GB/s effective against a ~819 GB/s v5e
HBM roofline — three orders of magnitude of headroom, and a single
end-to-end number that cannot say WHICH operator, transfer, or compile is
eating it.  This module closes that attribution gap: every exec operator
declares the bytes it moves per resource (HBM, host<->device link, socket
wire) plus rows and an estimated FLOP count (exec/base.record_cost;
whole-stage programs derive theirs from XLA's cost analysis on the
compiled HLO, utils/kernel_cache.stage_cost), and the ledger here joins
those declarations against measured span durations:

  * per resource r, the declaration implies a LOWER-BOUND time
    ``lb_r = bytes_r / peak_r`` (or flops / peak_flops) — the time the
    operator would take if r ran at its peak and nothing else mattered;
  * the node's **bottleneck resource** is the r with the largest lower
    bound (the classic roofline argmax) — a node declaring no device
    cost at all is labeled ``host`` (orchestration/dispatch-bound);
  * **utilization** is ``lb_bottleneck / measured_seconds`` — 1.0 means
    the node runs AT the roofline of its bottleneck resource; q6's 0.1%
    means 99.9% of its wall time is not explained by data movement.

Measured seconds come from the node's own WORK timers (totalTime, or
the operator-specific timers summed) — these wrap the actual per-batch
kernel dispatches.  Journal operator spans are only the fallback for
timer-less nodes: operator spans cover a generator's whole open
lifetime, so even after subtracting child intervals a producer's span
absorbs the time its CONSUMER spends between pulls — span-derived
"self time" systematically over-bills leaves and under-bills parents
in a pipelined plan (utilization >100% was the tell).

Surfaces: `QueryExecution.roofline_ledger()` /
`explain_with_metrics()` annotations, the offline
``python -m spark_rapids_tpu.metrics roofline <journal-dir>`` report
(reconstructed from journal files alone), and bench.py's
``profile_microbench`` -> BENCH_PROFILE.json, which scripts/
profile_regression.py gates CI against (docs/monitoring.md, "Reading
the roofline ledger").
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from . import names as N

#: resources a cost declaration can name; "host" is the fallback
#: bottleneck label for nodes that declare no device cost at all
RESOURCES = ("hbm", "h2d", "d2h", "wire", "ici", "flops")
HOST = "host"

#: resource -> the catalog metric names whose sum is its declared cost
COST_METRICS: Dict[str, Tuple[str, ...]] = {
    "hbm": (N.HBM_BYTES_READ, N.HBM_BYTES_WRITTEN),
    "h2d": (N.H2D_BYTES,),
    "d2h": (N.D2H_BYTES,),
    "wire": (N.WIRE_BYTES,),
    "ici": (N.ICI_BYTES_MOVED,),
    "flops": (N.EST_FLOPS,),
}

#: every metric name that feeds a cost declaration (ledger row filter)
ALL_COST_METRICS = tuple(m for ms in COST_METRICS.values() for m in ms)

# cost-accounting latch (spark.rapids.sql.tpu.roofline.costAccounting
# .enabled, latched by ExecContext like the packed-sort flag): the
# declarations are observability-only metadata increments, so any
# interleaving of concurrent queries with different settings is safe —
# a query at worst records or skips its OWN declarations.
_COST_ACCOUNTING = [True]


def set_cost_accounting(on: bool) -> None:
    _COST_ACCOUNTING[0] = bool(on)  # tpulint: disable=TPU009 per-session conf latch: an atomic boolean store, observability-only — a racing query at worst records/skips its own declarations


def cost_accounting_enabled() -> bool:
    return _COST_ACCOUNTING[0]

# Per-device peaks, keyed by `device_kind` as jax reports it: bytes/s for
# byte resources, ops/s for flops.  An unknown kind RAISES: utilization
# against another chip's peaks is a wrong number, not an estimate.
#   "TPU v5 lite" (v5e): 819 GB/s HBM, 197 TFLOP/s bf16 and 1,600 Gbit/s
#   (200 GB/s) chip-to-chip interconnect are the published figures
#   (Google Cloud documentation, "TPU v5e"); the host link and socket
#   wire rows are nominal (not published, not measured here).
#   "cpu" is the host backend's own row (its device_kind), a ballpark
#   for offline analysis and the CPU test environment.
# All overridable via spark.rapids.sql.tpu.roofline.peak*
# (docs/tuning-guide.md) — the ledger's RANKING is robust to peak error,
# the absolute utilization percentages are only as good as the peaks.
_DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm": 819e9, "h2d": 8e9, "d2h": 8e9, "wire": 1e9,
                    "ici": 200e9, "flops": 197e12},
    "cpu": {"hbm": 20e9, "h2d": 20e9, "d2h": 20e9, "wire": 1e9,
            "ici": 20e9, "flops": 50e9},
}


def known_device_kinds() -> tuple:
    return tuple(sorted(_DEVICE_PEAKS))


def detect_device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def platform_peaks(device_kind: Optional[str] = None,
                   conf=None) -> Dict[str, float]:
    """Per-resource peaks (bytes/s, flops/s) for the ledger's
    denominators: the device kind's table row, with any nonzero
    spark.rapids.sql.tpu.roofline.peak* conf override applied."""
    if device_kind is None:
        device_kind = detect_device_kind()
    if device_kind not in _DEVICE_PEAKS:
        raise KeyError(
            f"no roofline peaks for device kind {device_kind!r} (known: "
            f"{', '.join(known_device_kinds())}); add its published "
            "figures to metrics/roofline.py _DEVICE_PEAKS")
    peaks = dict(_DEVICE_PEAKS[device_kind])
    if conf is not None:
        from .. import config as C
        overrides = {
            "hbm": float(conf.get(C.ROOFLINE_PEAK_HBM)) * 1e9,
            "h2d": float(conf.get(C.ROOFLINE_PEAK_LINK)) * 1e9,
            "d2h": float(conf.get(C.ROOFLINE_PEAK_LINK)) * 1e9,
            "wire": float(conf.get(C.ROOFLINE_PEAK_WIRE)) * 1e9,
            "ici": float(conf.get(C.ROOFLINE_PEAK_ICI)) * 1e9,
            "flops": float(conf.get(C.ROOFLINE_PEAK_GFLOPS)) * 1e9,
        }
        for r, v in overrides.items():
            if v > 0:
                peaks[r] = v
    return peaks


# -- expression FLOP estimation ------------------------------------------------

def estimate_expr_flops(exprs: Sequence) -> int:
    """Per-ROW op-count estimate of an expression list: every interior
    node (arithmetic, comparison, function, cast) counts one op, leaves
    (column references, literals) are free.  Deliberately coarse — the
    roofline cares about orders of magnitude, and whole-stage programs
    replace this with XLA's exact HLO count anyway."""
    total = 0
    stack = list(exprs)
    while stack:
        e = stack.pop()
        # bound expressions expose .children, logical ColumnExpr .args
        kids = list(getattr(e, "children", ()) or
                    getattr(e, "args", ()) or ())
        kids = [k for k in kids if hasattr(k, "children")
                or hasattr(k, "args")]
        if kids:
            total += 1
            stack.extend(kids)
    return total


# -- cost extraction and attribution ------------------------------------------

def cost_from_metrics(vals: Dict[str, float]) -> Dict[str, float]:
    """Resource -> declared cost, from one node's metric snapshot."""
    out = {}
    for r, metric_names in COST_METRICS.items():
        v = sum(float(vals.get(m, 0.0)) for m in metric_names)
        if v > 0:
            out[r] = v
    return out


# exec-work timers usable as a node's measured seconds when no journal
# span is available (totalTime preferred; otherwise the operator's
# specific work timers summed).  Non-exec timers (compile, semaphore
# wait, queue, spill, checksum) are excluded: they measure waiting or
# one-time builds, not the per-batch device work the declaration covers.
_NON_EXEC_TIMERS = frozenset((
    N.STAGE_COMPILE_TIME, N.SEMAPHORE_WAIT_TIME, N.QUEUE_TIME,
    N.SPILL_TIME, N.CHECKSUM_TIME, N.REPLAN_TIME, N.COMPRESSION_TIME,
    N.DECOMPRESSION_TIME, N.SEG_AGG_TIME))


def seconds_from_metrics(vals: Dict[str, float]) -> Optional[float]:
    if vals.get(N.TOTAL_TIME, 0.0) > 0:
        return float(vals[N.TOTAL_TIME])
    total = 0.0
    for k, v in vals.items():
        spec = N.METRICS.get(k)
        if spec is not None and spec.kind == N.TIMER \
                and k not in _NON_EXEC_TIMERS:
            total += float(v)
    return total if total > 0 else None


def attribute(cost: Dict[str, float], seconds: Optional[float],
              peaks: Dict[str, float]) -> dict:
    """One ledger attribution: per-resource lower-bound seconds, the
    bottleneck resource (argmax lower bound), achieved rates, and
    utilization vs the bottleneck's peak."""
    lb = {r: cost[r] / peaks[r] for r in cost if peaks.get(r, 0) > 0}
    if not lb:
        return {"bottleneck": HOST, "lb_seconds": {}, "achieved": {},
                "utilization": None}
    bottleneck = max(lb, key=lambda r: lb[r])
    achieved = {}
    utilization = None
    if seconds is not None and seconds > 0:
        for r, v in cost.items():
            achieved[r] = v / seconds
        utilization = lb[bottleneck] / seconds
    return {"bottleneck": bottleneck,
            "lb_seconds": {r: round(v, 9) for r, v in lb.items()},
            "achieved": achieved,
            "utilization": utilization}


# -- measured seconds from journal spans --------------------------------------

def _interval_union(intervals: List[Tuple[int, int]]) -> int:
    """Total ns covered by the union of [t0, t1) intervals."""
    if not intervals:
        return 0
    intervals = sorted(intervals)
    total = 0
    cur_lo, cur_hi = intervals[0]
    for lo, hi in intervals[1:]:
        if lo > cur_hi:
            total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    total += cur_hi - cur_lo
    return total


def node_span_seconds(events: List[dict]) -> Dict[int, float]:
    """Per-node SELF seconds from a journal's operator spans: each
    span's duration minus the interval union of operator spans parented
    to it.  FALLBACK quality only (used for nodes without work timers):
    spans cover a generator's open lifetime, so a producer's span still
    includes the time its consumer spends between pulls — prefer
    seconds_from_metrics where timers exist."""
    spans: Dict[int, dict] = {}   # span id -> {node, t0, t1, parent}
    for e in events:
        if e.get("kind") != "operator":
            continue
        if e.get("ev") == "B":
            spans[e["id"]] = {"node": e.get("node"), "t0": e["ts"],
                              "t1": None, "parent": e.get("parent")}
        elif e.get("ev") == "E":
            s = spans.get(e.get("span"))
            if s is not None:
                s["t1"] = e["ts"]
    children: Dict[int, List[Tuple[int, int]]] = {}
    for sid, s in spans.items():
        if s["t1"] is None or s["parent"] is None:
            continue
        if s["parent"] in spans:
            children.setdefault(s["parent"], []).append((s["t0"], s["t1"]))
    out: Dict[int, float] = {}
    for sid, s in spans.items():
        if s["t1"] is None or s.get("node") is None:
            continue
        # children intervals clipped to the parent span (an adopted
        # dangling close can run past it)
        kids = [(max(lo, s["t0"]), min(hi, s["t1"]))
                for lo, hi in children.get(sid, []) if hi > lo]
        self_ns = (s["t1"] - s["t0"]) - _interval_union(
            [(lo, hi) for lo, hi in kids if hi > lo])
        nid = s["node"]
        out[nid] = out.get(nid, 0.0) + max(0, self_ns) / 1e9
    return out


# -- ledger construction -------------------------------------------------------

def ledger_from_execution(qe, peaks: Optional[Dict[str, float]] = None
                          ) -> List[dict]:
    """The roofline ledger of one executed query: one row per plan node
    (live objects: node metrics + the query journal when open)."""
    if peaks is None:
        peaks = platform_peaks(conf=getattr(qe, "conf", None))
    span_s: Dict[int, float] = {}
    if qe.journal is not None:
        try:
            span_s = node_span_seconds(qe.journal.events())
        except Exception:  # noqa: BLE001 — closed/truncated journal
            span_s = {}
    rows: List[dict] = []
    for node in qe.nodes:
        vals = node.metrics.snapshot()
        cost = cost_from_metrics(vals)
        # work timers first (they wrap the actual dispatches); span
        # self-time only for timer-less nodes — see module docstring
        seconds = seconds_from_metrics(vals)
        if seconds is None:
            seconds = span_s.get(node._node_id)
        rows.append(_ledger_row(node._node_id, type(node).__name__,
                                node.describe(), cost, vals, seconds,
                                peaks))
    return rows


def ledger_from_events(events: List[dict],
                       peaks: Optional[Dict[str, float]] = None
                       ) -> List[dict]:
    """Offline twin of ledger_from_execution: reconstruct the ledger of
    one query journal from its events alone (operator spans give the
    measured seconds, the finish-time `metric` instants give each node's
    cost declaration) — what `metrics roofline <journal-dir>` runs."""
    if peaks is None:
        peaks = platform_peaks()
    span_s = node_span_seconds(events)
    node_vals: Dict[int, dict] = {}
    node_name: Dict[int, str] = {}
    for e in events:
        if e.get("kind") == "metric" and e.get("node") is not None:
            node_vals[e["node"]] = dict(e.get("metrics", {}))
            node_name[e["node"]] = e.get("name", "?")
        elif e.get("kind") == "operator" and e.get("ev") == "B" \
                and e.get("node") is not None:
            node_name.setdefault(e["node"], e.get("name", "?"))
    rows: List[dict] = []
    for nid in sorted(set(node_vals) | set(span_s) | set(node_name)):
        vals = node_vals.get(nid, {})
        # same priority as the live ledger: work timers (carried by the
        # finish-time metric instants) first, span self-time fallback
        seconds = seconds_from_metrics(vals)
        if seconds is None:
            seconds = span_s.get(nid)
        name = node_name.get(nid, "?")
        rows.append(_ledger_row(nid, name.split("[")[0], name,
                                cost_from_metrics(vals), vals, seconds,
                                peaks))
    return rows


def _ledger_row(nid: int, op: str, name: str, cost: Dict[str, float],
                vals: Dict[str, float], seconds: Optional[float],
                peaks: Dict[str, float]) -> dict:
    att = attribute(cost, seconds, peaks)
    return {
        "node": nid,
        "op": op,
        "name": name,
        "seconds": round(seconds, 6) if seconds is not None else None,
        "rows": int(vals.get(N.NUM_OUTPUT_ROWS, 0)),
        "cost": {r: int(v) for r, v in sorted(cost.items())},
        "bottleneck": att["bottleneck"],
        "lb_seconds": att["lb_seconds"],
        "achieved_gb_s": {r: round(v / 1e9, 4)
                          for r, v in att["achieved"].items()
                          if r != "flops"},
        "achieved_gflops": round(att["achieved"].get("flops", 0.0) / 1e9,
                                 4) if "flops" in att["achieved"] else None,
        "utilization_pct": (round(att["utilization"] * 100.0, 4)
                            if att["utilization"] is not None else None),
    }


def explain_annotation(row: dict, peaks: Dict[str, float]) -> str:
    """One-line ledger suffix for explain_with_metrics: the bottleneck
    resource, the achieved rate on it, and utilization vs its peak.
    Never contains ']' (EXPLAIN consumers regex up to the metric
    bracket)."""
    b = row["bottleneck"]
    if b == HOST:
        return " <- host-bound (no device cost declared)"
    if b == "flops":
        rate = row.get("achieved_gflops")
        rate_s = f"{rate:.2f} GFLOP/s" if rate is not None else "?"
    else:
        rate = row.get("achieved_gb_s", {}).get(b)
        rate_s = f"{rate:.3f} GB/s" if rate is not None else "?"
    util = row.get("utilization_pct")
    util_s = f", {util:.2f}% of peak" if util is not None else ""
    return f" <- {b}-bound ({rate_s}{util_s})"


# -- rendering -----------------------------------------------------------------

def summarize(rows: List[dict]) -> dict:
    """Query-level rollup: total declared bytes per resource, the
    dominant bottleneck by time, and per-bottleneck seconds."""
    totals: Dict[str, float] = {}
    by_bottleneck: Dict[str, float] = {}
    measured = 0.0
    for r in rows:
        for res, v in r["cost"].items():
            totals[res] = totals.get(res, 0) + v
        if r["seconds"]:
            measured += r["seconds"]
            by_bottleneck[r["bottleneck"]] = \
                by_bottleneck.get(r["bottleneck"], 0.0) + r["seconds"]
    return {"cost_totals": {k: int(v) for k, v in sorted(totals.items())},
            "measured_seconds": round(measured, 6),
            "seconds_by_bottleneck": {k: round(v, 6) for k, v in
                                      sorted(by_bottleneck.items(),
                                             key=lambda kv: -kv[1])}}


def render(rows: List[dict], peaks: Dict[str, float],
           title: str = "roofline ledger") -> str:
    lines = [f"== {title} =="]
    lines.append("peaks: " + ", ".join(
        f"{r}={peaks[r] / 1e9:.1f}" + ("GFLOP/s" if r == "flops"
                                       else "GB/s")
        for r in RESOURCES if r in peaks))
    for row in rows:
        sec = f"{row['seconds'] * 1e3:8.2f}ms" if row["seconds"] \
            else "       --"
        util = (f"{row['utilization_pct']:7.3f}%"
                if row["utilization_pct"] is not None else "     --")
        cost_s = " ".join(f"{r}={v:,}" for r, v in row["cost"].items())
        lines.append(f"  [{row['node']:>3}] {sec} {util} "
                     f"{row['bottleneck']:>5}-bound  {row['name'][:60]}"
                     + (f"  ({cost_s})" if cost_s else ""))
    s = summarize(rows)
    if s["seconds_by_bottleneck"]:
        lines.append("time by bottleneck: " + ", ".join(
            f"{k}={v * 1e3:.1f}ms"
            for k, v in s["seconds_by_bottleneck"].items()))
    return "\n".join(lines)
