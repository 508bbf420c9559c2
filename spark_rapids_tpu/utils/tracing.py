"""Tracing/profiling ranges.

TPU-native analogue of the reference's NVTX integration
(rapids/NvtxWithMetrics.scala:44 — a profiler range that also accumulates a
SQLMetric; docs/dev/nvtx_profiling.md): ranges show up in the XLA/JAX trace
viewer instead of Nsight.  `profile_trace` wraps jax.profiler for capturing
a trace directory viewable in TensorBoard/XProf.
"""
from __future__ import annotations

import contextlib
import time

import jax

#: every span the program opens carries this prefix in the profiler's
#: trace, so one pattern (`^srt:`) separates the program's spans from
#: JAX's runtime spans and a harness's own
SPAN_PREFIX = "srt:"


class _TimedRange(jax.profiler.TraceAnnotation):
    """A span that also feeds a timer: `named_range` with `metrics`."""

    __slots__ = ("_metrics", "_metric_name", "_t0")

    def __exit__(self, *exc):
        try:
            return super().__exit__(*exc)
        finally:
            self._metrics.add(self._metric_name,
                              time.perf_counter() - self._t0)


def named_range(name: str, metrics=None, metric_name: str = None, **args):
    """THE span primitive: a `jax.profiler.TraceAnnotation` named
    `srt:<name>` on the profiler's clock (the one the device trace shares),
    to be entered at once (`with named_range(...):`; the annotation starts
    when it is built); with `metrics` it also accumulates elapsed seconds
    into that Metrics object under `metric_name` (NvtxWithMetrics
    equivalent), so a span and its timer are one site.  It opens no
    `jax.named_scope`: a scope marks only ops TRACED inside it, nothing
    reads it, and it was 3.3 of a span's 3.8 us with no trace running; a
    scope for phases inside a program belongs in the kernel's body, not
    around its launch.

    `args` (`q=<query id>`, `rows=`, `bytes=`) land in the annotation and
    must be host-known: a span never reads the device and never syncs it.
    The span that caused a span is the one it nests in on its thread; the
    outermost of an operator's are its pull spans
    `srt:op:<ClassName>@<node id>` (`exec/base.py`, one site for all)."""
    if metrics is None:
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)
    span = _TimedRange(SPAN_PREFIX + name, **args)
    span._metrics = metrics
    span._metric_name = metric_name or name
    span._t0 = time.perf_counter()
    return span


@contextlib.contextmanager
def profile_trace(log_dir: str, journal=None):
    """Capture a device trace for the enclosed block (the Nsight-capture
    equivalent; open with TensorBoard's profile plugin).  Pass a query
    `journal` (metrics.journal.EventJournal) to also emit its spans as a
    Chrome trace-event file in `log_dir`, so the engine's
    operator/retry/spill/fetch timeline sits next to the XLA device
    timeline in the same viewer."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        if journal is not None:
            import os
            write_chrome_trace(journal.events(),
                               os.path.join(log_dir, "journal_trace.json"))


def journal_to_trace_events(events) -> list:
    """metrics.journal event records -> Chrome trace-event format (the
    XLA trace viewer / Perfetto / chrome://tracing input format).  B/E
    spans map to ph B/E duration events on a per-kind 'thread'; instant
    events map to ph i."""
    kinds = sorted({e.get("kind", "?") for e in events})
    tid_of = {k: i + 1 for i, k in enumerate(kinds)}
    out = [{"name": "process_name", "ph": "M", "pid": 1,
            "args": {"name": "spark_rapids_tpu journal"}}]
    for k, tid in tid_of.items():
        out.append({"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                    "args": {"name": k}})
    for e in events:
        ts_us = e.get("ts", 0) / 1e3  # monotonic ns -> us
        if e.get("kind") == "mem" and e.get("name") == "pressure":
            # memory lane: sampled per-tier pool usage renders as a
            # Chrome COUNTER track (stacked area) instead of an instant
            out.append({"name": "memory", "ph": "C", "pid": 1,
                        "ts": ts_us, "cat": "mem",
                        "args": {"device": e.get("device", 0),
                                 "host": e.get("host", 0),
                                 "disk": e.get("disk", 0)}})
            continue
        if e.get("kind") == "metric" and e.get("name") == "gaugeSample":
            # telemetry counter lanes: one counter track per sampled lane
            for lane in ("device_used", "in_flight_tasks", "spill_bytes"):
                if lane in e:
                    out.append({"name": lane, "ph": "C", "pid": 1,
                                "ts": ts_us, "cat": "telemetry",
                                "args": {lane: e[lane]}})
            continue
        rec = {"name": e.get("name", "?"), "pid": 1,
               "tid": tid_of.get(e.get("kind", "?"), 0), "ts": ts_us,
               "cat": e.get("kind", "?")}
        args = {k: v for k, v in e.items()
                if k not in ("ts", "ev", "kind", "name")}
        if args:
            rec["args"] = args
        ev = e.get("ev")
        if ev == "B":
            rec["ph"] = "B"
        elif ev == "E":
            rec["ph"] = "E"
        elif ev == "I":
            rec["ph"] = "i"
            rec["s"] = "t"  # thread-scoped instant
        else:
            continue
        out.append(rec)
    return out


def write_chrome_trace(events, path: str) -> str:
    """Write journal events as a Chrome trace-event JSON file."""
    import json
    import os
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": journal_to_trace_events(events),
                   "displayTimeUnit": "ms"}, f)
    return path


def timeline_to_trace_events(timeline) -> list:
    """Merged cluster timeline (metrics.timeline.Timeline) -> Chrome
    trace events: ONE PID LANE PER WORKER (process_name = executor id),
    a thread per span kind inside each lane, wall-clock-aligned
    timestamps, and FLOW events (ph s/f) tying every reducer fetch span
    to the mapper's serve record — so a multi-process shuffle reads as
    one picture in Perfetto / chrome://tracing / the XLA trace viewer."""
    executors = sorted(timeline.executors())
    pid_of = {ex: i + 1 for i, ex in enumerate(executors)}
    kinds = sorted({s.kind for s in timeline.spans}
                   | {i["kind"] for i in timeline.instants})
    tid_of = {k: i + 1 for i, k in enumerate(kinds)}
    out = []
    for ex, pid in pid_of.items():
        out.append({"name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": ex}})
        for k, tid in tid_of.items():
            out.append({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": k}})
    for sp in timeline.spans:
        rec = {"name": sp.name, "cat": sp.kind, "ph": "X",
               "pid": pid_of[sp.executor], "tid": tid_of[sp.kind],
               "ts": sp.t0_ns / 1e3,
               "dur": ((sp.t1_ns - sp.t0_ns) / 1e3
                       if sp.t1_ns is not None else 0)}
        if sp.attrs:
            rec["args"] = dict(sp.attrs)
        out.append(rec)
    for i in timeline.instants:
        if i["kind"] == "mem" and i["name"] == "pressure":
            # per-worker memory lane: one counter track per executor pid
            # so each worker's pool pressure renders as its own stacked
            # area under its span lanes
            out.append({"name": "memory", "ph": "C", "cat": "mem",
                        "pid": pid_of[i["executor"]],
                        "ts": i["wall_ns"] / 1e3,
                        "args": {
                            "device": i["attrs"].get("device", 0),
                            "host": i["attrs"].get("host", 0),
                            "disk": i["attrs"].get("disk", 0)}})
            continue
        if i["kind"] == "metric" and i["name"] == "gaugeSample":
            # telemetry counter lanes (metrics/ring.GaugeSampler ticks):
            # one counter track per worker per lane key, so pool bytes /
            # in-flight tasks / spill bytes render as per-executor area
            # charts alongside the span lanes
            for lane, val in i["attrs"].items():
                out.append({"name": lane, "ph": "C", "cat": "telemetry",
                            "pid": pid_of[i["executor"]],
                            "ts": i["wall_ns"] / 1e3,
                            "args": {lane: val}})
            continue
        rec = {"name": i["name"], "cat": i["kind"], "ph": "i", "s": "t",
               "pid": pid_of[i["executor"]], "tid": tid_of[i["kind"]],
               "ts": i["wall_ns"] / 1e3}
        if i["attrs"]:
            rec["args"] = dict(i["attrs"])
        out.append(rec)
    for idx, link in enumerate(timeline.links()):
        fetch, serve = link["fetch"], link["serve"]
        common = {"name": "shuffleFetch", "cat": "fetch-serve",
                  "id": idx}
        out.append({**common, "ph": "s",
                    "pid": pid_of[fetch.executor],
                    "tid": tid_of[fetch.kind], "ts": fetch.t0_ns / 1e3})
        out.append({**common, "ph": "f", "bp": "e",
                    "pid": pid_of[serve["executor"]]
                    if serve["executor"] in pid_of
                    else pid_of[fetch.executor],
                    "tid": tid_of.get("serve", 1),
                    "ts": serve["wall_ns"] / 1e3})
    return out


def write_cluster_chrome_trace(timeline, path: str) -> str:
    """Write a merged cluster timeline as a multi-pid Chrome trace."""
    import json
    import os
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": timeline_to_trace_events(timeline),
                   "displayTimeUnit": "ms"}, f)
    return path
