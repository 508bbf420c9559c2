"""Per-query observability driver.

`QueryExecution` wraps one executed physical plan: it assigns stable node
ids, pins every operator's metrics to the session's configured level,
opens the per-query event journal (file-backed under
`spark.rapids.sql.tpu.metrics.journal.dir`, in-memory at DEBUG level
otherwise), and instruments every node's execute/execute_cpu so operator
spans land in the journal with parent links that mirror the plan tree.

After the query runs, the same object is the reporting surface:

  * `explain_with_metrics()` — the plan tree annotated with each node's
    accumulated metrics (the Spark SQL UI analogue; printed automatically
    when `spark.rapids.sql.explain=METRICS`);
  * `prometheus()` — Prometheus text-format dump of every node metric plus
    the runtime pool/retry counters (export.py);
  * `node_metrics()` / `aggregate()` — structured access for the tests
    and tools.

Instrumentation notes: `execute` wrappers are plain functions that emit
the span-begin eagerly at CALL time and delegate to the original
generator, so a parent operator's span always opens before the child's
(operators call `child.execute(ctx)` from inside their own body).  Spans
close when the generator is exhausted or closed; `finish()` force-closes
anything a short-circuiting consumer (limit) left dangling.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Dict, List, Optional

from . import names as N
from .journal import (EventJournal, active_journal, pop_active,
                      push_active, trace_context)
from .registry import Metrics, parse_level

_QUERY_IDS = itertools.count(1)
_QUERY_ID_LOCK = threading.Lock()


def _next_query_id() -> int:
    with _QUERY_ID_LOCK:
        return next(_QUERY_IDS)


class QueryExecution:
    def __init__(self, conf, physical, runtime=None):
        from .. import config as C
        self.query_id = _next_query_id()
        self.physical = physical
        self.runtime = runtime
        self.conf = conf
        self.level = parse_level(conf.get(C.METRICS_LEVEL))
        jdir = str(conf.get(C.METRICS_JOURNAL_DIR) or "")
        self.journal: Optional[EventJournal] = None
        self._owns_journal = True
        # executor worker processes keep ONE process-lifetime trace shard
        # (journal.open_shard); a query executed there adopts it so
        # operator spans land in the shard the driver drains — and worker
        # processes never open per-query files whose names would collide
        # across processes under a shared journal.dir.  Adopted journals
        # are never closed by finish().
        shared = active_journal()
        # a live flight recorder (metrics/ring.py) mirrors every emitted
        # record, so an in-memory journal is worth opening even below
        # DEBUG with no journal dir: the query's spans land in the ring
        # and a post-mortem bundle can dump the driver's final seconds
        from .ring import get_telemetry
        if shared is not None and shared.is_shard:
            self.journal = shared
            self._owns_journal = False
        elif jdir or self.level >= N.DEBUG or get_telemetry() is not None:
            path = (os.path.join(jdir, f"query-{self.query_id}.jsonl")
                    if jdir else None)
            # file-backed journals carry a wall-clock anchor record so the
            # driver's query spans align with worker trace shards offline
            # (metrics/timeline.py)
            self.journal = EventJournal(path, query_id=self.query_id,
                                        anchor=path is not None,
                                        label="driver")
        # preorder walk: node ids, parent links, per-query metrics level
        self.nodes: List = []
        self._parent_of: Dict[int, Optional[int]] = {}
        self._assign_ids(physical, None)
        for node in self.nodes:
            node.metrics.configure(self.level)
        self._span_of: Dict[int, int] = {}  # node id -> open span id
        self._runtime_before = (dict(runtime.metrics.snapshot())
                                if runtime is not None else {})
        self.started_at = time.perf_counter()
        self.duration = None
        self.error = None
        self.finished = False
        self._trace_cm = None
        if self.journal is not None:
            self._query_span = self.journal.begin(
                "query", f"query-{self.query_id}", level=self.level,
                root=type(physical).__name__)
            for node in self.nodes:
                self._instrument(node)
            push_active(self.journal)
            if self._owns_journal:
                # driver-side trace context: loopback/in-process serve
                # events record which query's fetch they answered.  On a
                # worker (adopted shard) the task dispatch already set the
                # DRIVER's trace context — never clobber it with the
                # worker-local query id.
                self._trace_cm = trace_context(
                    query=f"q{self.query_id}", span=self._query_span,
                    executor="driver")
                self._trace_cm.__enter__()

    # -- tree bookkeeping ----------------------------------------------------

    def _assign_ids(self, node, parent_id) -> None:
        nid = len(self.nodes)
        node._node_id = nid
        self.nodes.append(node)
        self._parent_of[nid] = parent_id
        for c in node.children:
            self._assign_ids(c, nid)

    def _parent_span(self, nid: int) -> int:
        pid = self._parent_of.get(nid)
        while pid is not None:
            sid = self._span_of.get(pid)
            if sid is not None:
                return sid
            pid = self._parent_of.get(pid)
        return self._query_span

    def _instrument(self, node) -> None:
        journal = self.journal
        nid = node._node_id

        def wrap(orig, mode):
            # *args/**kwargs pass through: the adaptive shuffle reader
            # calls execute_partitions(ctx, specs)
            def wrapped(ctx, *args, _orig=orig, _nid=nid, _node=node,
                        **kwargs):
                sid = journal.begin(
                    "operator", _node.name, parent=self._parent_span(_nid),
                    node=_nid, mode=mode)
                self._span_of[_nid] = sid

                def drive(gen):
                    try:
                        yield from gen
                    finally:
                        journal.end(sid)
                        if self._span_of.get(_nid) == sid:
                            del self._span_of[_nid]
                return drive(_orig(ctx, *args, **kwargs))
            return wrapped

        # instance-attribute shadowing: per-query plan trees are fresh
        # objects, so the wrap never leaks across queries.  Exchanges are
        # additionally driven through execute_partitions (a shuffled hash
        # join pulls both children partition-wise, never calling execute),
        # so that entry point gets its own span wrapper too.
        try:
            node.execute = wrap(node.execute, "device")
            node.execute_cpu = wrap(node.execute_cpu, "cpu")
            if hasattr(node, "execute_partitions"):
                node.execute_partitions = wrap(node.execute_partitions,
                                               "partitions")
        except AttributeError:  # pragma: no cover - exotic nodes
            pass  # tpulint: disable=TPU006 a node without execute twins simply stays uninstrumented; metrics are additive

    def adopt(self, root=None) -> None:
        """Register plan nodes added by adaptive re-planning
        (adaptive/executor.py): assign node ids, pin metric levels,
        refresh parent links for moved nodes, and instrument fresh nodes
        so EXPLAIN METRICS, the journal metric dump and the Prometheus
        export all describe the FINAL (re-planned) stage plan."""
        start = root if root is not None else self.physical
        fresh: List = []

        def walk(node, parent_id):
            nid = getattr(node, "_node_id", None)
            if nid is None:
                nid = len(self.nodes)
                node._node_id = nid
                self.nodes.append(node)
                node.metrics.configure(self.level)
                fresh.append(node)
            self._parent_of[nid] = parent_id
            for c in node.children:
                walk(c, nid)

        walk(start, self._parent_of.get(getattr(start, "_node_id", 0)))
        if self.journal is not None:
            for node in fresh:
                self._instrument(node)

    # -- lifecycle -----------------------------------------------------------

    def finish(self, error: Optional[BaseException] = None
               ) -> "QueryExecution":
        if self.finished:
            return self
        self.finished = True
        self.duration = time.perf_counter() - self.started_at
        self.error = error
        if self.journal is not None:
            try:
                # final per-node metric dump: the journal carries the SAME
                # numbers explain_with_metrics and the Prometheus dump
                # render, so the three surfaces agree by construction
                for node in self.nodes:
                    vals = node.metrics.snapshot()
                    if vals:
                        self.journal.instant(
                            "metric", node.name, parent=self._query_span,
                            node=node._node_id, metrics=vals)
                delta = self.runtime_delta()
                if delta:
                    self.journal.instant(
                        "metric", "runtime", parent=self._query_span,
                        metrics=delta)
                self.journal.end(
                    self._query_span,
                    error=repr(error)[:200] if error else None,
                    duration_s=round(self.duration, 6))
            finally:
                # whatever the metric dump did, the journal must come off
                # the active stack (or later queries' events misroute into
                # it) and release its file handle.  An adopted worker
                # trace shard outlives every query: popped (it was pushed
                # a second time above), never closed.
                if self._trace_cm is not None:
                    try:
                        self._trace_cm.__exit__(None, None, None)
                    except Exception:  # pragma: no cover - thread moved
                        pass  # tpulint: disable=TPU006 trace-context exit after the owning thread moved on; the context is already unwound
                    self._trace_cm = None
                pop_active(self.journal)
                if self._owns_journal:
                    self.journal.close()
        return self

    # -- reporting -----------------------------------------------------------

    def runtime_delta(self) -> Dict[str, float]:
        """Runtime (pool/retry/spill) counter movement during this query."""
        if self.runtime is None:
            return {}
        after = self.runtime.metrics.snapshot()
        out = {}
        for k, v in after.items():
            d = v - self._runtime_before.get(k, 0)
            if d:
                out[k] = d
        return out

    def node_metrics(self) -> List[dict]:
        return [{"node": n._node_id, "op": type(n).__name__,
                 "name": n.describe(), "metrics": n.metrics.snapshot()}
                for n in self.nodes]

    def aggregate(self) -> Dict[str, float]:
        """Counters summed across every node (timers too — a coarse
        'time in operators' figure), plus the runtime delta."""
        out: Dict[str, float] = {}
        for n in self.nodes:
            for k, v in n.metrics.snapshot().items():
                out[k] = out.get(k, 0) + v
        for k, v in self.runtime_delta().items():
            out[k] = out.get(k, 0) + v
        return out

    @staticmethod
    def _fmt_metrics(vals: Dict[str, float]) -> str:
        parts = []
        for k in sorted(vals):
            v = vals[k]
            spec = N.METRICS.get(k)
            if spec is not None and spec.kind == N.TIMER:
                parts.append(f"{k}: {v * 1e3:.1f}ms")
            elif float(v) == int(v):
                parts.append(f"{k}: {int(v)}")
            else:
                parts.append(f"{k}: {v:.3f}")
        return f" [{', '.join(parts)}]" if parts else ""

    def _render(self, node, indent: int, lines: List[str]) -> None:
        lines.append(" " * indent + node.describe()
                     + self._fmt_metrics(node.metrics.snapshot()))
        if hasattr(node, "op_rows"):
            # whole-stage fused node: render the constituent operators
            # with their *(N) prefix and the stage-level counts folded
            # into each lazily (exec/whole_stage.TpuWholeStageExec)
            for desc, m in node.op_rows():
                lines.append(" " * (indent + 2) + desc
                             + self._fmt_metrics(m.snapshot()))
        for c in node.children:
            self._render(c, indent + 2, lines)

    def explain_with_metrics(self) -> str:
        """The executed plan tree with each node's accumulated metrics —
        what the reference surfaces per-node in the Spark SQL UI."""
        lines = [f"== Query {self.query_id} "
                 f"({N.LEVEL_NAMES[self.level]}"
                 + (f", {self.duration:.3f}s" if self.duration is not None
                    else "") + ") =="]
        self._render(self.physical, 0, lines)
        delta = self.runtime_delta()
        if delta:
            parts = ", ".join(f"{k}: {int(v) if v == int(v) else v}"
                              for k, v in sorted(delta.items()))
            lines.append(f"runtime: [{parts}]")
        return "\n".join(lines)

    def prometheus(self) -> str:
        from .export import prometheus_dump
        return prometheus_dump(self)
