"""TpuSession + DataFrame: the user-facing entry points.

Standalone equivalent of the reference's plugin bootstrap + Spark session
surface (reference: com/nvidia/spark/SQLPlugin.scala, rapids/Plugin.scala):
a session owns the conf and the device runtime; DataFrames build logical
plans; collect() runs the overrides pass (tag -> explain -> convert ->
transitions) and executes the physical plan.
"""
from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence

from . import config as C
from .config import TpuConf
from .exec.base import CpuExec, ExecContext, ExecNode, TpuExec
from .exec import basic as B
from .plan import logical as L
from .plan.logical import ColumnExpr, SortOrder, col, functions, lit
from .plan.grouping import has_grouping, resolve_grouping
from .plan.overrides import PlanMeta, plan_schema
from .plan.physical import convert
from .plan import transitions as T
from .types import Schema, StructField, from_arrow
from .utils.tracing import named_range


# one shared owner of jax's persistent-cache configuration: engine,
# bench.py children and the executor worker bootstrap all call this, so
# the cache knobs cannot drift between entry points
from .utils.compile_cache import enable_compilation_cache  # noqa: E402


class TpuSession:
    def __init__(self, conf: Optional[Dict] = None):
        self.conf = TpuConf(conf)
        self._runtime = None
        # observability surface (docs/monitoring.md): the last query's
        # QueryExecution (explain_with_metrics / prometheus / journal) and
        # session-cumulative counters for bench/export rollups
        self.last_execution = None
        self.query_metrics_total: Dict[str, float] = {}
        self.queries_executed = 0
        # live-progress surface (docs/monitoring.md): a ProcCluster
        # constructed with session= attaches itself here and progress()
        # delegates to its heartbeat monitor
        self._proc_cluster = None
        self._progress_high_water = 0
        # serving tier (serve/scheduler.py): built lazily by submit();
        # the locks make the lazy singletons and the session-cumulative
        # counters safe under the scheduler's concurrent query threads
        self._scheduler = None
        self._serve_lock = threading.Lock()
        self._finish_lock = threading.Lock()
        self._lazy_lock = threading.RLock()  # runtime/cluster first touch
        # the cache gate asks which backend is in use, which initializes
        # it: a multi-host process must have joined the coordination
        # service by then (no-op without a coordinator)
        from .parallel.mesh import init_distributed
        init_distributed(self.conf)
        enable_compilation_cache(self.conf.get(C.COMPILATION_CACHE_DIR))
        # post-mortem plane (metrics/bundle.py, docs/monitoring.md):
        # armed only on the DRIVER (executor workers set ring.PROCESS_ROLE
        # before building their session) and only when a bundle dir is
        # configured.  _last_qe feeds the explain section of dumps whose
        # trigger has no QueryExecution in hand (SIGUSR1, watchdog).
        self._last_qe = None
        self._postmortem = None
        try:
            from .metrics import bundle as _bundle, ring as _ring
            pm_dir = str(self.conf.get(C.TELEMETRY_POSTMORTEM_DIR) or "")
            if pm_dir and _ring.PROCESS_ROLE[0] == "driver":
                self._postmortem = _bundle.PostmortemManager(
                    self, pm_dir,
                    int(self.conf.get(C.TELEMETRY_POSTMORTEM_MIN_INTERVAL)))
                _bundle.install_sigusr1(self._postmortem)
        except Exception:  # noqa: BLE001 — arming is observability-only
            from .metrics.registry import count_swallowed
            count_swallowed("numPostmortemErrors", "spark_rapids_tpu",
                            "postmortem arming failed at session init")
        # flight recorder + gauge sampler + /metrics endpoint: the
        # per-process telemetry singleton (metrics/ring.py).  The LATEST
        # session rebinds the driver gauge source and the endpoint
        # payloads (weakref — telemetry must never keep a session alive)
        try:
            from .metrics import ring as _ring
            t = _ring.init_telemetry(self.conf,
                                     role=_ring.PROCESS_ROLE[0])
            if t is not None and _ring.PROCESS_ROLE[0] == "driver":
                self._wire_driver_telemetry(t)
        except Exception:  # noqa: BLE001 — telemetry must never block
            from .metrics.registry import count_swallowed
            count_swallowed("numTelemetrySampleErrors", "spark_rapids_tpu",
                            "driver telemetry wiring failed")

    def _wire_driver_telemetry(self, t) -> None:
        """Bind this session to the process telemetry: the driver gauge
        source (pool / scheduler / spill figures the sampler snapshots)
        and — once per process — the loopback HTTP endpoint."""
        t.session_ref = weakref.ref(self)

        def driver_gauges() -> Dict[str, float]:
            s = t.session_ref()
            if s is None:
                return {}
            out: Dict[str, float] = {}
            rt = s._runtime  # never force a runtime build from a sampler
            if rt is not None:
                stats = rt.pool_stats()
                out.update({k: float(v) for k, v in stats.items()
                            if isinstance(v, (int, float))})
                out["spill_bytes"] = float(stats.get("host_used", 0)
                                           + stats.get("disk_used", 0))
            sched = s._scheduler
            out["in_flight_tasks"] = 0.0
            out["queued_queries"] = 0.0
            if sched is not None:
                out.update(sched.telemetry_gauges())
            return out

        def policy_gauges() -> Dict[str, float]:
            s = t.session_ref()
            rt = s._runtime if s is not None else None
            pol = getattr(rt, "policy", None) if rt is not None else None
            return pol.gauges() if pol is not None else {}

        t.sampler.add_source("driver", driver_gauges)
        t.sampler.add_source("policy", policy_gauges)
        t.sampler.start()
        if t.http is None \
                and bool(self.conf.get(C.TELEMETRY_HTTP_ENABLED)):
            from .metrics.export import session_observability
            from .metrics.http import serve_telemetry

            def observability() -> Dict:
                s = t.session_ref()
                if s is None:
                    return {}
                return {"session_observability": session_observability(s),
                        "progress": s.progress()}

            def healthz():
                s = t.session_ref()
                payload = {"ok": s is not None, "role": "driver",
                           "pid": os.getpid()}
                pc = getattr(s, "_proc_cluster", None) if s else None
                if pc is not None and pc.monitor is not None:
                    lag = pc.monitor.lag_s()
                    payload["heartbeat_lag_s"] = \
                        max(lag.values()) if lag else 0.0
                    payload["hung_tasks"] = pc.monitor.hung_tasks
                    payload["workers"] = len(pc.workers)
                return (200 if payload["ok"] else 503), payload

            serve_telemetry(t, {"executor": "driver"}, healthz=healthz,
                            observability=observability,
                            port=int(self.conf.get(C.TELEMETRY_HTTP_PORT)))

    def dump_diagnostics(self, out_dir: Optional[str] = None,
                         reason: str = "manual") -> str:
        """Write a post-mortem diagnostic bundle NOW (config, EXPLAIN
        with roofline, merged timeline, memledger replay, SLO state,
        per-process flight-recorder rings) and return its directory.
        Render it with `python -m spark_rapids_tpu.metrics postmortem
        <bundle>` (docs/monitoring.md, Post-mortem bundles)."""
        from .metrics import bundle as _bundle
        if out_dir is None:
            base = str(self.conf.get(C.TELEMETRY_POSTMORTEM_DIR) or "") \
                or "."
            out_dir = os.path.join(
                base, f"postmortem-{reason}-{os.getpid()}-"
                      f"{time.time_ns() // 1_000_000}")
        return _bundle.dump_diagnostics(out_dir, session=self,
                                        reason=reason)

    def _begin_execution(self, physical: ExecNode, runtime=None):
        """Open the per-query observability scope (metrics levels, event
        journal, operator spans) around an about-to-run physical tree."""
        from .metrics.query import QueryExecution
        return QueryExecution(self.conf, physical,
                              runtime=runtime or self._runtime)

    def _finish_execution(self, qe, error=None) -> None:
        # runs in every execution finally-block: a failure in the
        # observability path (journal write on a full disk, metric fold on
        # an exhausted device) must neither fail a successful query nor
        # mask the real error — and the journal must come off the active
        # stack regardless (QueryExecution.finish guarantees that part)
        try:
            with named_range("finish", q=qe.query_id):
                qe.finish(error)
                with self._finish_lock:
                    # concurrent serving: N query threads finish at once;
                    # the read-modify-write counter folds must not race
                    self.last_execution = qe
                    self._last_qe = qe
                    self.queries_executed += 1
                    for k, v in qe.aggregate().items():
                        self.query_metrics_total[k] = \
                            self.query_metrics_total.get(k, 0) + v
            if self.conf.explain == "METRICS" and error is None:
                print(qe.explain_with_metrics(), file=sys.stderr)
            if error is not None and self._postmortem is not None:
                # first-failure diagnostics: the bundle is written while
                # the dying query's journal/metrics are still warm
                self._postmortem.trigger("query-failure", qe=qe,
                                         error=error)
        except Exception:  # pragma: no cover - reporting is best-effort
            import logging
            logging.getLogger("spark_rapids_tpu.metrics").warning(
                "observability finish failed", exc_info=True)

    # -- data sources -------------------------------------------------------
    def from_arrow(self, table) -> "DataFrame":
        fields = [StructField(n, from_arrow(t))
                  for n, t in zip(table.column_names, table.schema.types)]
        return DataFrame(self, L.LogicalScan(table, Schema(fields), "memory"))

    def from_pydict(self, data: Dict, schema: Optional[Schema] = None
                    ) -> "DataFrame":
        import pyarrow as pa
        if schema is None:
            table = pa.table(data)
        else:
            from .types import to_arrow
            table = pa.table(
                {k: pa.array(v, type=to_arrow(schema.field(k).dtype))
                 for k, v in data.items()})
        return self.from_arrow(table)

    def from_pandas(self, df) -> "DataFrame":
        import pyarrow as pa
        return self.from_arrow(pa.Table.from_pandas(df, preserve_index=False))

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)

    # -- runtime ------------------------------------------------------------
    @property
    def runtime(self):
        if self._runtime is None:
            with self._lazy_lock:
                if self._runtime is not None:
                    return self._runtime
                self._build_runtime()
        return self._runtime

    def _build_runtime(self) -> None:
        from .mem.runtime import TpuRuntime
        limit = None
        if int(self.conf.get(C.CLUSTER_EXECUTORS)) > 1:
            # cluster mode: the N executor pools already claim half of
            # the session budget (plugin.TpuCluster); the driving
            # session's compute pool takes the other half so combined
            # accounting reflects ONE physical device, not two.
            # configured_pool_bytes honors an explicit poolSizeBytes
            # before falling back to allocFraction of detected HBM.
            from .mem.runtime import configured_pool_bytes
            limit = configured_pool_bytes(self.conf) // 2
        self._runtime = TpuRuntime(self.conf, pool_limit_bytes=limit)

    @property
    def cluster(self):
        """Multi-executor host-mode cluster, or None (plugin.TpuCluster;
        enabled by spark.rapids.sql.tpu.cluster.executors > 1)."""
        if getattr(self, "_cluster", None) is None:
            with self._lazy_lock:
                if getattr(self, "_cluster", None) is None:
                    if int(self.conf.get(C.CLUSTER_EXECUTORS)) > 1:
                        from .plugin import TpuCluster
                        self._cluster = TpuCluster(self.conf)
                    else:
                        self._cluster = False  # resolved: disabled
        return self._cluster or None

    def set(self, key: str, value) -> "TpuSession":
        self.conf.set(key, value)
        return self

    def progress(self) -> Dict:
        """Live progress snapshot, advancing monotonically while work
        happens.  With an attached ProcCluster (`ProcCluster(...,
        session=session)`) this is the heartbeat monitor's cluster
        rollup; for a local session it tracks executed queries, the
        in-flight query's journal growth, and cumulative output rows.
        `score` is the single never-decreasing figure."""
        pc = self._proc_cluster
        if pc is not None:
            return pc.progress()
        from .metrics.journal import active_journal
        j = active_journal()
        events = j.event_count() if j is not None else 0
        rows = int(self.query_metrics_total.get("numOutputRows", 0))
        raw = self.queries_executed + events + rows
        # high-water: per-query journal ids restart, so the raw sum may
        # dip between queries — the surfaced score never does.  The
        # max() makes concurrent racing writes (watchdog/postmortem
        # threads snapshotting progress) order-independent: the water
        # mark only rises, so the lock would buy nothing.
        self._progress_high_water = max(self._progress_high_water, raw)  # tpulint: disable=TPU009 monotonic max is race-tolerant by construction
        out = {"queries": self.queries_executed,
               "journal_events": events, "rows": rows,
               "active_query": j is not None,
               "score": self._progress_high_water}
        if self._runtime is not None:
            # local-session twin of the cluster roll-up: the runtime's
            # store high-waters (pool_stats device_peak/host_peak/
            # disk_peak are store-tracked and monotonic until reset)
            ps = self._runtime.pool_stats()
            out["peak_memory"] = {
                f: int(ps.get(f, 0))
                for f in ("device_peak", "host_peak", "disk_peak")}
        return out

    # -- serving tier (serve/) ----------------------------------------------
    @property
    def scheduler(self):
        """The session's QueryScheduler, built on first submit() from the
        spark.rapids.sql.tpu.serve.* confs; None before that."""
        return self._scheduler

    def submit(self, df, priority: int = 0,
               memory_need: Optional[int] = None,
               deadline_ms: Optional[float] = None):
        """Submit a DataFrame (or logical plan) for concurrent execution;
        returns a serve.QueryFuture immediately.  Queries flow through
        the priority queue, fair-share admission control, the
        parameterized plan cache and a per-query memory budget
        (docs/tuning-guide.md, Concurrent serving and plan caching);
        the blocking collect() paths are unchanged.  `deadline_ms`
        bounds the query end to end: past it the query fails with a
        typed QueryDeadlineExceeded at its next lifecycle checkpoint —
        or is shed at admission when the remaining deadline cannot cover
        the estimated plan+compile cost (docs/tuning-guide.md, Query
        lifecycle)."""
        if self._scheduler is None:
            with self._serve_lock:
                if self._scheduler is None:
                    from .serve.scheduler import QueryScheduler
                    self._scheduler = QueryScheduler(self)
        return self._scheduler.submit(df, priority=priority,
                                      memory_need=memory_need,
                                      deadline_ms=deadline_ms)

    def shutdown_serving(self, wait: bool = True) -> None:
        """Stop the scheduler's workers (idempotent).  In-flight queries
        finish; queued-but-never-admitted futures resolve with a
        RuntimeError so nothing blocks forever in result()."""
        with self._serve_lock:
            sched = self._scheduler
        if sched is not None:
            sched.shutdown(wait=wait)

    # -- execution core ------------------------------------------------------

    def _collect_physical(self, physical, out_schema, *, budget_bytes=0,
                          sched_attrs=None, future=None):
        """Execute an already-planned physical tree to ONE pyarrow Table —
        the shared body of DataFrame.to_arrow and the serving tier's
        worker threads.  Installs the per-query observability scope, the
        memory-ledger query scope (buffer ownership + optional budget)
        and the device semaphore (wait time attributed to THIS query's
        root-node metrics)."""
        import pyarrow as pa
        with named_range("begin"):
            runtime = self.runtime
            on_device = isinstance(physical, TpuExec)
            # adaptive execution wraps at EXECUTE time (never in
            # physical_plan()): map stages materialize first and the reduce
            # side re-plans from observed sizes (adaptive/executor.py)
            from .adaptive.executor import maybe_wrap_adaptive
            physical = maybe_wrap_adaptive(physical, self.conf)
            if on_device:
                physical = B.DeviceToHostExec(physical)
            qe = self._begin_execution(physical, runtime)
            if future is not None:
                future.query_id = qe.query_id
            if sched_attrs and qe.journal is not None:
                # the scheduling decision, journaled into THIS query's
                # journal under its own trace context (kind `sched`)
                qe.journal.instant("sched", "admitted", **sched_attrs)
            ctx = ExecContext(self.conf, runtime=runtime,
                              cluster=self.cluster, journal=qe.journal,
                              query_execution=qe)
            # lifecycle token of a scheduler-run query (serve/lifecycle.py):
            # installed on the ledger query scope so every tier's checkpoint
            # reaches it thread-locally; None for blocking collect() paths
            # and when the serve.lifecycle.enabled kill switch is off
            lifecycle = getattr(future, "lifecycle", None) \
                if future is not None else None
            if lifecycle is not None:
                lifecycle.journal = qe.journal
        error = None
        qscope = None
        try:
            with runtime.ledger.query_scope(f"q{qe.query_id}",
                                            budget_bytes,
                                            lifecycle=lifecycle) as qscope:
                with named_range("execute", q=qe.query_id), \
                        contextlib.ExitStack() as slot:
                    if on_device:
                        # device semaphore: this "task" holds a device
                        # slot for the duration of its device work
                        # (reference: GpuSemaphore.acquireIfNecessary,
                        # released on task completion).  Blocked-wait
                        # time lands on the query's own root-node
                        # metrics, not the runtime globals (per-query
                        # attribution under concurrency).
                        with named_range("semaphore", q=qe.query_id):
                            slot.enter_context(runtime.semaphore.held(
                                metrics=physical.metrics))
                    tables = list(physical.execute_cpu(ctx))
        except BaseException as e:
            error = e
            raise
        finally:
            # task-completion cleanup, success or failure: releases
            # resources operators registered (e.g. shuffle partitions
            # orphaned by a mid-write error)
            ctx.run_cleanups()
            if error is not None:
                # owner-confined cleanup for lifecycle kills: after the
                # shuffle cleanups above, free whatever buffers still
                # carry this query's owner stamp across device/host/disk
                # — a cancelled or past-deadline query must not leak
                # pool bytes (received shuffle buffers, parked
                # checkpoints, partial writes the cleanups missed)
                from .serve.lifecycle import (QueryCancelled,
                                              QueryDeadlineExceeded)
                if isinstance(error, (QueryCancelled,
                                      QueryDeadlineExceeded)):
                    freed = runtime.release_owner(f"q{qe.query_id}")
                    if qe.journal is not None:
                        qe.journal.instant(
                            "lifecycle", "ownerCleanup",
                            q=f"q{qe.query_id}", freed_bytes=freed,
                            reason=type(error).__name__)
            self._finish_execution(qe, error)
            if future is not None:
                # phase breakdown for the serving SLO histograms
                # (metrics/slo.py): the scheduler observes these into
                # the per-priority compile/execute/spill distributions
                try:
                    from .metrics import names as MN
                    agg = qe.aggregate()
                    # stageCompileTime is NODE-recorded, so the
                    # aggregate is per-query even under concurrency;
                    # spill time comes from THIS query's scope (the
                    # runtime spillTime metric is shared — a delta
                    # window would absorb concurrent neighbors' spills)
                    future.compile_seconds = float(
                        agg.get(MN.STAGE_COMPILE_TIME, 0.0))
                    future.spill_seconds = float(
                        qscope.spill_seconds if qscope is not None
                        else 0.0)
                    future.exec_seconds = float(qe.duration or 0.0)
                except Exception:  # noqa: BLE001 — reporting only
                    pass  # tpulint: disable=TPU006 phase metrics are best-effort; the future's result/error is already set by the caller
        if not tables:
            from .types import to_arrow
            return pa.table({f.name: pa.array([], type=to_arrow(f.dtype))
                             for f in out_schema})
        return pa.concat_tables(tables)

    # -- planning -----------------------------------------------------------
    def plan(self, logical: L.LogicalPlan) -> ExecNode:
        from .plan.pushdown import optimize_scans
        logical = optimize_scans(logical, self.conf)
        meta = PlanMeta(logical, self.conf)
        meta.tag_tree()
        explain_mode = self.conf.explain
        if explain_mode in ("ALL", "NOT_ON_TPU", "NOT_ON_GPU"):
            text = meta.explain(verbose=explain_mode == "ALL")
            if explain_mode == "ALL" or "!" in text:
                print(text, file=sys.stderr)
        physical = convert(meta)
        return T.finalize(physical, self.conf)

    def explain_str(self, logical: L.LogicalPlan) -> str:
        meta = PlanMeta(logical, self.conf)
        meta.tag_tree()
        return meta.explain()


class DataFrameReader:
    def __init__(self, session: TpuSession):
        self.session = session
        self._options: Dict = {}

    def option(self, k, v) -> "DataFrameReader":
        self._options[k] = v
        return self

    def options(self, **kw) -> "DataFrameReader":
        self._options.update(kw)
        return self

    def parquet(self, *paths: str) -> "DataFrame":
        from .io.scan import scan_info
        files, schema, opts = scan_info(paths, "parquet", self._options)
        return DataFrame(self.session,
                         L.LogicalScan(files, schema, "parquet", opts))

    def csv(self, *paths: str, schema: Optional[Schema] = None,
            header: bool = False) -> "DataFrame":
        from .io.scan import scan_info
        opts = dict(self._options)
        opts.setdefault("header", header)
        files, schema, opts = scan_info(paths, "csv", opts, schema)
        return DataFrame(self.session,
                         L.LogicalScan(files, schema, "csv", opts))

    def orc(self, *paths: str) -> "DataFrame":
        from .io.scan import scan_info
        files, schema, opts = scan_info(paths, "orc", self._options)
        return DataFrame(self.session,
                         L.LogicalScan(files, schema, "orc", opts))


class DataFrame:
    def __init__(self, session: TpuSession, plan: L.LogicalPlan):
        self.session = session
        self.plan = plan

    # -- transformations ----------------------------------------------------
    def _wrap_cols(self, cols):
        out = []
        for c in cols:
            if isinstance(c, str):
                out.append(col(c))
            elif isinstance(c, ColumnExpr):
                out.append(c)
            else:
                out.append(lit(c))
        return out

    def __getitem__(self, name: str) -> ColumnExpr:
        if name not in self.schema.names:
            raise KeyError(name)
        return col(name)

    def _project(self, exprs) -> "DataFrame":
        """Build a projection, splitting out window expressions (including
        ones nested inside arithmetic, like sum(v).over(w) + 1) into
        LogicalWindow nodes beneath the project (Spark's
        ExtractWindowExpressions analyzer rule, in spirit)."""
        win: list = []

        def extract(e):
            if not isinstance(e, ColumnExpr):
                return e
            if e.op == "WindowExpr":
                if e._alias is None:
                    e = e.alias(f"_w{len(win)}")
                win.append(e)
                return col(e.output_name)

            def walk(a):
                if isinstance(a, ColumnExpr):
                    return extract(a)
                if isinstance(a, (list, tuple)):
                    return type(a)(walk(x) for x in a)
                return a
            new_args = tuple(walk(a) for a in e.args)
            return ColumnExpr(e.op, new_args, alias=e._alias)

        # generators (explode/posexplode) first: they change the row count
        gens = [e for e in exprs if e.op in ("Explode", "PosExplode")]
        if len(gens) > 1:
            raise ValueError("only one generator (explode/posexplode) is "
                             "allowed per select, like Spark")
        if gens:
            g = gens[0]
            pos = g.op == "PosExplode"
            names = (["pos"] if pos else []) + [g._alias or "col"]
            base = DataFrame(self.session,
                             L.LogicalGenerate(g, names, self.plan))
            out = []
            for e in exprs:
                if e is g:
                    out.extend(col(n) for n in names)
                else:
                    out.append(e)
            return base._project(out)

        rewritten = [extract(e) for e in exprs]
        if not win:
            return self._resolved(L.LogicalProject(exprs, self.plan))
        groups: dict = {}
        for e in win:
            spec = e.args[1]
            groups.setdefault(spec._group_key(), (spec, []))[1].append(e)
        child = self.plan
        # grouping() in a window's keys, or above it: the rollup's id has
        # to come up through every window below its last user
        later = [has_grouping([es, spec.parts, spec.orders])
                 for spec, es in groups.values()] + [has_grouping(rewritten)]
        for i, (spec, es) in enumerate(groups.values()):
            child = resolve_grouping(
                L.LogicalWindow(es, spec.parts, spec.orders, child),
                self.session.conf, want_id=any(later[i + 1:]))
        return self._resolved(L.LogicalProject(rewritten, child))

    def _resolved(self, node) -> "DataFrame":
        """The frame of `node`, a node just built over this frame's plan,
        with any grouping() / grouping_id() in it resolved against the
        rollup or cube below (plan/grouping.py)."""
        return DataFrame(self.session,
                         resolve_grouping(node, self.session.conf))

    def select(self, *cols) -> "DataFrame":
        return self._project(self._wrap_cols(cols))

    def with_column(self, name: str, expr: ColumnExpr) -> "DataFrame":
        exprs = [col(n) for n in self.schema.names if n != name]
        exprs.append(expr.alias(name))
        return self._project(exprs)

    withColumn = with_column

    def filter(self, condition: ColumnExpr) -> "DataFrame":
        return self._resolved(L.LogicalFilter(condition, self.plan))

    where = filter

    def group_by(self, *cols) -> "GroupedData":
        return GroupedData(self, self._wrap_cols(cols))

    groupBy = group_by

    def rollup(self, *cols) -> "GroupedData":
        """GROUP BY ROLLUP: grouping sets {(k1..kn), (k1..kn-1), ..., ()}
        planned as an Expand fan-out + one hash aggregate keyed on
        (keys..., grouping id), Spark's physical shape (reference:
        GpuExpandExec, rapids/GpuExpandExec.scala)."""
        return GroupedData(self, self._wrap_cols(cols), rollup=True)

    def cube(self, *cols) -> "GroupedData":
        """GROUP BY CUBE: every subset of the keys as a grouping set (the
        same Expand + grouping-id plan as rollup, 2^n projections)."""
        return GroupedData(self, self._wrap_cols(cols), rollup=True,
                           cube=True)

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def join(self, other: "DataFrame", on=None, how: str = "inner"
             ) -> "DataFrame":
        how = how.replace("outer", "").rstrip("_") or how
        how = {"leftsemi": "left_semi", "leftanti": "left_anti",
               "left_semi": "left_semi", "left_anti": "left_anti",
               "inner": "inner", "left": "left", "cross": "cross",
               "full": "full", "right": "right"}.get(how, how)
        if isinstance(on, (list, tuple)) and on \
                and all(isinstance(x, str) for x in on):
            return DataFrame(self.session, L.LogicalJoin(
                self.plan, other.plan, how, using=list(on)))
        if isinstance(on, str):
            return DataFrame(self.session, L.LogicalJoin(
                self.plan, other.plan, how, using=[on]))
        return DataFrame(self.session, L.LogicalJoin(
            self.plan, other.plan, how, condition=on))

    def order_by(self, *orders) -> "DataFrame":
        os = []
        for o in orders:
            if isinstance(o, SortOrder):
                os.append(o)
            elif isinstance(o, str):
                os.append(SortOrder(col(o)))
            else:
                os.append(SortOrder(o))
        return self._resolved(L.LogicalSort(os, self.plan))

    orderBy = sort = order_by

    def limit(self, n: int) -> "DataFrame":
        return DataFrame(self.session, L.LogicalLimit(n, self.plan))

    def hint(self, name: str, *args) -> "DataFrame":
        """Spark-style plan hints; \"broadcast\" marks this side for a
        broadcast hash join."""
        hints = set(getattr(self.plan, "_hints", ())) | {name.lower()}
        self.plan._hints = hints
        return self

    def union(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(self.session,
                         L.LogicalUnion([self.plan, other.plan]))

    unionAll = union

    def distinct(self) -> "DataFrame":
        return DataFrame(self.session, L.LogicalDistinct(self.plan))

    def repartition(self, n: int, *cols) -> "DataFrame":
        keys = self._wrap_cols(cols)
        mode = "hash" if keys else "round_robin"
        return DataFrame(self.session, L.LogicalRepartition(
            n, keys, self.plan, mode))

    def repartition_by_range(self, n: int, *orders) -> "DataFrame":
        keys, asc, nf = [], [], []
        for o in orders:
            if isinstance(o, str):
                o = SortOrder(col(o))
            elif not isinstance(o, SortOrder):
                o = SortOrder(o)
            keys.append(o.child)
            asc.append(o.ascending)
            nf.append(o.effective_nulls_first)
        return DataFrame(self.session, L.LogicalRepartition(
            n, keys, self.plan, "range", asc, nf))

    repartitionByRange = repartition_by_range

    # -- actions ------------------------------------------------------------
    @property
    def schema(self) -> Schema:
        return plan_schema(self.plan, self.session.conf)

    def explain(self) -> str:
        return self.session.explain_str(self.plan)

    def physical_plan(self) -> ExecNode:
        return self.session.plan(self.plan)

    def to_arrow(self):
        with named_range("plan"):
            physical = self.session.plan(self.plan)
            schema = self.schema
        return self.session._collect_physical(physical, schema)

    def collect(self) -> List[tuple]:
        table = self.to_arrow()
        with named_range("rows", rows=table.num_rows):
            return [tuple(r.values()) for r in table.to_pylist()]

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    def count(self) -> int:
        return self.to_arrow().num_rows

    def show(self, n: int = 20):
        print(self.limit(n).to_arrow().to_pandas())

    @property
    def write(self) -> "DataFrameWriter":
        return DataFrameWriter(self)

    # ML integration: ColumnarRdd equivalent (reference: ColumnarRdd.scala)
    def to_device_batches(self):
        """Export device ColumnarBatches for ML handoff (requires
        spark.rapids.sql.exportColumnarRdd=true, like the reference)."""
        if not self.session.conf.get(C.EXPORT_COLUMNAR_RDD):
            raise RuntimeError(
                f"set {C.EXPORT_COLUMNAR_RDD.key}=true to export device "
                "columnar data")
        with named_range("plan"):
            physical = self.session.plan(self.plan)
        with named_range("begin"):
            runtime = self.session.runtime
            from .adaptive.executor import maybe_wrap_adaptive
            physical = maybe_wrap_adaptive(physical, self.session.conf)
            qe = self.session._begin_execution(physical, runtime)
            ctx = ExecContext(self.session.conf, runtime=runtime,
                              cluster=self.session.cluster,
                              journal=qe.journal, query_execution=qe)
        error = None
        try:
            if isinstance(physical, TpuExec):
                runtime.semaphore.acquire_if_necessary()
                try:
                    yield from physical.execute(ctx)
                finally:
                    runtime.semaphore.task_done()
            else:
                for table in physical.execute_cpu(ctx):
                    from .columnar import ColumnarBatch
                    yield ColumnarBatch.from_arrow(table)
        except BaseException as e:
            error = e
            raise
        finally:
            ctx.run_cleanups()
            self.session._finish_execution(qe, error)


class GroupedData:
    def __init__(self, df: DataFrame, keys: List[ColumnExpr],
                 rollup: bool = False, cube: bool = False):
        self.df = df
        self.keys = keys
        self.rollup = rollup
        self.cube = cube

    def agg(self, *aggs) -> "DataFrame":
        """Aggregate; compound expressions over aggregates (e.g.
        sum(a)/sum(b)) are split into leaf aggregates + a result projection,
        the way Spark's analyzer plans them (and the reference's
        resultProjection phase executes them, aggregate.scala:403-510)."""
        from .ops.aggregates import AGG_FUNCS
        leaf_aggs: List[ColumnExpr] = []
        projections: List[ColumnExpr] = []
        compound = False

        def walk(e):
            if not isinstance(e, ColumnExpr):
                return e
            if e.op in AGG_FUNCS:
                name = f"_agg{len(leaf_aggs)}"
                leaf_aggs.append(e.alias(name))
                return col(name)

            def sub(a):
                if isinstance(a, ColumnExpr):
                    return walk(a)
                if isinstance(a, (list, tuple)):
                    return type(a)(sub(x) for x in a)
                return a
            return ColumnExpr(e.op, tuple(sub(a) for a in e.args),
                              alias=e._alias)

        for e in aggs:
            if isinstance(e, ColumnExpr) and e.op in AGG_FUNCS:
                leaf_aggs.append(e)
                projections.append(col(e.output_name))
            else:
                before = len(leaf_aggs)
                rewritten = walk(e)
                if len(leaf_aggs) == before and not has_grouping(e):
                    raise ValueError(
                        f"aggregate expression {e!r} contains no aggregate "
                        "function")
                compound = True
                projections.append(rewritten.alias(e.output_name))

        child_plan = self.df.plan
        group_keys = list(self.keys)
        if self.rollup:
            child_plan, group_keys = self._expand_rollup(child_plan)
        agg_plan = L.LogicalAggregate(
            group_keys, leaf_aggs, child_plan,
            rollup_keys=([k.output_name for k in self.keys]
                         if self.rollup else None))
        key_cols = [col(k.output_name) for k in self.keys]
        if not compound and not self.rollup:
            return DataFrame(self.df.session, agg_plan)
        if not compound:
            projections = [col(a.output_name) for a in leaf_aggs]
        # rollup drops the internal grouping-id column here, after any
        # grouping() / grouping_id() among the outputs has read it
        return self.df._resolved(L.LogicalProject(
            key_cols + projections, agg_plan))

    def _expand_rollup(self, child_plan):
        """Expand fan-out for ROLLUP grouping sets: one projection per set.
        Every ORIGINAL column passes through unchanged (aggregates over a
        grouping-key column must still see real values in subtotal rows —
        Spark's Expand nulls only duplicated grouping COPIES), plus one
        nullable copy per key for grouping and a grouping-id column so a
        rolled-up null never merges with a data null."""
        schema = self.df.schema
        key_names = [k.output_name for k in self.keys]
        for k, name in zip(self.keys, key_names):
            if k.op != "col" or name not in schema.names:
                raise ValueError(
                    "rollup keys must be existing columns; project "
                    f"{name!r} first")
        gid = "_grouping_id"
        n = len(self.keys)
        if self.cube:
            # every subset; grouping id = bitmask of PRUNED keys (Spark's
            # grouping_id bit convention)
            sets = [[name for b, name in enumerate(key_names)
                     if not (mask >> (n - 1 - b)) & 1]
                    for mask in range(1 << n)]
            gids = list(range(1 << n))
        else:
            sets = [key_names[:g] for g in range(n, -1, -1)]
            gids = [(1 << (n - g)) - 1 for g in range(n, -1, -1)]
        projections = []
        for kept, g_val in zip(sets, gids):
            proj = [col(f.name) for f in schema]
            for name in key_names:
                f = schema.field(name)
                copy = (col(name) if name in kept
                        else lit(None).cast(f.dtype))
                proj.append(copy.alias(f"_gkey_{name}"))
            proj.append(lit(g_val).alias(gid))
            projections.append(proj)
        expand = L.LogicalExpand(projections, child_plan)
        group_keys = [col(f"_gkey_{name}").alias(name)
                      for name in key_names] + [col(gid)]
        return expand, group_keys

    def count(self) -> "DataFrame":
        return self.agg(functions.count(lit(1)).alias("count"))


class DataFrameWriter:
    def __init__(self, df: DataFrame):
        self.df = df
        self._options: Dict = {}
        self._partition_by: List[str] = []

    def option(self, k, v) -> "DataFrameWriter":
        self._options[k] = v
        return self

    def partition_by(self, *cols: str) -> "DataFrameWriter":
        self._partition_by = list(cols)
        return self

    partitionBy = partition_by

    def parquet(self, path: str):
        self._write(path, "parquet")

    def csv(self, path: str):
        self._write(path, "csv")

    def orc(self, path: str):
        self._write(path, "orc")

    def _write(self, path: str, fmt: str):
        plan = L.LogicalWrite(path, fmt, self.df.plan, self._options,
                              self._partition_by)
        physical = self.df.session.plan(plan)
        runtime = self.df.session.runtime
        from .adaptive.executor import maybe_wrap_adaptive
        physical = maybe_wrap_adaptive(physical, self.df.session.conf)
        qe = self.df.session._begin_execution(physical, runtime)
        ctx = ExecContext(self.df.session.conf, runtime=runtime,
                          cluster=self.df.session.cluster,
                          journal=qe.journal, query_execution=qe)
        error = None
        try:
            if isinstance(physical, TpuExec):
                with runtime.semaphore.held():
                    for _ in physical.execute(ctx):
                        pass
            else:
                for _ in physical.execute_cpu(ctx):
                    pass
        except BaseException as e:
            error = e
            raise
        finally:
            ctx.run_cleanups()
            self.df.session._finish_execution(qe, error)
