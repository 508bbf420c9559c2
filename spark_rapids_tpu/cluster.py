"""Multi-process cluster driver: spawn executor workers, ship plan
fragments, run distributed map/shuffle/reduce over the socket wire.

This is the PROCESS-level deployment of the shuffle stack — the analogue
of a Spark cluster running the reference's UCX shuffle
(shuffle-plugin/.../RapidsShuffleInternalManager.scala + UCX transport):
`ProcCluster` spawns N worker processes (shuffle/worker.py), each with its
own runtime + ShuffleEnv + SocketTransport server; the driver distributes
the peer address map (the management handshake), sends map fragments to
every worker, assigns reduce partitions round-robin, and concatenates the
arrow IPC results.  Shuffle bytes cross real process boundaries over TCP;
on a TPU pod the same wire is the DCN path between hosts while ICI
collectives handle the in-mesh exchange (shuffle/ici.py).

In-process `plugin.TpuCluster` remains the single-interpreter deployment
for tests and one-host runs; `ProcCluster` is its multi-process twin.
"""
from __future__ import annotations

import json
import logging
import os
import pickle
import random
import re
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

from .config import TpuConf
from .metrics.journal import journal_event
from .metrics.registry import count_swallowed

log = logging.getLogger("spark_rapids_tpu.cluster")


class HeartbeatMonitor:
    """Driver-side live progress: polls every worker's `rpc_heartbeat`
    on an interval over DEDICATED SocketClients — a long-running task rpc
    holds its own client's lock for the whole call, so liveness must ride
    separate sockets (the worker server threads answer concurrently).

    What one heartbeat buys:
      * progress: monotonic cluster totals (tasks completed, rows
        written, wire bytes) accumulated restart-aware, surfaced as
        `cluster.progress()` / `session.progress()`;
      * liveness: per-worker heartbeat lag (`heartbeatLag`) + missed-poll
        counting (`numMissedHeartbeats`);
      * the hung-task watchdog: a task active past
        `spark.rapids.sql.tpu.trace.hungTaskTimeoutMs` in successive
        snapshots is logged once and counted (`numHungTasks`);
      * clock probes: every round trip is an NTP-style sample
        (local-before, worker wall, local-after) feeding the merged
        timeline's per-worker offset estimation (metrics/timeline.py).
    """

    def __init__(self, cluster: "ProcCluster", interval_s: float,
                 hung_timeout_s: float):
        self.cluster = cluster
        self.interval_s = max(float(interval_s), 0.05)
        self.hung_timeout_s = float(hung_timeout_s)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._clients: Dict[str, tuple] = {}
        self.latest: Dict[str, dict] = {}
        self.last_ok_mono: Dict[str, float] = {}
        self.clock_probes: Dict[str, deque] = {}
        self._last_seen: Dict[str, dict] = {}
        self._warned_hung = set()
        self.started_mono = time.monotonic()
        self.missed_heartbeats = 0
        self.hung_tasks = 0
        self.max_lag_s = 0.0
        # watchdog hook: called with each newly-flagged hung task's
        # snapshot AFTER the monitor lock is released (_ingest) — the
        # post-mortem trigger behind it does rpc sweeps and must never
        # run under (or deadlock against) the monitor's own lock
        self.on_hung = None
        self.totals = {"heartbeats": 0, "tasks_completed": 0,
                       "tasks_failed": 0, "rows_written": 0,
                       "wire_bytes": 0}
        # per-executor memory high-waters from heartbeat pool stats,
        # accumulated max-monotonic across restarts: a replaced worker's
        # reset peaks never regress the cluster roll-up (same contract
        # as the monotonic counter totals above)
        self._peak_seen: Dict[str, Dict[str, int]] = {}
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="heartbeat-monitor")
        self._thread.start()

    # -- polling -------------------------------------------------------------

    def _client_for(self, worker):
        from .shuffle.net import SocketClient
        addr = tuple(worker.address)
        stale = None
        with self._lock:
            if self._stop.is_set():
                # stop() already closed + cleared the clients; never
                # re-create one behind its back (fd leak on shutdown)
                return None
            cur = self._clients.get(worker.executor_id)
            if cur is not None and cur[0] == addr:
                return cur[1]
            stale = cur[1] if cur is not None else None
            # inject_faults=False: liveness polls must not consume the
            # deterministic net-fault ordinals a test armed for the data
            # plane.  The connect bound mirrors the poll's rpc timeout —
            # one blackholed worker must not starve the other workers'
            # heartbeats behind the transport's 30s data-plane default.
            client = SocketClient(self.cluster._transport, addr,
                                  inject_faults=False,
                                  connect_timeout=max(
                                      self.interval_s * 2, 2.0))
            self._clients[worker.executor_id] = (addr, client)
        if stale is not None:
            stale.close()  # worker was replaced on a new port
        return client

    def poll_once(self) -> None:
        for worker in list(self.cluster.workers):
            if self._stop.is_set():
                return
            try:
                client = self._client_for(worker)
                if client is None:
                    return
                t0 = time.time_ns()
                hb = client.rpc(
                    "heartbeat",
                    _rpc_timeout=max(self.interval_s * 2, 2.0))
                t1 = time.time_ns()
            except Exception as e:  # noqa: BLE001 — liveness, not control
                with self._lock:
                    self.missed_heartbeats += 1
                    stale = self._clients.pop(worker.executor_id, None)
                if stale is not None:
                    try:
                        stale[1].close()
                    except Exception:  # noqa: BLE001 — already broken
                        pass  # tpulint: disable=TPU006 closing an already-broken heartbeat client; the poll failure itself is logged+counted just below
                log.debug("heartbeat poll of %s failed: %r",
                          worker.executor_id, e)
                continue
            self._ingest(worker.executor_id, hb, t0, t1)

    def _ingest(self, executor: str, hb: dict, t0: int, t1: int) -> None:
        newly_hung: List[dict] = []
        with self._lock:
            self.latest[executor] = hb
            self.last_ok_mono[executor] = time.monotonic()
            self.clock_probes.setdefault(executor, deque(maxlen=64)) \
                .append((t0, hb.get("wall_ns", t0), t1))
            # restart-aware monotonic accumulation: a replaced worker's
            # counters reset to zero — its full new value is the delta,
            # so cluster totals NEVER go backwards (progress() contract)
            last = self._last_seen.get(executor)
            fresh = last is None or last.get("pid") != hb.get("pid")

            def delta(field, new):
                return new if fresh else max(0, new - last.get(field, 0))

            counters = hb.get("counters", {}) or {}
            wire = (int(counters.get("bytes_sent", 0))
                    + int(counters.get("bytes_received", 0)))
            self.totals["heartbeats"] += 1
            self.totals["tasks_completed"] += delta(
                "tasks_completed", int(hb.get("tasks_completed", 0)))
            self.totals["tasks_failed"] += delta(
                "tasks_failed", int(hb.get("tasks_failed", 0)))
            self.totals["rows_written"] += delta(
                "rows_written", int(hb.get("rows_written", 0)))
            self.totals["wire_bytes"] += delta("wire_bytes", wire)
            self._last_seen[executor] = {
                "pid": hb.get("pid"),
                "tasks_completed": int(hb.get("tasks_completed", 0)),
                "tasks_failed": int(hb.get("tasks_failed", 0)),
                "rows_written": int(hb.get("rows_written", 0)),
                "wire_bytes": wire}
            pool = hb.get("pool", {}) or {}
            peaks = self._peak_seen.setdefault(executor, {})
            for field in ("device_peak", "host_peak", "disk_peak"):
                v = int(pool.get(field, 0) or 0)
                if v > peaks.get(field, 0):
                    peaks[field] = v
            if self.hung_timeout_s > 0:
                for task in hb.get("active_tasks", []) or []:
                    if task.get("elapsed_s", 0) <= self.hung_timeout_s:
                        continue
                    key = (executor, hb.get("pid"), task.get("span"),
                           task.get("name"))
                    if key in self._warned_hung:
                        continue
                    self._warned_hung.add(key)
                    self.hung_tasks += 1
                    log.warning(
                        "hung-task watchdog: %s task %r (stage %s) "
                        "active for %.1fs (> %.1fs)", executor,
                        task.get("name"), task.get("stage"),
                        task.get("elapsed_s", 0), self.hung_timeout_s)
                    newly_hung.append(dict(task, executor=executor))
        # watchdog hook outside the lock: the post-mortem dump it
        # triggers sweeps rpcs and must not serialize the monitor
        if newly_hung and self.on_hung is not None:
            for info in newly_hung:
                try:
                    self.on_hung(info)
                except Exception as e:  # noqa: BLE001 — observability
                    count_swallowed(
                        "numPostmortemErrors", "spark_rapids_tpu.cluster",
                        "hung-task postmortem hook failed (%r)", e)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.poll_once()
                # fold the current per-worker lag into the heartbeatLag
                # high-water every sweep — an outage must register even
                # if nobody calls progress() while it lasts
                self.lag_s()
            except Exception:  # noqa: BLE001 — the monitor must survive
                log.debug("heartbeat poll sweep failed", exc_info=True)

    # -- surfaces ------------------------------------------------------------

    def lag_s(self) -> Dict[str, float]:
        """Seconds since each worker was last heard from (workers never
        heard from count from monitor start)."""
        now = time.monotonic()
        with self._lock:
            out = {w.executor_id:
                   now - self.last_ok_mono.get(w.executor_id,
                                               self.started_mono)
                   for w in self.cluster.workers}
            if out:
                self.max_lag_s = max(self.max_lag_s, max(out.values()))
        return out

    def probes(self) -> Dict[str, list]:
        with self._lock:
            return {ex: list(dq) for ex, dq in self.clock_probes.items()}

    def peak_memory(self) -> dict:
        """Cluster peak memory from heartbeat pool stats: per-executor
        restart-aware high-waters (max over every epoch of that executor
        id) plus the cluster sum per tier.  Monotonic: values never
        decrease over the monitor's lifetime."""
        with self._lock:
            per_worker = {ex: dict(p) for ex, p in self._peak_seen.items()}
        return {
            "per_worker": per_worker,
            **{f: sum(p.get(f, 0) for p in per_worker.values())
               for f in ("device_peak", "host_peak", "disk_peak")},
        }

    def progress(self) -> dict:
        lag = self.lag_s()
        with self._lock:
            active = [dict(t, executor=ex)
                      for ex, hb in self.latest.items()
                      for t in (hb.get("active_tasks") or [])]
            totals = dict(self.totals)
            out = {
                **totals,
                "workers": len(self.cluster.workers),
                "active_tasks": active,
                "heartbeat_lag_s": max(lag.values()) if lag else 0.0,
                "missed_heartbeats": self.missed_heartbeats,
                "hung_tasks": self.hung_tasks,
                # single monotonic figure for "is the query advancing?":
                # every component is a cluster-lifetime high-water total
                # of WORK (heartbeats deliberately excluded — a fully
                # hung cluster keeps answering polls, and liveness is
                # already surfaced as heartbeat_lag_s)
                "score": (totals["tasks_completed"]
                          + totals["rows_written"]
                          + totals["wire_bytes"]),
            }
        # cluster peak memory (restart-aware max roll-up of each worker's
        # pool_stats high-waters; peak_memory() takes the lock itself)
        out["peak_memory"] = self.peak_memory()
        return out

    def metrics(self) -> dict:
        """The lint-checked metric names this monitor owns
        (docs/monitoring.md): folded into observability rollups."""
        from .metrics import names as MN
        return {MN.HEARTBEAT_LAG: self.max_lag_s,
                MN.NUM_HUNG_TASKS: self.hung_tasks,
                MN.NUM_MISSED_HEARTBEATS: self.missed_heartbeats}

    def stop(self) -> None:
        self._stop.set()
        # let an in-flight poll finish (bounded by its rpc timeout) so it
        # cannot re-create clients after the close/clear below
        self._thread.join(timeout=5.0)
        with self._lock:
            clients = list(self._clients.values())
            self._clients.clear()
        for _addr, client in clients:
            try:
                client.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass  # tpulint: disable=TPU006 driver shutdown close of a possibly-dead control client; nothing actionable remains

# the control RPC flattens worker-side exceptions to strings; FetchFailed's
# repr deliberately carries this machine-parseable peer marker so the
# driver can identify WHICH peer served garbage even through two layers of
# wrapping (mem/integrity.FetchFailed.__repr__)
_FETCH_FAILED_RE = re.compile(r"FetchFailed\(peer='([^']+)'")


def _fetch_failed_peer(err: BaseException) -> Optional[str]:
    """Executor id of the peer a (possibly rpc-flattened) FetchFailed
    blames, scanning the exception chain; None when no FetchFailed is
    involved."""
    seen = set()
    e: Optional[BaseException] = err
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        peer = getattr(e, "peer", None)
        if peer is not None and type(e).__name__ == "FetchFailed":
            return str(peer)
        m = _FETCH_FAILED_RE.search(str(e))
        if m:
            return m.group(1)
        e = e.__cause__ or e.__context__
    return None


class WorkerProc:
    """One spawned executor worker and its control-plane client."""

    def __init__(self, executor_id: str, conf_env: str, cpu: bool,
                 ready_timeout: float):
        env = dict(os.environ)
        env["SPARK_RAPIDS_TPU_CONF"] = conf_env
        if cpu:
            env["SPARK_RAPIDS_TPU_WORKER_CPU"] = "1"
            env["JAX_PLATFORMS"] = "cpu"
        self.executor_id = executor_id
        self.cpu = cpu
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "spark_rapids_tpu.shuffle.worker",
             "--executor-id", executor_id],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=sys.stderr, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        self.address: Optional[tuple] = None
        self.http_port: Optional[int] = None
        # reader thread: readline() itself can block forever on a silently
        # hung worker (e.g. backend bring-up waiting on a chip another
        # process holds), so the deadline must bound the WAIT, not line
        # arrivals
        lines: List[str] = []
        cond = threading.Condition()

        def _pump():
            for ln in self.proc.stdout:
                with cond:
                    lines.append(ln)
                    cond.notify()
            with cond:
                lines.append("")  # EOF marker
                cond.notify()

        threading.Thread(target=_pump, daemon=True).start()
        deadline = time.time() + ready_timeout
        while self.address is None:
            with cond:
                while not lines:
                    remaining = deadline - time.time()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"worker {executor_id} never became ready")
                    cond.wait(min(remaining, 5))
                line = lines.pop(0)
            if line == "":
                raise RuntimeError(
                    f"worker {executor_id} exited before announcing "
                    f"(rc={self.proc.poll()})")
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                # library banner noise is normal; a FLOOD of it means the
                # worker is dying before it ever announces — keep each
                # skipped line visible at debug and counted
                count_swallowed("numWorkerStdoutNoise",
                                "spark_rapids_tpu.cluster",
                                "worker %s stdout noise before ready: %r",
                                executor_id, line[:200])
                continue
            if rec.get("ready"):
                self.address = (rec["host"], rec["port"])
                # telemetry endpoint, when the worker serves one
                # (metrics/http.py): /metrics, /healthz, /debug
                self.http_port = rec.get("http_port")
        self.client = None  # set by ProcCluster (needs its transport)

    def rpc(self, method: str, **kw):
        return self.client.rpc(method, **kw)

    def stop(self, grace_s: float = 10.0) -> None:
        try:
            self.rpc("shutdown")
        except Exception:  # noqa: BLE001 — already dead is fine
            pass  # tpulint: disable=TPU006 shutdown RPC to a worker that may already have exited; both outcomes are the goal state
        try:
            self.proc.stdin.close()  # workers also exit on stdin EOF
        except OSError:
            pass  # tpulint: disable=TPU006 stdin already closed means the EOF signal was already delivered
        deadline = time.time() + grace_s
        while self.proc.poll() is None and time.time() < deadline:
            time.sleep(0.05)
        if self.proc.poll() is None:
            # past the grace period: the chip (if any) frees when its
            # process dies, so device-attached workers are killed too
            self.proc.kill()


class ProcCluster:
    """N executor worker PROCESSES + a driver-side transport for control.

    Usage:
        cluster = ProcCluster(2, conf)
        table = cluster.run_map_reduce(map_plans, key_names, n_parts,
                                       reduce_plan)
        cluster.shutdown()
    """

    def __init__(self, n_workers: int, conf: Optional[dict] = None,
                 cpu: bool = True, ready_timeout: float = 120.0,
                 max_task_retries: int = 1, session=None):
        from .shuffle.net import SocketTransport
        self.conf = dict(conf or {})
        self._conf_env = json.dumps(self.conf)
        self._cpu = cpu
        self._ready_timeout = ready_timeout
        self.max_task_retries = max_task_retries
        self.workers: List[WorkerProc] = []
        try:
            for i in range(n_workers):
                self.workers.append(WorkerProc(f"exec-{i}", self._conf_env,
                                               cpu, ready_timeout))
        except Exception:
            self.shutdown()
            raise
        # driver-side transport: client factory only (no server)
        self._transport = SocketTransport()
        from . import config as C
        from .config import TpuConf
        tconf = TpuConf(self.conf)
        self._transport.configure(tconf)
        self._sid = 0
        self._lock = threading.Lock()
        self.task_retries = 0   # observability: recoveries this cluster
        self.lost_map_outputs = 0  # FetchFailed-driven recompute count
        # bumped on every worker replacement: statistics consumers
        # (exec/exchange._ShuffleHandle) treat a bump as "a map stage
        # died" and re-aggregate instead of re-planning on dead stats
        self.map_epoch = 0
        self._publish_peers()
        # distributed tracing + live heartbeats (docs/monitoring.md):
        # accumulated worker journal drains, straggler conf, and the
        # heartbeat monitor on its dedicated connections
        self.trace_enabled = bool(tconf.get(C.TRACE_ENABLED))
        self.straggler_factor = float(tconf.get(C.TRACE_STRAGGLER_FACTOR))
        # task deadlines / bounded retry / speculation (docs/tuning-guide
        # .md, Fault tolerance, speculation, and chaos testing)
        self._task_timeout_ms = int(tconf.get(C.TASK_TIMEOUT))
        self._hung_timeout_ms = int(tconf.get(C.TRACE_HUNG_TASK_TIMEOUT))
        self._task_backoff_s = int(tconf.get(C.TASK_RETRY_BACKOFF)) / 1e3
        self._task_backoff_cap_s = int(tconf.get(C.TASK_MAX_BACKOFF)) / 1e3
        self.speculation_enabled = bool(
            tconf.get(C.TASK_SPECULATION_ENABLED))
        self.max_worker_replacements = int(
            tconf.get(C.TASK_MAX_WORKER_REPLACEMENTS))
        self._replacements_used = 0  # reset per query (run_map_reduce)
        # deterministic jitter for the inter-wave backoff (never wall
        # clock: chaos rounds must replay identically under one seed)
        self._backoff_rng = random.Random("task-retry-backoff")
        self.speculative_tasks = 0
        self.speculation_wins = 0
        self.evicted_workers = 0
        self.abandoned_tasks = 0
        self.worker_shrinks = 0
        # accumulated shard drains, keyed (executor_id, shard pid) so a
        # replaced worker's restarted journal never aliases its
        # predecessor's span ids (drain_journals)
        self._drained: Dict[tuple, dict] = {}
        self._query_counter = 0
        # session attachment: session.progress() delegates here, and the
        # post-mortem triggers below reach the session's manager through
        # a weakref (the cluster must never keep a dead session alive)
        self._session_ref = None
        if session is not None:
            session._proc_cluster = self
            import weakref
            self._session_ref = weakref.ref(session)
        self.monitor: Optional[HeartbeatMonitor] = None
        interval_ms = int(tconf.get(C.TRACE_HEARTBEAT_INTERVAL))
        if self.trace_enabled and interval_ms > 0:
            self.monitor = HeartbeatMonitor(
                self, interval_ms / 1e3,
                int(tconf.get(C.TRACE_HUNG_TASK_TIMEOUT)) / 1e3)
            # hung-task watchdog -> post-mortem bundle: fired OFF the
            # monitor lock (see _ingest) and dumped asynchronously so a
            # multi-second rpc sweep never stalls the heartbeat loop
            self.monitor.on_hung = self._on_hung_task

    def _on_hung_task(self, info: dict) -> None:
        self._postmortem_trigger(
            "hung-task",
            error=RuntimeError(
                "hung-task watchdog: %s task %r active for %.1fs"
                % (info.get("executor"), info.get("name"),
                   info.get("elapsed_s", 0.0))),
            asynchronous=True)

    def _postmortem_trigger(self, reason: str, error=None,
                            asynchronous: bool = False) -> None:
        s = self._session_ref() if self._session_ref is not None else None
        pm = getattr(s, "_postmortem", None) if s is not None else None
        if pm is not None:
            pm.trigger(reason, error=error, asynchronous=asynchronous)

    def _publish_peers(self) -> None:
        # replace=True prunes peers that are GONE (a shrunk worker slot):
        # survivors must stop dialing the dead address on remote fetches
        peers = {w.executor_id: list(w.address) for w in self.workers}
        self._transport.set_peers(peers, replace=True)
        for w in self.workers:
            if w.client is None:
                w.client = self._transport.make_client(w.executor_id)
            try:
                w.rpc("set_peers", peers=peers, replace=True)
            except Exception as e:  # noqa: BLE001 — a peer that is ALSO
                # dead (multi-worker loss) gets replaced by its own
                # recovery iteration, which re-publishes to everyone;
                # failing the whole recovery on ITS broken socket would
                # burn the retry budget before the second replacement
                # happens.  But never SILENTLY: a survivor that missed a
                # replacement's address dials a dead port on its next
                # remote fetch, and without this log + counter that
                # failure mode is indistinguishable from a network fault.
                self._transport.count("peer_publish_failures")
                log.warning("peer-map publish to %s failed (it may still "
                            "hold stale addresses): %r", w.executor_id, e)

    def _replace_worker(self, i: int) -> "WorkerProc":
        """Executor-loss recovery (the Spark task-retry / lineage analogue:
        the logical map fragment IS the lineage, recomputed on a fresh
        worker).  Spawns a replacement under the SAME executor id, rewires
        every peer map, and returns it."""
        old = self.workers[i]
        try:
            old.stop(grace_s=1.0)
        except Exception:  # noqa: BLE001 — it is already gone
            pass  # tpulint: disable=TPU006 stopping the worker being REPLACED for unresponsiveness; its death is the point
        fresh = WorkerProc(old.executor_id, self._conf_env, self._cpu,
                           self._ready_timeout)
        self.workers[i] = fresh
        # the dead worker's client holds a broken socket; drop it and
        # re-point the peer map at the replacement BEFORE dialing
        self._transport.drop_client(old.executor_id)
        self._transport.set_peers(
            {fresh.executor_id: list(fresh.address)})
        fresh.client = self._transport.make_client(fresh.executor_id)
        self._publish_peers()
        self.task_retries += 1
        self.map_epoch += 1  # its old map outputs died with the process
        return fresh

    def _shrink_worker(self, i: int, cause: str) -> "WorkerProc":
        """Graceful degradation: remove a worker SLOT instead of failing
        the query — the replacement budget is exhausted or the spawn
        itself failed.  Task assignments re-balance onto the survivors
        (task i runs on workers[i % len(workers)]); the caller recomputes
        any map fragments the dead slot homed via on_replace.  Returns
        the adoptive survivor for the slot's tasks."""
        w = self.workers[i]
        if len(self.workers) <= 1:
            raise RuntimeError(
                f"cluster cannot shrink below one worker: last worker "
                f"{w.executor_id} lost ({cause}) and no replacement "
                f"could be spawned")
        try:
            w.stop(grace_s=0.5)
        except Exception:  # noqa: BLE001 — it is already gone
            pass  # tpulint: disable=TPU006 stopping the worker being shrunk away; its loss is already the subject
        del self.workers[i]
        self._transport.drop_client(w.executor_id)
        self._transport.count("worker_shrinks")
        self._count("worker_shrinks")
        self.map_epoch += 1  # its map outputs died with the slot
        self._publish_peers()  # prunes the dead address everywhere
        journal_event("spec", "clusterShrunk", executor=w.executor_id,
                      cause=cause, workers=len(self.workers))
        log.warning(
            "graceful degradation: worker %s shrunk away (%s); cluster "
            "re-balanced onto %d surviving worker(s)", w.executor_id,
            cause, len(self.workers))
        return self.workers[i % len(self.workers)]

    def _replace_or_shrink(self, worker: "WorkerProc",
                           cause: str) -> "WorkerProc":
        """Replace a lost/evicted worker, degrading to a cluster shrink
        when the per-query replacement budget is exhausted or the spawn
        fails.  Returns the worker now responsible for the slot (the
        replacement, or the adoptive survivor)."""
        i = next((k for k, w in enumerate(self.workers) if w is worker),
                 None)
        if i is None:
            # already replaced/shrunk (e.g. two tasks blamed one peer in
            # one wave): hand back the current holder of the executor id
            return next((w for w in self.workers
                         if w.executor_id == worker.executor_id),
                        self.workers[0])
        if self.max_worker_replacements < 0 \
                or self._replacements_used < self.max_worker_replacements:
            self._replacements_used += 1
            try:
                return self._replace_worker(i)
            except Exception as e:  # noqa: BLE001 — degrade, not fail
                log.error("replacement spawn for %s failed (%r); "
                          "degrading to a cluster shrink",
                          worker.executor_id, e)
                return self._shrink_worker(i, f"spawn_failed:{cause}")
        log.warning("worker replacement budget exhausted (%d used); "
                    "degrading to a cluster shrink",
                    self._replacements_used)
        return self._shrink_worker(i, f"budget_exhausted:{cause}")

    def new_shuffle_id(self) -> int:
        with self._lock:
            self._sid += 1
            return self._sid

    # -- task scheduling: deadlines, retry with backoff, speculation ---------

    def _count(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def _task_deadline_s(self) -> Optional[float]:
        """Per-attempt task rpc deadline: task.timeoutMs, derived as
        2 x trace.hungTaskTimeoutMs when unset (the watchdog WARNS at the
        hung bound; the scheduler ACTS at twice it, so a task flagged
        hung gets one watchdog interval of grace — and a test tuning the
        watchdog alone does not change scheduling).  None = unbounded."""
        if self._task_timeout_ms > 0:
            return self._task_timeout_ms / 1e3
        if self._hung_timeout_ms > 0:
            return 2 * self._hung_timeout_ms / 1e3
        return None

    def _task_rpc(self, worker: "WorkerProc", method: str, **kw):
        """Task rpc on a DEDICATED connection: a task that outlives its
        deadline (or a speculation loser grinding on) must never hold the
        worker's shared control client hostage — cleanup rpcs and later
        waves dial fresh."""
        from .shuffle.net import SocketClient
        client = SocketClient(self._transport, tuple(worker.address))
        try:
            return client.rpc(method, **kw)
        finally:
            client.close()

    def _probe_worker(self, worker: "WorkerProc") -> bool:
        """Health-probe a worker whose task crossed its deadline, over
        the heartbeat monitor's dedicated connection when the monitor is
        running (the probe must never queue behind the wedged task rpc),
        falling back to a FRESH dial when that fails — a stale monitor
        socket must not misclassify a live worker as dead (the hung-vs-
        dead attribution feeds numEvictedWorkers and the journal).
        True = the process answers (wedged-but-alive); False = dead."""
        try:
            if self.monitor is not None:
                client = self.monitor._client_for(worker)
                if client is not None:
                    client.rpc("heartbeat", _rpc_timeout=2.0)
                    return True
        except Exception:  # noqa: BLE001 — stale socket, not a verdict
            pass  # tpulint: disable=TPU006 a broken monitor client is inconclusive; the fresh-dial probe below delivers the verdict
        try:
            from .shuffle.net import SocketClient
            probe = SocketClient(self._transport, tuple(worker.address),
                                 inject_faults=False, connect_timeout=2.0)
            try:
                probe.rpc("ping", _rpc_timeout=2.0)
                return True
            finally:
                probe.close()
        except Exception:  # noqa: BLE001 — the probe's answer IS the info
            return False

    def _speculation_candidates_locked(self, tasks: Dict[int, dict],
                                       durations: List[float]):
        """Straggler detection over the running wave (caller holds the
        wave condition): tasks past stragglerFactor x the stage's median
        successful-attempt duration (or past the hung-task bound) with no
        copy yet.  Returns [(task, target worker, attempt id)] with the
        target chosen least-loaded among healthy workers."""
        if not self.speculation_enabled:
            return []
        med = sorted(durations)[len(durations) // 2] \
            if len(durations) >= 2 else None
        hung_s = self._hung_timeout_ms / 1e3 \
            if self._hung_timeout_ms > 0 else None
        if med is None and hung_s is None:
            return []
        now = time.monotonic()
        load: Dict[str, int] = {}
        for t in tasks.values():
            for a in t["attempts"]:
                if not a["done"]:
                    ex = a["worker"].executor_id
                    load[ex] = load.get(ex, 0) + 1
        out = []
        for i, t in sorted(tasks.items()):
            if t["resolved"] or len(t["attempts"]) != 1:
                continue  # already raced, or already settled
            a = t["attempts"][0]
            if a["done"]:
                continue
            elapsed = now - a["start"]
            # the 250ms floor keeps speculation out of millisecond-task
            # noise: a 60ms transient stall on a 20ms-median stage is not
            # a straggler worth a copy (and possibly an eviction)
            straggling = (med is not None and elapsed >= 0.25
                          and elapsed > self.straggler_factor * med)
            hung = hung_s is not None and elapsed > hung_s
            if not (straggling or hung):
                continue
            healthy = [w for w in self.workers
                       if w is not a["worker"] and w.proc.poll() is None]
            if not healthy:
                continue
            target = min(healthy,
                         key=lambda w: load.get(w.executor_id, 0))
            load[target.executor_id] = \
                load.get(target.executor_id, 0) + 1
            out.append((i, target, len(t["attempts"]) + 1))
        return out

    def _run_task_round(self, stage: str, indices, attempt, store,
                        durations: List[float], on_loser,
                        on_replace=None) -> Dict[int, tuple]:
        """One wave: launch every pending task on its assigned worker,
        speculate on stragglers, resolve first-result-wins, clean up
        losers.  Returns {task: (error, worker, all_failed_attempts)}
        for unresolved tasks."""
        cond = threading.Condition()
        tasks: Dict[int, dict] = {
            i: {"resolved": False, "stored": False, "winner": None,
                "attempts": []}
            for i in indices}

        def launch(i: int, worker: "WorkerProc", attempt_id: int) -> None:
            rec = {"id": attempt_id, "worker": worker, "done": False,
                   "ok": False, "out": None, "start": time.monotonic(),
                   "thread": None}

            def run():
                try:
                    res = attempt(i, worker=worker, attempt_id=attempt_id)
                    ok = True
                except Exception as e:  # noqa: BLE001 — classified below
                    res, ok = e, False
                dur = time.monotonic() - rec["start"]
                if not ok and isinstance(res, TimeoutError):
                    # the deadline cut this attempt off: abandoned, the
                    # wave moves on (worker health handled in recovery)
                    self._count("abandoned_tasks")
                    journal_event("spec", "taskAbandoned", stage=stage,
                                  task=i, attempt=attempt_id,
                                  executor=worker.executor_id,
                                  elapsed_s=round(dur, 3))
                first = False
                with cond:
                    rec["done"], rec["ok"], rec["out"] = True, ok, res
                    t = tasks[i]
                    if ok and not t["resolved"]:
                        t["resolved"], t["winner"] = True, rec
                        durations.append(dur)
                        first = True
                    cond.notify_all()
                if first:
                    store(i, res, worker=worker)
                    if attempt_id > 1:
                        self._count("speculation_wins")
                        journal_event("spec", "speculationWin",
                                      stage=stage, task=i,
                                      attempt=attempt_id,
                                      executor=worker.executor_id)
                    # `stored` gates the settle loop: the round must not
                    # return while the winner's result is still being
                    # written (results[i] would read None — silent row
                    # loss in the reduce concat)
                    with cond:
                        tasks[i]["stored"] = True
                        cond.notify_all()

            th = threading.Thread(target=run, daemon=True,  # tpulint: disable=TPU009 attempt threads journal spec recovery events on the DRIVING query's behalf by design (worker-side they land on the process shard; driver-side on the submitting query's journal)
                                  name=f"task-{stage}-{i}-a{attempt_id}")
            rec["thread"] = th
            with cond:
                tasks[i]["attempts"].append(rec)
            th.start()

        for i in indices:
            launch(i, self._task_worker(i), 1)

        while True:
            with cond:
                settled = all(
                    t["stored"] if t["resolved"]
                    else (t["attempts"] and all(a["done"]
                                                for a in t["attempts"]))
                    for t in tasks.values())
                to_spec = [] if settled else \
                    self._speculation_candidates_locked(tasks, durations)
                if settled:
                    break
                if not to_spec:
                    cond.wait(0.05)
            for i, target, attempt_id in to_spec:
                self._count("speculative_tasks")
                self._transport.count("task_retries_speculation")
                journal_event("spec", "speculativeLaunch", stage=stage,
                              task=i, attempt=attempt_id,
                              executor=target.executor_id)
                log.warning("%s task %d flagged as a straggler; "
                            "launching speculative copy on %s (attempt "
                            "%d)", stage, i, target.executor_id,
                            attempt_id)
                launch(i, target, attempt_id)

        # first result won; cancel/ignore the losers.  Side-effectful
        # stages (on_loser set: the map stage) must ERASE the losing
        # attempt's registrations before the reduce side can read a mix
        # of attempts — result-only stages just ignore late results.
        #
        # Cleanup is SURGICAL FIRST: the worker's per-fragment lock
        # serializes remove_map_range behind any still-running attempt
        # of that fragment, so a merely-late loser is waited out (within
        # the cleanup rpc's deadline) and cleaned without killing its
        # worker; only a cleanup that FAILS (worker wedged past the
        # bound, or dead) escalates to eviction inside on_loser
        # (process death is total cleanup).
        for i, t in sorted(tasks.items()):
            if t["winner"] is None or on_loser is None:
                continue
            for a in t["attempts"]:
                if a is t["winner"]:
                    continue
                a["thread"].join(2.0)  # grace: most losers settle fast
                w = a["worker"]
                if any(x is w for x in self.workers):
                    on_loser(i, w)

        errs: Dict[int, tuple] = {}
        for i, t in sorted(tasks.items()):
            if t["resolved"]:
                continue
            fails = [a for a in t["attempts"] if not a["ok"]]
            # prefer the error that names a blamable peer (FetchFailed);
            # EVERY failed attempt rides along so recovery can handle
            # the other attempts' workers too (a task whose original AND
            # speculative copy both wedged must evict both)
            pick = next((a for a in fails
                         if _fetch_failed_peer(a["out"]) is not None),
                        fails[0])
            errs[i] = (pick["out"], pick["worker"],
                       [(a["out"], a["worker"]) for a in fails])
        return errs

    def _task_worker(self, i: int) -> "WorkerProc":
        """Worker assigned to task i: 1:1 while the cluster is at full
        strength, re-balanced modulo the survivors after a shrink."""
        return self.workers[i % len(self.workers)]

    def _recover_task_failure(self, stage: str, i: int, err, worker,
                              handled: set, on_replace) -> str:
        """Classify one failed task and run its recovery.  Returns the
        retry CAUSE ('dead' | 'timeout' | 'fetch_failed' | 'other') for
        the per-cause transport counters."""
        def lost(w, label):
            if w.executor_id in handled:
                return
            handled.add(w.executor_id)
            if not any(x is w for x in self.workers):
                # already replaced/shrunk this wave (loser-cleanup
                # escalation, or two attempts naming one worker): its
                # fragments were recomputed then — replacing the
                # innocent fresh process again would be pure churn
                return
            new = self._replace_or_shrink(w, label)
            if on_replace is not None:
                on_replace(w.executor_id, new)

        if worker is not None and worker.proc.poll() is not None:
            lost(worker, "dead")
            return "dead"
        if isinstance(err, TimeoutError):
            # the attempt crossed its deadline: probe the worker over the
            # monitor's dedicated connection — a wedged-but-alive worker
            # is evicted exactly like a dead one (replace + lineage
            # recompute); a dead one just failed to be noticed yet
            present = worker is not None \
                and any(x is worker for x in self.workers)
            alive = present and self._probe_worker(worker)
            if alive and worker.executor_id not in handled:
                self._count("evicted_workers")
                journal_event("spec", "workerEvicted",
                              executor=worker.executor_id, stage=stage,
                              task=i, cause="hung")
                log.warning("%s task %d: worker %s wedged past the task "
                            "deadline (alive on probe); evicting it",
                            stage, i, worker.executor_id)
            if worker is not None:
                lost(worker, "hung" if alive else "dead")
            return "timeout"
        # typed FetchFailed escalation: the error names the peer whose
        # map output is lost (corrupt/gone), which may be a DIFFERENT
        # worker than the one whose task failed — and one whose process
        # is perfectly alive, just serving garbage.  Replace the blamed
        # peer and recompute ITS map fragments; the failing task re-runs
        # in the next wave.
        peer = _fetch_failed_peer(err)
        if peer is not None:
            if peer not in handled:
                self.lost_map_outputs += 1
                log.warning(
                    "%s task %d lost map output at %s; replacing it and "
                    "recomputing the fragment", stage, i, peer)
                pw = next((w for w in self.workers
                           if w.executor_id == peer), None)
                if pw is not None:
                    lost(pw, "fetch_failed")
                else:
                    # blamed peer already shrunk away: its fragments
                    # still need a new home for the retry to fetch from
                    handled.add(peer)
                    if on_replace is not None:
                        on_replace(peer, self._task_worker(i))
            return "fetch_failed"
        return "other"

    def _run_tasks_with_retry(self, stage: str, attempt, store,
                              on_replace=None, on_loser=None,
                              n_tasks: Optional[int] = None) -> None:
        """Run every task in parallel waves with per-attempt DEADLINES,
        speculative re-execution of stragglers, and bounded PER-TASK
        retry with jittered exponential backoff between waves.

        Contract with the callers (run_map_reduce builds these):
          attempt(i, worker=, attempt_id=) — run task i on `worker`;
          store(i, out, worker=)           — first (winning) result only;
          on_replace(executor_id, worker)  — map outputs homed on
              `executor_id` are gone; recompute them on `worker` (the
              logical plan is the lineage);
          on_loser(i, worker)              — a losing speculative copy of
              task i may have registered side effects on `worker`; erase
              them (attempt-id-guarded map-output registration).

        Recovery per failed task, classified and counted per cause
        (task_retries_* transport counters): a DEAD worker is replaced
        under the same executor id; an attempt past its deadline
        (task.timeoutMs, derived from trace.hungTaskTimeoutMs) is
        ABANDONED, its worker health-probed, and a wedged-but-alive
        worker EVICTED exactly like a dead one; a typed FetchFailed
        blames the peer whose map output is unservable and that peer is
        replaced even if alive.  When the per-query replacement budget
        (task.maxWorkerReplacements) is exhausted — or a spawn fails —
        the slot is SHRUNK and tasks re-balance onto the survivors
        instead of failing the query.  Failed waves back off
        (task.retryBackoffMs doubling to task.maxBackoffMs, jittered)
        instead of hammering a recovering peer."""
        n_tasks = len(self.workers) if n_tasks is None else n_tasks
        budget = {i: self.max_task_retries for i in range(n_tasks)}
        durations: List[float] = []
        pending = sorted(range(n_tasks))
        round_no = 0
        while pending:
            errs = self._run_task_round(stage, pending, attempt, store,
                                        durations, on_loser,
                                        on_replace=on_replace)
            if not errs:
                return
            round_no += 1
            for i in sorted(errs):
                if budget[i] <= 0:
                    exhausted = RuntimeError(
                        f"{stage} task {i} failed after "
                        f"{self.max_task_retries} retries")
                    exhausted.__cause__ = errs[i][0]
                    # first-failure diagnostics BEFORE the raise unwinds
                    # the wave: the dying stage's journals/rings are
                    # still warm, and the query-failure trigger upstream
                    # would only see the driver side of the story
                    self._postmortem_trigger("retry-exhausted",
                                             error=exhausted)
                    raise exhausted
                budget[i] -= 1
            handled: set = set()
            for i in sorted(errs):
                err, worker, all_fails = errs[i]
                cause = self._recover_task_failure(stage, i, err, worker,
                                                   handled, on_replace)
                self._transport.count(f"task_retries_{cause}")
                # the OTHER failed attempts' workers get the same
                # dead/wedged recovery (dedup'd through `handled`), but
                # the task's retry is counted once, under the primary
                # error's cause
                for e2, w2 in all_fails:
                    if w2 is worker:
                        continue
                    self._recover_task_failure(stage, i, e2, w2,
                                               handled, on_replace)
            if on_loser is not None:
                # side-effectful stage: erase every failed attempt's
                # possible partial registrations on SURVIVING workers
                # before the retry wave — the re-run may land on a
                # different worker (replacement, shrink re-balance), and
                # its own attempt-id guard only cleans the worker it
                # runs on.  The fragment lock serializes this behind a
                # still-writing server task; failures escalate to
                # eviction inside on_loser.
                for i in sorted(errs):
                    for _e2, w2 in errs[i][2]:
                        if any(x is w2 for x in self.workers):
                            on_loser(i, w2)
            pending = sorted(errs)
            if self._task_backoff_s > 0:
                raw = min(self._task_backoff_cap_s,
                          self._task_backoff_s * (2 ** (round_no - 1)))
                time.sleep(raw * (0.5 + self._backoff_rng.random() / 2))

    def run_map_reduce(self, map_plans: Sequence, key_names: List[str],
                       n_parts: int, reduce_plan,
                       trace_query: Optional[str] = None):
        """One full distributed stage:
          map_plans[i] — logical fragment worker i executes (its input
                         slice), hash-partitioned on key_names;
          reduce_plan  — logical fragment with a LogicalPlaceholder where
                         the fetched partition rows attach.
        Returns the concatenated arrow table of every partition's reduce
        output, plus map statuses.

        `trace_query` names the query in the distributed trace (defaults
        to a driver-unique id): every task rpc carries a {query, stage}
        trace context, so the merged timeline groups the map and reduce
        stages of ONE query across workers (metrics/timeline.py)."""
        import pyarrow as pa

        from .shuffle.catalog import MAP_ID_STRIDE
        n_tasks = len(map_plans)
        assert n_tasks == len(self.workers), \
            "one map fragment per worker"
        sid = self.new_shuffle_id()
        with self._lock:
            self._replacements_used = 0  # replacement budget is per query
        if trace_query is None:
            with self._lock:
                self._query_counter += 1
                trace_query = f"mr-{os.getpid()}-{self._query_counter}"
        map_trace = {"query": trace_query, "stage": f"s{sid}.map"}
        reduce_trace = {"query": trace_query, "stage": f"s{sid}.reduce"}
        map_stats: List[dict] = [None] * n_tasks
        # which executor each map FRAGMENT's outputs live on (a fragment
        # follows its winning attempt: speculation, shrink re-balancing
        # and lineage recomputes can all move it off its home slot)
        frag_home: Dict[int, str] = {}
        deadline_s = self._task_deadline_s()

        def _attempt_map(i: int, worker=None, attempt_id: int = 1) -> dict:
            w = worker if worker is not None else self._task_worker(i)
            return self._task_rpc(
                w, "run_map", sid=sid,
                plan_blob=pickle.dumps(map_plans[i]),
                key_names=list(key_names), n_parts=n_parts,
                trace=map_trace, map_id_base=i * MAP_ID_STRIDE,
                attempt=attempt_id, _rpc_timeout=deadline_s)

        def _store_map(i: int, out: dict, worker=None) -> None:
            map_stats[i] = out
            if worker is not None:
                frag_home[i] = worker.executor_id

        def _recompute_fragments(executor_id: str, worker) -> None:
            # map outputs homed on `executor_id` died with it (process
            # loss, eviction, or shrink): the map fragments (the logical
            # lineage) recompute on `worker` — during the map stage this
            # covers fragments a lost worker had already WON (its own
            # pending task just re-runs in the wave); during the reduce
            # stage it runs before failed reduce tasks retry their
            # fetches
            for i in sorted(frag_home):
                if frag_home[i] != executor_id:
                    continue
                map_stats[i] = _attempt_map(i, worker=worker)
                frag_home[i] = worker.executor_id

        def _cleanup_map_loser(i: int, worker) -> None:
            # a losing speculative map copy registered fragment i's
            # blocks on a worker that also (rightly) holds other state:
            # drop exactly that fragment's range.  If the surgical
            # cleanup fails the bit-for-bit invariant is at stake —
            # escalate to eviction (process death is total cleanup).
            # The wait bound is the TASK deadline (the fragment lock
            # serializes behind a still-running loser, and a loser that
            # legitimately runs long on a heavy stage must not get its
            # healthy worker killed over a hardcoded 30s).
            try:
                self._task_rpc(worker, "remove_map_range", sid=sid,
                               lo=i * MAP_ID_STRIDE,
                               hi=(i + 1) * MAP_ID_STRIDE,
                               _rpc_timeout=deadline_s or 30.0)
            except Exception as e:  # noqa: BLE001 — escalates, never silent
                log.warning("speculation-loser cleanup of task %d at %s "
                            "failed (%r); evicting the worker", i,
                            worker.executor_id, e)
                if any(x is worker for x in self.workers):
                    self._count("evicted_workers")
                    journal_event("spec", "workerEvicted",  # tpulint: disable=TPU011 reached through the on_loser callback parameter of _run_tasks_with_retry (closure indirection the call graph cannot resolve)
                                  executor=worker.executor_id,
                                  stage="map", task=i,
                                  cause="loser_cleanup_failed")
                    new = self._replace_or_shrink(worker,
                                                  "loser_cleanup_failed")
                    _recompute_fragments(worker.executor_id, new)

        self._run_tasks_with_retry("map", _attempt_map, _store_map,
                                   on_replace=_recompute_fragments,
                                   on_loser=_cleanup_map_loser,
                                   n_tasks=n_tasks)

        reduce_blob = pickle.dumps(reduce_plan)
        results: List[Optional[bytes]] = [None] * n_tasks

        def _attempt_reduce(i: int, worker=None,
                            attempt_id: int = 1) -> bytes:
            w = worker if worker is not None else self._task_worker(i)
            # partition ownership is keyed by TASK index (fixed at stage
            # entry), not worker count — a mid-stage shrink re-balances
            # workers without re-slicing the partition space
            parts = [p for p in range(n_parts) if p % n_tasks == i]
            return self._task_rpc(w, "run_reduce", sid=sid,
                                  partitions=parts, plan_blob=reduce_blob,
                                  trace=reduce_trace, attempt=attempt_id,
                                  _rpc_timeout=deadline_s)

        def _store_reduce(i: int, out, worker=None) -> None:
            results[i] = out

        self._run_tasks_with_retry(
            "reduce", _attempt_reduce, _store_reduce,
            # a replaced worker lost its map outputs with the process;
            # the map fragments (the lineage) recompute them first
            on_replace=_recompute_fragments, n_tasks=n_tasks)
        for w in self.workers:
            try:
                w.rpc("remove_shuffle", sid=sid)
            except Exception:  # noqa: BLE001 — cleanup best-effort
                pass  # tpulint: disable=TPU006 remove_shuffle on a worker that may have died; the shuffle dies with it either way

        tables = []
        for blob in results:
            if blob is None:
                continue
            with pa.ipc.open_stream(blob) as r:
                tables.append(r.read_all())
        if not tables:
            return pa.table({}), map_stats
        return pa.concat_tables(tables), map_stats

    def transport_counters(self) -> Dict[str, dict]:
        """Per-worker wire counters (bytes_sent/received, metadata round
        trips) — observability + test assertions that bytes really crossed
        process boundaries.  The extra 'driver' entry carries the
        DRIVER-side transport's counters: per-cause task retries
        (task_retries_dead/timeout/fetch_failed/speculation/other),
        worker_shrinks, peer_publish_failures."""
        out = {w.executor_id: w.rpc("transport_counters")
               for w in self.workers}
        out["driver"] = dict(self._transport.counters)
        return out

    def pool_stats(self) -> Dict[str, dict]:
        """Per-worker runtime pool/retry/spill stats over the control RPC
        (the cluster half of docs/monitoring.md's aggregation story)."""
        return {w.executor_id: w.rpc("pool_stats") for w in self.workers}

    def map_output_stats(self, sid: int, num_partitions: int):
        """Cluster-wide MapOutputStatistics for one shuffle, aggregated
        over the control RPC (rpc_map_output_stats, alongside
        rpc_pool_stats) — what adaptive re-planning reads after a
        distributed map stage."""
        from .adaptive.stats import merge_cluster_stats
        return merge_cluster_stats(
            sid, num_partitions,
            (w.rpc("map_output_stats", sid=sid) for w in self.workers))

    def observability_snapshot(self) -> Dict[str, dict]:
        """{executor_id: {"transport": ..., "pool": ...}} — one RPC sweep,
        also reachable via metrics.export.cluster_snapshot(cluster)."""
        from .metrics.export import cluster_snapshot
        return cluster_snapshot(self)

    # -- distributed tracing / live progress ---------------------------------

    def progress(self) -> dict:
        """Live, monotonically advancing progress snapshot (heartbeat
        totals + recovery counters).  The `score` field never decreases
        while work is happening — the serving tier's admission signal and
        what `session.progress()` surfaces."""
        if self.monitor is not None:
            out = self.monitor.progress()
        else:
            out = {"heartbeats": 0, "tasks_completed": 0,
                   "tasks_failed": 0, "rows_written": 0, "wire_bytes": 0,
                   "workers": len(self.workers), "active_tasks": [],
                   "heartbeat_lag_s": 0.0, "missed_heartbeats": 0,
                   "hung_tasks": 0, "score": 0,
                   "peak_memory": {"per_worker": {}, "device_peak": 0,
                                   "host_peak": 0, "disk_peak": 0}}
        out["task_retries"] = self.task_retries
        out["lost_map_outputs"] = self.lost_map_outputs
        with self._lock:
            out["speculative_tasks"] = self.speculative_tasks
            out["speculation_wins"] = self.speculation_wins
            out["evicted_workers"] = self.evicted_workers
            out["abandoned_tasks"] = self.abandoned_tasks
            out["worker_shrinks"] = self.worker_shrinks
        return out

    def recovery_metrics(self) -> dict:
        """The lint-checked metric names the task-recovery tier owns
        (docs/monitoring.md): folded into timeline_report()['metrics']
        and session_observability."""
        from .metrics import names as MN
        with self._lock:
            return {MN.NUM_SPECULATIVE_TASKS: self.speculative_tasks,
                    MN.NUM_SPECULATION_WINS: self.speculation_wins,
                    MN.NUM_EVICTED_WORKERS: self.evicted_workers,
                    MN.NUM_ABANDONED_TASKS: self.abandoned_tasks}

    def drain_journals(self) -> Dict[tuple, dict]:
        """Pull every worker's undrained trace-shard events
        (rpc_drain_journal) and fold them into the cluster-lifetime
        accumulation — repeated drains compose, a dead worker keeps its
        previously drained history.

        Accumulation is keyed per shard EPOCH (executor id + the anchor's
        pid): a replaced worker restarts its journal, so its span ids —
        and its wall-clock anchor — collide with the dead process's.
        Folding both under one label would re-pair old B records with new
        E records and mis-aim flow links; instead the replacement gets a
        suffixed timeline label (`exec-1#r2`) and its own anchor."""
        for w in self.workers:
            try:
                rec = w.rpc("drain_journal")
            except Exception as e:  # noqa: BLE001 — a dead worker keeps
                log.debug("journal drain of %s failed: %r",  # its history
                          w.executor_id, e)
                continue
            if not rec:
                continue
            ex = rec.get("executor_id", w.executor_id)
            pid = (rec.get("anchor") or {}).get("pid")
            key = (ex, pid)
            if key not in self._drained:
                n_epochs = sum(1 for (e2, _p) in self._drained
                               if e2 == ex)
                label = ex if n_epochs == 0 else f"{ex}#r{n_epochs + 1}"
                self._drained[key] = {"label": label, "anchor": None,
                                      "events": [], "dropped": 0}
            acc = self._drained[key]
            if rec.get("anchor"):
                acc["anchor"] = rec["anchor"]
            acc["events"].extend(rec.get("events") or [])
            # the shard's dropped counter is cumulative over ITS lifetime
            acc["dropped"] = int(rec.get("dropped") or 0)
        return self._drained

    def merged_timeline(self, extra_shards: Optional[List[dict]] = None):
        """Drain every worker shard and merge into ONE wall-clock-aligned
        Timeline, clock-corrected from the heartbeat monitor's probe
        samples.  `extra_shards` adds driver-side journals (e.g. the
        session's last query journal events under a 'driver' label)."""
        from .metrics.timeline import merge_shards
        self.drain_journals()
        shards = [dict(rec) for rec in self._drained.values()]
        shards.extend(extra_shards or [])
        probes = self.monitor.probes() if self.monitor is not None else None
        if probes:
            # probe samples are keyed by executor id; restarted shard
            # epochs carry suffixed labels (exec-1#r2) — hand each epoch
            # its executor's samples under its timeline label
            probes = dict(probes, **{
                rec["label"]: probes[ex]
                for (ex, _pid), rec in self._drained.items()
                if ex in probes})
        return merge_shards(shards, probes)

    def timeline_report(self) -> dict:
        """The merged timeline's analysis dict (critical path, per-task
        overlap, stragglers, flow links) at the configured straggler
        factor, plus the monitor's heartbeat metrics."""
        rep = self.merged_timeline().report(self.straggler_factor)
        if self.monitor is not None:
            rep["metrics"].update(self.monitor.metrics())
        rep["metrics"].update(self.recovery_metrics())
        return rep

    def shutdown(self) -> None:
        if getattr(self, "monitor", None) is not None:
            self.monitor.stop()
        for w in self.workers:
            w.stop()
        t = getattr(self, "_transport", None)
        if t is not None:
            t.shutdown()
