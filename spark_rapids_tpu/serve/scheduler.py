"""Concurrent query scheduler + admission control over one TpuRuntime.

The serving half of ROADMAP item 2.  One `QueryScheduler` per TpuSession
multiplexes submitted queries over the session's single runtime:

  * **Priority queue** — `submit(df, priority=N)` enqueues; higher
    priority dispatches first, FIFO within a priority (Presto-style
    queue discipline).
  * **Admission control** — every query declares (or gets an estimated)
    memory need; the scheduler keeps the sum of in-flight needs under
    `admission.memoryFraction x` the accounted HBM pool, so a burst of
    heavy queries queues instead of shredding the spill tier.  A full
    queue rejects (`AdmissionRejected`, counted in
    numAdmissionRejections) — backpressure, not unbounded buffering.
    The device itself stays guarded one level down by the existing
    `TpuSemaphore` (spark.rapids.sql.concurrentTpuTasks): admission
    bounds MEMORY commitment, the semaphore bounds simultaneous device
    occupancy.
  * **Per-query budgets** — `serve.queryBudgetBytes` installs a
    `MemoryLedger` query scope around each execution; `reserve()`
    enforces the budget by spilling the query's OWN buffers first and
    raising RetryOOM into the query's own retry ladder, so one hog
    spills itself, not its neighbors (mem/runtime.py).
  * **Plan cache** — submissions run through `PlanCache.lookup`, so a
    literal variant of a seen query replays cached compiled stages
    (plan_cache.py) and the persistent XLA compile cache
    (utils/compile_cache.py) covers process restarts.

Metrics (lint-checked catalog): queueTime, numAdmitted,
numQueuedQueries, numAdmissionRejections, planCacheHits/Misses,
numBudgetOoms — all on the runtime Metrics, so pool_stats()/prometheus
and session_observability pick them up.  Each query's journal carries a
kind-`sched` "admitted" instant (queue time, priority, need, cache
state) under its own trace context.
"""
from __future__ import annotations

import heapq
import threading
import time
from typing import List, Optional

from .. import config as C
from ..metrics import names as MN
from ..utils.tracing import named_range
from .lifecycle import (QueryCancelled, QueryDeadlineExceeded,
                        QueryLifecycle, QueryTimeout)
from .plan_cache import PlanCache


class AdmissionRejected(RuntimeError):
    """The scheduler's queue is full; resubmit later (HTTP-429 moral)."""


class QueryFuture:
    """Handle for one submitted query (concurrent.futures shape, plus
    scheduling observability: queue/plan timings, plan-cache state)."""

    def __init__(self, priority: int, need_bytes: int):
        self.priority = priority
        self.need_bytes = need_bytes
        self.submitted_ns = time.monotonic_ns()
        self.admitted_ns: Optional[int] = None
        self.finished_ns: Optional[int] = None
        self.queue_seconds: Optional[float] = None
        self.plan_seconds: Optional[float] = None
        # per-phase breakdown of the execution (engine._collect_physical
        # fills these; the scheduler feeds them into the SLO histograms):
        # whole-stage trace+compile, synchronous-spill cascades, and the
        # physical execution wall clock
        self.compile_seconds: Optional[float] = None
        self.spill_seconds: Optional[float] = None
        self.exec_seconds: Optional[float] = None
        self.plan_cache: Optional[str] = None  # "hit" | "miss" | "off"
        self.n_params = 0
        self.query_id: Optional[int] = None
        self._event = threading.Event()
        self._table = None
        self._error: Optional[BaseException] = None
        self.cancelled = False
        # serve.lifecycle.QueryLifecycle token (None with the
        # serve.lifecycle.enabled kill switch off): cancel()/deadline/
        # preemption all route through it
        self.lifecycle: Optional[QueryLifecycle] = None
        self.deadline_ms: Optional[float] = None
        self._scheduler = None  # owning QueryScheduler (cancel routing)

    # -- completion (scheduler side) ----------------------------------------

    def _set_result(self, table) -> None:
        if self._event.is_set():
            return  # first resolution wins (cancel/complete races)
        self._table = table
        self.finished_ns = time.monotonic_ns()
        self._event.set()

    def _set_error(self, error: BaseException) -> None:
        if self._event.is_set():
            return  # first resolution wins (cancel/complete races)
        self._error = error
        self.finished_ns = time.monotonic_ns()
        self._event.set()

    # -- consumer side -------------------------------------------------------

    def done(self) -> bool:
        return self._event.is_set()

    def cancel(self, reason: str = "cancelled by caller") -> bool:
        """Request cooperative cancellation.  A still-QUEUED query is
        dequeued and resolved immediately (it never cost a worker); a
        RUNNING one stops at its next lifecycle checkpoint (reserve/
        retry/stage/exchange boundary) with QueryCancelled as its own
        error, followed by owner-confined cleanup of its buffers and
        shuffle outputs.  Returns True when the cancel was requested;
        False when the query already finished or the
        serve.lifecycle.enabled kill switch is off.  Cooperative: a
        query that completes before observing the request still delivers
        its result."""
        if self._event.is_set():
            return False
        tok = self.lifecycle
        sched = self._scheduler
        if tok is None or sched is None:
            return False
        return sched._cancel(self, reason)

    def result(self, timeout: Optional[float] = None):
        """The query's pyarrow Table (raises the query's error).  A
        timed-out WAIT raises QueryTimeout (a TimeoutError subclass) —
        the query itself keeps running; use cancel() to stop it."""
        if not self._event.wait(timeout):
            raise QueryTimeout(
                f"query still running after {timeout}s wait; the query "
                "was not stopped — cancel() it or wait again")
        if self._error is not None:
            raise self._error
        return self._table

    def collect(self, timeout: Optional[float] = None) -> list:
        """Row-tuple view of result(), like DataFrame.collect()."""
        table = self.result(timeout)
        return [tuple(r.values()) for r in table.to_pylist()]

    def exception(self, timeout: Optional[float] = None
                  ) -> Optional[BaseException]:
        """The query's error, or None on success.  Like result(), a
        timed-out wait raises QueryTimeout — timing out is a property of
        the WAIT, not a resolution of the query."""
        if not self._event.wait(timeout):
            raise QueryTimeout(
                f"query still running after {timeout}s wait; the query "
                "was not stopped — cancel() it or wait again")
        return self._error

    @property
    def latency_seconds(self) -> Optional[float]:
        if self.finished_ns is None:
            return None
        return (self.finished_ns - self.submitted_ns) / 1e9


class _Item:
    __slots__ = ("logical", "priority", "need", "future", "skips", "seq",
                 "need_released")

    def __init__(self, logical, priority: int, need: int,
                 future: QueryFuture, seq: int = 0):
        self.logical = logical
        self.priority = priority
        self.need = need
        self.future = future
        self.skips = 0  # admission bypass count (starvation bound)
        self.seq = seq  # submission order (FIFO-within-priority resume)
        # True while this item holds NO admission share: before
        # admission, after completion, and while preemption-suspended.
        # The worker's finally and the suspend path both settle the
        # in-flight need through this flag so it can never double-count.
        self.need_released = True


# a queued query smaller items have leapfrogged this many times becomes a
# BARRIER: nothing behind it is admitted until it fits.  Bounds starvation
# of big-memory-need queries under a sustained stream of small ones.
_MAX_ADMISSION_SKIPS = 64


class QueryScheduler:
    """Session-multiplexing scheduler (one per TpuSession; built lazily
    by TpuSession.submit)."""

    def __init__(self, session):
        self.session = session
        conf = session.conf
        # resolve the lazy singletons BEFORE worker threads exist: their
        # double-checked inits are not guarded against concurrent first
        # touch from N query threads
        self.runtime = session.runtime
        session.cluster
        self.max_concurrent = max(1, int(conf.get(C.SERVE_MAX_CONCURRENT)))
        self.queue_capacity = max(1, int(conf.get(C.SERVE_QUEUE_CAPACITY)))
        self.default_need = int(conf.get(C.SERVE_DEFAULT_NEED))
        self.query_budget = int(conf.get(C.SERVE_QUERY_BUDGET))
        from ..mem.runtime import configured_pool_bytes
        frac = float(conf.get(C.SERVE_ADMISSION_FRACTION))
        self.admission_budget = max(1, int(configured_pool_bytes(conf)
                                           * frac))
        self.plan_cache: Optional[PlanCache] = None
        if bool(conf.get(C.SERVE_PLAN_CACHE_ENABLED)):
            self.plan_cache = PlanCache(
                int(conf.get(C.SERVE_PLAN_CACHE_SIZE)))
        # serving path owns the persistent XLA compile-cache wiring: a
        # restarted server replays kernels from disk (platform-gated
        # helper; active_cache_dir() reports what actually took effect)
        from ..utils.compile_cache import (active_cache_dir,
                                           enable_compilation_cache)
        enable_compilation_cache(str(conf.get(C.COMPILATION_CACHE_DIR)))
        self.compile_cache_dir = active_cache_dir()
        self._metrics = self.runtime.metrics
        # query lifecycle layer (serve/lifecycle.py): the kill switch
        # gates token creation itself — off means no token anywhere, so
        # every checkpoint is a no-op byte-identical to pre-lifecycle
        self.lifecycle_enabled = bool(conf.get(C.SERVE_LIFECYCLE_ENABLED))
        self.preemption_enabled = self.lifecycle_enabled and \
            bool(conf.get(C.SERVE_PREEMPTION_ENABLED))
        self.resume_timeout = float(
            conf.get(C.SERVE_PREEMPTION_RESUME_TIMEOUT))
        self.shed_factor = float(conf.get(C.SERVE_DEADLINE_SHED_FACTOR))
        self._lock = threading.Condition()
        self._queue: List[tuple] = []  # heap of (-priority, seq, _Item)
        self._seq = 0
        self._inflight_need = 0
        self._running = 0
        self._shutdown = False
        self.admitted = 0
        self.rejected = 0
        self.completed = 0
        self.failed = 0
        self.cancelled_queries = 0
        self.deadline_sheds = 0
        self.deadline_exceeded = 0
        self.preemptions = 0
        self.preemption_resumes = 0
        # preemption-suspended victims: heap of (-priority, seq, _Item),
        # resumed FIFO-within-priority by _grant_resumes_locked; _active
        # maps seq -> _Item for every query currently inside _run_one
        # (suspended or not) — the victim pool preemption picks from
        self._suspended: List[tuple] = []
        self._active: dict = {}
        # EWMA of observed plan+compile seconds — the admission-time
        # shedding estimate (a query whose remaining deadline can't
        # cover it is rejected instead of admitted doomed)
        self._plan_compile_ewma = 0.0
        # fair-share observability (guarded by self._lock): per-priority
        # admission/rejection counters behind cluster_snapshot /
        # prometheus_serve_dump — the PR-10 fairness behavior, observable
        self.admitted_by_priority: dict = {}
        self.rejected_by_priority: dict = {}
        # per-(phase, priority) latency histograms (metrics/slo.py):
        # queue/plan/compile/execute/spill/total, p50/p95/p99 each
        from ..metrics.slo import SloTracker
        self.slo = SloTracker()
        # planning mutates no shared state by design, but logical nodes
        # are shared between submissions of one DataFrame — serialize the
        # (cheap, host-side) planning step rather than audit every pass
        self._plan_lock = threading.Lock()
        self._workers = [
            threading.Thread(target=self._worker_loop, daemon=True,
                             name=f"tpu-serve-{i}")
            for i in range(self.max_concurrent)]
        for w in self._workers:
            w.start()

    # -- submission ----------------------------------------------------------

    def _estimate_need(self, logical) -> int:
        try:
            from ..plan.physical import _estimate_plan_bytes
            est = _estimate_plan_bytes(logical, self.session.conf)
        except Exception:  # noqa: BLE001 — estimation is best-effort
            est = None
        if est is None or est <= 0:
            return self.default_need
        return int(est)

    def submit(self, logical, priority: int = 0,
               memory_need: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> QueryFuture:
        """Enqueue a logical plan (or DataFrame via TpuSession.submit).
        Raises AdmissionRejected when the queue is at capacity.  With
        `deadline_ms` set the query carries a wall-clock budget from
        SUBMISSION: it is shed at admission when the remaining budget
        cannot cover the estimated plan+compile cost, and stopped at its
        next lifecycle checkpoint once the budget expires — either way
        QueryDeadlineExceeded lands in this query's own failure path."""
        if hasattr(logical, "plan") and hasattr(logical, "session"):
            logical = logical.plan  # a DataFrame
        need = int(memory_need) if memory_need else \
            self._estimate_need(logical)
        fut = QueryFuture(priority, need)
        with self._lock:
            if self._shutdown:
                raise RuntimeError("scheduler is shut down")
            if len(self._queue) >= self.queue_capacity:
                self.rejected += 1
                self.rejected_by_priority[int(priority)] = \
                    self.rejected_by_priority.get(int(priority), 0) + 1
                self._metrics.add(MN.NUM_ADMISSION_REJECTIONS, 1)
                raise AdmissionRejected(
                    f"queue full ({self.queue_capacity} queries pending); "
                    "resubmit later or raise "
                    f"{C.SERVE_QUEUE_CAPACITY.key}")
            self._seq += 1
            item = _Item(logical, int(priority), need, fut, seq=self._seq)
            if self.lifecycle_enabled:
                tok = QueryLifecycle(label=f"p{int(priority)}s{self._seq}",
                                     priority=int(priority),
                                     deadline_ms=deadline_ms)
                tok.metrics = self._metrics
                tok.resume_timeout_s = self.resume_timeout
                tok._sched = self
                tok._item = item
                fut.lifecycle = tok
                fut.deadline_ms = deadline_ms
                fut._scheduler = self
            heapq.heappush(self._queue, (-int(priority), self._seq, item))
            self._metrics.set_max(MN.NUM_QUEUED_QUERIES, len(self._queue))
            if self.preemption_enabled:
                # a higher-priority arrival may preempt a running
                # lower-priority victim at its next stage boundary
                self._maybe_preempt_locked(int(priority))
            self._lock.notify()
        return fut

    def _cancel(self, fut: QueryFuture, reason: str) -> bool:
        """QueryFuture.cancel() back end.  Marks the token, then — when
        the query is still QUEUED — dequeues and resolves it right here
        (it never cost a worker, so cancellation is free); a RUNNING
        query observes the token at its next checkpoint instead."""
        tok = fut.lifecycle
        tok.cancel(reason)
        removed = False
        with self._lock:
            for i, ent in enumerate(self._queue):
                if ent[2].future is fut:
                    del self._queue[i]
                    heapq.heapify(self._queue)
                    removed = True
                    break
            self._lock.notify_all()
        if removed:
            self._metrics.add(MN.NUM_CANCELLED_QUERIES, 1)
            with self._lock:
                self.cancelled_queries += 1
            fut.cancelled = True
            fut._set_error(QueryCancelled(
                f"query cancelled while queued: {reason}"))
        return True

    # -- dispatch ------------------------------------------------------------

    def _pop_admissible_locked(self) -> Optional[_Item]:
        """Highest-priority queued query whose declared need fits the
        admission budget given in-flight commitments.  With nothing in
        flight the head is admitted regardless (a query bigger than the
        budget must still make progress — the budget shapes concurrency,
        it is not a hard per-query cap; that is queryBudgetBytes).  An
        over-budget query smaller items have leapfrogged
        _MAX_ADMISSION_SKIPS times becomes a barrier: nothing behind it
        admits until in-flight work drains enough for it to fit, so a
        sustained stream of small queries cannot starve a big one."""
        if not self._queue:
            return None
        skipped = []
        picked = None
        # "nothing in flight" must look through preemption-suspended
        # victims: their worker threads still count in _running but they
        # hold no admission share, and an over-budget head must not
        # deadlock against a parked victim waiting for it to finish
        idle = self._running - len(self._suspended) <= 0
        while self._queue:
            ent = heapq.heappop(self._queue)
            item = ent[2]
            if idle or \
                    self._inflight_need + item.need <= self.admission_budget:
                picked = item
                break
            skipped.append(ent)
            if item.skips >= _MAX_ADMISSION_SKIPS:
                break  # barrier: admit nothing behind this query
            item.skips += 1
        for ent in skipped:
            heapq.heappush(self._queue, ent)
        return picked

    def _worker_loop(self) -> None:
        while True:
            with self._lock:
                item = None
                while not self._shutdown:
                    item = self._pop_admissible_locked()
                    if item is not None:
                        break
                    if self.preemption_enabled and self._queue:
                        # the head cannot be admitted: a waiting
                        # higher-priority query may still preempt a
                        # running lower-priority one to make room
                        self._maybe_preempt_locked()
                    self._lock.wait()
                if item is None:
                    return  # shutdown
                self._inflight_need += item.need
                item.need_released = False
                self._running += 1
                self._active[item.seq] = item
                if self.preemption_enabled:
                    self._maybe_preempt_locked(item.priority)
            try:
                self._run_one(item)
            finally:
                with self._lock:
                    self._active.pop(item.seq, None)
                    if not item.need_released:
                        self._inflight_need -= item.need
                        item.need_released = True
                    self._running -= 1
                    # a finished query frees admission budget: re-check
                    # suspended victims first, then every queued waiter
                    self._grant_resumes_locked()
                    self._lock.notify_all()

    def _run_one(self, item: _Item) -> None:
        fut = item.future
        tok = fut.lifecycle
        if tok is not None:
            # race backstop: a cancel that arrived between the queue
            # scan in _cancel and this worker's pop resolves here,
            # before the query costs any planning or device work
            if tok.cancel_requested:
                self._metrics.add(MN.NUM_CANCELLED_QUERIES, 1)
                with self._lock:
                    self.cancelled_queries += 1
                fut.cancelled = True
                fut._set_error(QueryCancelled(
                    f"query cancelled while queued: {tok._cancel_reason}"))
                return
            # deadline shedding: when the remaining budget cannot even
            # cover the estimated plan+compile cost, fail fast instead
            # of admitting a query that is already doomed — overload
            # sheds at the queue edge, not halfway through a compile
            rem = tok.remaining_s()
            if rem is not None:
                est = self._plan_compile_ewma * self.shed_factor \
                    if self.shed_factor > 0 else 0.0
                if rem <= 0 or rem < est:
                    self._metrics.add(MN.NUM_DEADLINE_SHEDS, 1)
                    with self._lock:
                        self.deadline_sheds += 1
                    if tok.journal is not None:
                        tok.journal.instant(
                            "lifecycle", "shed", q=tok.label,
                            remaining_s=round(max(rem, 0.0), 6),
                            estimate_s=round(est, 6))
                    fut._set_error(QueryDeadlineExceeded(
                        "shed at admission: remaining deadline "
                        f"{max(rem, 0.0):.3f}s cannot cover estimated "
                        f"plan+compile {est:.3f}s"))
                    return
        fut.admitted_ns = time.monotonic_ns()
        queue_s = (fut.admitted_ns - fut.submitted_ns) / 1e9
        fut.queue_seconds = queue_s
        self._metrics.add(MN.QUEUE_TIME, queue_s)
        self._metrics.add(MN.NUM_ADMITTED, 1)
        with self._lock:
            self.admitted += 1
            self.admitted_by_priority[item.priority] = \
                self.admitted_by_priority.get(item.priority, 0) + 1
        session = self.session
        try:
            logical = item.logical
            cache_state = "off"
            t0 = time.perf_counter()
            # normalization + fingerprinting + planning all under the
            # plan lock: logical nodes are SHARED between submissions of
            # one DataFrame, and planning lazily writes into their
            # __dict__ (plan_schema's _cached_schema) — fingerprinting
            # vars() concurrently would race that first-touch insert
            with self._plan_lock, named_range("plan"):
                if self.plan_cache is not None:
                    normalized, values, hit = self.plan_cache.lookup(
                        logical, session.conf)
                    self._metrics.add(
                        MN.PLAN_CACHE_HITS if hit else
                        MN.PLAN_CACHE_MISSES, 1)
                    logical = normalized
                    fut.n_params = len(values)
                    cache_state = "hit" if hit else "miss"
                fut.plan_cache = cache_state
                from ..plan.overrides import plan_schema
                out_schema = plan_schema(logical, session.conf)
                physical = session.plan(logical)
            fut.plan_seconds = time.perf_counter() - t0
            sched_attrs = {
                "queue_s": round(queue_s, 6),
                "plan_s": round(fut.plan_seconds, 6),
                "priority": item.priority,
                "need_bytes": item.need,
                "plan_cache": cache_state,
                "params": fut.n_params,
            }
            table = session._collect_physical(
                physical, out_schema, budget_bytes=self.query_budget,
                sched_attrs=sched_attrs, future=fut)
            fut._set_result(table)
            with self._lock:
                self.completed += 1
                # feed the deadline-shedding estimator: EWMA of observed
                # plan+compile seconds over successful queries
                dt = (fut.plan_seconds or 0.0) + (fut.compile_seconds
                                                  or 0.0)
                self._plan_compile_ewma = dt \
                    if self._plan_compile_ewma == 0.0 \
                    else 0.7 * self._plan_compile_ewma + 0.3 * dt
        except QueryCancelled as e:
            self._metrics.add(MN.NUM_CANCELLED_QUERIES, 1)
            fut.cancelled = True
            fut._set_error(e)
            with self._lock:
                self.cancelled_queries += 1
                self.failed += 1
        except QueryDeadlineExceeded as e:
            self._metrics.add(MN.NUM_DEADLINE_EXCEEDED, 1)
            fut._set_error(e)
            with self._lock:
                self.deadline_exceeded += 1
                self.failed += 1
        except BaseException as e:  # noqa: BLE001 — future carries it
            fut._set_error(e)
            with self._lock:
                self.failed += 1
        finally:
            # SLO histograms (metrics/slo.py): per-phase observations
            # for this query's priority class — success or failure, so
            # timeouts/errors still move the queue/total percentiles
            self.slo.observe_phases(
                item.priority,
                queue=queue_s,
                plan=fut.plan_seconds,
                compile=fut.compile_seconds,
                execute=fut.exec_seconds,
                spill=fut.spill_seconds,
                total=fut.latency_seconds)

    # -- preemption (serve/lifecycle.py drives the suspend side) -------------

    def _maybe_preempt_locked(self,
                              incoming_priority: Optional[int] = None
                              ) -> None:
        """Pick at most one running victim to suspend.  The bar is the
        highest priority that wants resources right now (the incoming
        submission and/or the queue head); the victim is the LOWEST-
        priority most-recently-admitted active query strictly below that
        bar.  The victim suspends cooperatively at its next stage
        boundary (exec/whole_stage.py, exec/exchange.py), releasing its
        semaphore depth and admission share until _grant_resumes_locked
        lets it back in."""
        if not self.preemption_enabled:
            return
        top = incoming_priority
        if self._queue:
            head_pri = -self._queue[0][0]
            top = head_pri if top is None else max(top, head_pri)
        if top is None:
            return
        victim = None
        victim_key = None
        for it in self._active.values():
            tok = it.future.lifecycle
            if tok is None or it.need_released or it.priority >= top:
                continue
            if tok.suspended or tok._preempt_req.is_set():
                continue
            key = (it.priority, -it.seq)
            if victim_key is None or key < victim_key:
                victim, victim_key = it, key
        if victim is not None:
            victim.future.lifecycle.request_preempt()

    def _on_suspend(self, item: _Item) -> None:
        """Called from the victim's own thread (lifecycle._suspend)
        AFTER it parked its buffers and semaphore depth: release its
        admission share and enqueue it for a FIFO-within-priority
        resume."""
        with self._lock:
            if not item.need_released:
                self._inflight_need -= item.need
                item.need_released = True
            heapq.heappush(self._suspended,
                           (-item.priority, item.seq, item))
            self.preemptions += 1
            self._metrics.add(MN.NUM_PREEMPTIONS, 1)
            # grant immediately when nothing actually outranks the
            # victim (the contender may have finished between the
            # preempt request and this suspend — without this, an
            # uncontested victim would park until the force-resume
            # timeout); then wake waiters: the freed share may admit
            # the query that triggered the preemption
            self._grant_resumes_locked()
            self._lock.notify_all()

    def _grant_resumes_locked(self) -> None:
        """Resume suspended victims — highest priority first, FIFO
        within a priority — whenever no strictly-higher-priority query
        is queued or active and the admission budget fits the victim
        again.  Caller holds self._lock."""
        while self._suspended:
            neg_pri, seq, item = self._suspended[0]
            # a queued query that outranks the victim gets the resources
            # first ((-priority, seq) ordering on both heaps) — but only
            # while a FREE worker exists to pop it: suspended victims
            # still occupy their worker threads, so when every worker is
            # parked the queued query cannot start no matter what, and
            # holding the victims for it would deadlock until the
            # force-resume timeout
            free_workers = self.max_concurrent - self._running
            if free_workers > 0 and self._queue \
                    and self._queue[0][:2] < (neg_pri, seq):
                return
            # an ACTIVE higher-priority query still runs: hold the
            # victim parked until it finishes
            if any(not it.need_released and it.priority > item.priority
                   for it in self._active.values()):
                return
            others = any(not it.need_released
                         for it in self._active.values())
            if others and self._inflight_need + item.need > \
                    self.admission_budget:
                return
            heapq.heappop(self._suspended)
            self._inflight_need += item.need
            item.need_released = False
            self.preemption_resumes += 1
            self._metrics.add(MN.NUM_PREEMPTION_RESUMES, 1)
            item.future.lifecycle._resume_evt.set()

    def _abort_suspended(self, item: _Item) -> None:
        """A suspended victim was cancelled / hit its deadline while
        parked: drop it from the resume queue (its need is already
        released; the worker finally settles the rest)."""
        with self._lock:
            self._suspended = [ent for ent in self._suspended
                               if ent[2] is not item]
            heapq.heapify(self._suspended)
            self._lock.notify_all()

    def _force_resume(self, item: _Item) -> None:
        """resumeTimeoutSeconds fired: resume the victim regardless of
        budget so a pathological priority stream cannot park a query
        forever (liveness beats fairness at this horizon)."""
        with self._lock:
            self._suspended = [ent for ent in self._suspended
                               if ent[2] is not item]
            heapq.heapify(self._suspended)
            if item.need_released:
                self._inflight_need += item.need
                item.need_released = False
            self.preemption_resumes += 1
            self._metrics.add(MN.NUM_PREEMPTION_RESUMES, 1)
            item.future.lifecycle._resume_evt.set()

    def _on_resumed(self, item: _Item, seconds: float) -> None:
        """Victim-side resume accounting: the suspend->resume latency is
        the cost half of the preemption SLO story."""
        self.slo.observe("preempt", item.priority, seconds)

    # -- lifecycle / observability -------------------------------------------

    def shutdown(self, wait: bool = True, timeout: float = 30.0) -> None:
        """Stop the workers.  Queued-but-never-admitted queries resolve
        with an error (a consumer blocked in result() must not hang
        forever on a future no worker will ever run); in-flight queries
        are cancel-signalled through their lifecycle tokens so they stop
        at the next checkpoint (reserve/retry/stage/exchange boundary)
        instead of running to completion — including victims parked in a
        preemption suspend, whose wait loop observes the token.  With
        the lifecycle kill switch off there are no tokens and in-flight
        queries finish normally, the pre-lifecycle behavior."""
        with self._lock:
            self._shutdown = True
            abandoned = [ent[2].future for ent in self._queue]
            self._queue.clear()
            running_toks = [it.future.lifecycle
                            for it in self._active.values()
                            if it.future.lifecycle is not None]
            self._lock.notify_all()
        for fut in abandoned:
            fut.cancelled = True
            fut._set_error(RuntimeError(
                "scheduler shut down before this query was admitted"))
        for tok in running_toks:
            tok.cancel("scheduler shutdown")
        if wait:
            deadline = time.monotonic() + timeout
            for w in self._workers:
                w.join(max(0.0, deadline - time.monotonic()))

    def fairness_snapshot(self) -> dict:
        """Per-priority-class fair-share observability: live queue depth
        plus cumulative admitted/rejected counters — the block
        cluster_snapshot/prometheus_serve_dump expose so the PR-10
        fair-share behavior is observable, not just implemented."""
        with self._lock:
            depth: dict = {}
            for ent in self._queue:
                p = ent[2].priority
                depth[p] = depth.get(p, 0) + 1
            return {
                "queue_depth_by_priority": dict(sorted(depth.items())),
                "admitted_by_priority":
                    dict(sorted(self.admitted_by_priority.items())),
                "rejected_by_priority":
                    dict(sorted(self.rejected_by_priority.items())),
                "running": self._running,
                "queued": sum(depth.values()),
            }

    def telemetry_gauges(self) -> dict:
        """The live gauge-sampler series this scheduler owns (the
        driver source metrics/ring.GaugeSampler snapshots; names from
        names.TELEMETRY_GAUGES): queries executing now and queries
        waiting in the priority queue."""
        fair = self.fairness_snapshot()
        return {"in_flight_tasks": float(fair["running"]),
                "queued_queries": float(fair["queued"])}

    def prometheus(self) -> str:
        """Serving-tier Prometheus exposition: fairness gauges + the
        per-phase SLO histograms (export.prometheus_serve_dump)."""
        from ..metrics.export import prometheus_serve_dump
        return prometheus_serve_dump(self)

    def stats(self) -> dict:
        with self._lock:
            out = {
                "max_concurrent": self.max_concurrent,
                "queued": len(self._queue),
                "running": self._running,
                "inflight_need_bytes": self._inflight_need,
                "admission_budget_bytes": self.admission_budget,
                "admitted": self.admitted,
                "rejected": self.rejected,
                "completed": self.completed,
                "failed": self.failed,
                "query_budget_bytes": self.query_budget,
                "compile_cache_dir": self.compile_cache_dir,
                "lifecycle": {
                    "enabled": self.lifecycle_enabled,
                    "preemption_enabled": self.preemption_enabled,
                    "cancelled": self.cancelled_queries,
                    "deadline_sheds": self.deadline_sheds,
                    "deadline_exceeded": self.deadline_exceeded,
                    "preemptions": self.preemptions,
                    "preemption_resumes": self.preemption_resumes,
                    "suspended": len(self._suspended),
                },
            }
        if self.plan_cache is not None:
            out["plan_cache"] = self.plan_cache.stats()
        out["fairness"] = self.fairness_snapshot()
        out["slo"] = self.slo.report()
        return out
