"""StreamingQuery: the micro-batch trigger loop.

One StreamingQuery = one append-only source + one grouped aggregation,
executed incrementally.  Each epoch:

  1. The epoch planner slices unread data into a micro-batch scan
     (source.py).
  2. A DELTA query — the same aggregation rewritten so its output IS a
     partial state (`_delta_aggregates`: Sum/Count/Min/Max unchanged,
     Average split into Sum(Cast(x, double)) + Count(x)) — runs over
     just that slice THROUGH `TpuSession.submit`.  Riding the scheduler
     buys the whole serving tier per epoch: a lifecycle token (so
     `stop()` cancels the in-flight epoch at its next checkpoint and
     `epochDeadlineMs` bounds it end to end), fair-share admission, SLO
     accounting, and the parameterized plan cache — whose fingerprint
     keys the stamped streaming scan by source identity + schema
     (serve/plan_cache.py), so every epoch after the first is a plan-
     cache hit replaying the already-compiled stages: warm epochs
     perform ZERO stage compiles (asserted in tests/test_streaming.py).
  3. The delta's output is renamed positionally onto the aggregate's
     partial-state schema and folded into the device-resident state
     with the aggregate's own merge kernel (state.py).
  4. The epoch commits atomically: source offsets + state snapshot,
     marker last (checkpoint.py).  A killed-and-restarted query resumes
     from the last committed epoch bit-for-bit.

Observability: every epoch journals `epoch` events (slice/commit, plus
recover on restart), bumps numEpochs/epochTime/streamStateBytes/
numStateRecoveries, and lands its wall time in the `epoch` SLO phase
histogram for its priority class.

What stays incremental-safe is deliberately narrow (everything else
raises StreamingUnsupported up front, not mid-stream): grouped
Sum/Count/Min/Max/Average (rollup/cube included — the grouping-id is
just another key), no distinct, no First/Last/Percentile, no compound
result projections, exactly one streaming scan under the aggregate.
docs/tuning-guide.md ("Streaming micro-batch execution") walks through
why each exclusion breaks incremental folding.
"""
from __future__ import annotations

import copy
import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..metrics import names as MN
from ..metrics.journal import EventJournal, journal_event, pop_active, \
    push_active
from ..plan import logical as L
from ..types import DoubleType
from .checkpoint import EpochCheckpoint
from .source import StreamingSource
from .state import StreamState

_SUPPORTED_AGGS = ("Sum", "Count", "Min", "Max", "Average")
_query_seq = itertools.count(1)


class StreamingUnsupported(ValueError):
    """The query shape cannot be folded incrementally."""


# ---------------------------------------------------------------------------
# plan surgery
# ---------------------------------------------------------------------------

def _find_stream_scans(node: L.LogicalPlan, identity: str, acc: list):
    if getattr(node, "source_identity", None) == identity:
        acc.append(node)
    for c in node.children:
        _find_stream_scans(c, identity, acc)


def _swap_scan(node: L.LogicalPlan, identity: str,
               new_scan: L.LogicalScan) -> L.LogicalPlan:
    """Rebuild the path to the stamped scan with the epoch's slice in
    its place (copy-on-write, like plan_cache._copy_node — DataFrames
    share logical nodes, so the original tree is never mutated)."""
    if getattr(node, "source_identity", None) == identity:
        return new_scan
    new_children = tuple(_swap_scan(c, identity, new_scan)
                         for c in node.children)
    if all(n is o for n, o in zip(new_children, node.children)):
        return node
    new = copy.copy(node)
    new.children = new_children
    new.__dict__.pop("_cached_schema", None)
    return new


def _delta_aggregates(aggregates: List[L.ColumnExpr]) -> List[L.ColumnExpr]:
    """Rewrite the aggregate list so the delta query's FINALIZED output
    is, column for column, the aggregate's partial state
    (TpuHashAggregateExec._make_state_schema / _AggState.fields):

      Sum/Count/Min/Max — already their own partial (same value, same
        dtype, same validity bit).
      Average — two columns: Sum(Cast(x, double)) + Count(x), exactly
        the (sum, count) pair the update kernel accumulates (both cast
        to f64 before the masked segment sum, both with the same
        any-valid validity), so the fold's division-free merge and the
        single finalize division see identical raw bits.

    The positional rename onto the state schema happens in fold()."""
    out: List[L.ColumnExpr] = []
    for ai, a in enumerate(aggregates):
        child = a.args[0]
        if a.op == "Average":
            cast = L.ColumnExpr("Cast", (child, DoubleType))
            out.append(L.ColumnExpr("Sum", (cast, False),
                                    alias=f"_a{ai}_sum"))
            out.append(L.ColumnExpr("Count", (child, False),
                                    alias=f"_a{ai}_count"))
        else:
            out.append(L.ColumnExpr(a.op, (child, False),
                                    alias=f"_a{ai}_{a.op.lower()}"))
    return out


def _decompose(plan: L.LogicalPlan, identity: str
               ) -> Tuple[L.LogicalAggregate,
                          Optional[List[Tuple[str, str]]]]:
    """Validate + split the built query into (the aggregate node, the
    optional pure-column result projection as (source, output) name
    pairs).  GroupedData.agg wraps rollup/compound results in a
    LogicalProject; only the pure column-select form (rollup's
    grouping-id drop) is incremental-safe — compound projections
    (sum(a)/sum(b)) would need re-finalization arithmetic the state
    store does not model."""
    proj: Optional[List[Tuple[str, str]]] = None
    node = plan
    if isinstance(node, L.LogicalProject):
        if not all(isinstance(e, L.ColumnExpr) and e.op == "col"
                   for e in node.exprs):
            raise StreamingUnsupported(
                "compound aggregate result projections (e.g. "
                "sum(a)/sum(b)) are not incremental-safe; compute them "
                "from the streaming result table instead")
        proj = [(e.args[0], e.output_name) for e in node.exprs]
        node = node.children[0]
    if not isinstance(node, L.LogicalAggregate):
        raise StreamingUnsupported(
            "a streaming query must end in a grouped aggregation "
            f"(got {type(node).__name__})")
    if not node.grouping:
        raise StreamingUnsupported(
            "global (ungrouped) streaming aggregation is not supported; "
            "group by a constant to emulate it")
    for a in node.aggregates:
        if not isinstance(a, L.ColumnExpr) or a.op not in _SUPPORTED_AGGS:
            raise StreamingUnsupported(
                f"aggregate {a!r} cannot be folded incrementally "
                f"(supported: {', '.join(_SUPPORTED_AGGS)})")
        if a.args[1]:  # distinct
            raise StreamingUnsupported(
                f"distinct aggregate {a!r} is not incremental-safe: "
                "partial distinct states are not mergeable across "
                "epochs")
    scans: list = []
    _find_stream_scans(node, identity, scans)
    if len(scans) != 1:
        raise StreamingUnsupported(
            f"expected exactly one scan of streaming source "
            f"{identity!r} under the aggregate, found {len(scans)} "
            "(joins between two streams are not supported)")
    return node, proj


# ---------------------------------------------------------------------------
# the query
# ---------------------------------------------------------------------------

class StreamingQuery:
    """Incremental micro-batch execution of one grouped aggregation over
    one append-only source.  Build with `stream_query(...)` or directly:

        src = MemoryStream(schema, name="events")
        q = StreamingQuery(session, src,
                           lambda df: df.group_by(col("k"))
                                        .agg(F.sum(col("v"))),
                           checkpoint_dir="/path/ckpt")
        src.append(batch1); q.process_available(); q.result()
    """

    def __init__(self, session, source: StreamingSource, build, *,
                 name: str = "stream", output_mode: str = "complete",
                 checkpoint_dir: Optional[str] = None, priority: int = 0,
                 epoch_deadline_ms: Optional[float] = None,
                 budget_bytes: Optional[int] = None):
        from .. import config as C
        if output_mode not in ("complete", "update"):
            raise ValueError(
                f"output_mode must be 'complete' or 'update', got "
                f"{output_mode!r}")
        self.session = session
        self.source = source
        self.name = name
        self.output_mode = output_mode
        self.priority = int(priority)
        conf = session.conf
        if epoch_deadline_ms is None:
            epoch_deadline_ms = float(conf.get(C.STREAM_EPOCH_DEADLINE_MS))
        self.epoch_deadline_ms = epoch_deadline_ms or None
        if budget_bytes is None:
            budget_bytes = int(conf.get(C.SERVE_QUERY_BUDGET))
        # the owner stamp every state buffer carries (unique per query
        # INSTANCE: release() must never free a namesake's state)
        self.owner = f"stream:{name}#{next(_query_seq)}"
        self.journal = EventJournal(label=f"stream-{name}")

        # -- analyze the built query ------------------------------------
        from ..engine import DataFrame
        df = DataFrame(session, source.placeholder_scan())
        built = build(df)
        plan = built.plan if hasattr(built, "plan") else built
        self._agg_plan, self._proj = _decompose(plan, source.identity)
        self._delta_aggs = _delta_aggregates(self._agg_plan.aggregates)
        self._agg_exec = self._find_agg_exec()
        self._state = StreamState(session, self._agg_exec, self.owner,
                                  budget_bytes=budget_bytes)

        # -- epoch bookkeeping ------------------------------------------
        self.epochs_committed = 0
        self.rows_folded = 0
        self.recovered = False
        self._offsets: Dict[str, int] = {source.identity: 0}
        self._last_output = None
        self._error: Optional[BaseException] = None
        self._stopped = False
        self._stop_event = threading.Event()
        self._inflight = None
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.RLock()
        self._ckpt = (EpochCheckpoint(checkpoint_dir,
                                      keep=int(conf.get(
                                          C.STREAM_CHECKPOINT_KEEP)))
                      if checkpoint_dir else None)
        if self._ckpt is not None:
            self._recover()

    # -- setup ---------------------------------------------------------------

    def _find_agg_exec(self):
        """Plan the BATCH-shaped aggregate (over the empty placeholder
        scan) and pull out its physical TpuHashAggregateExec: the state
        store borrows its state schema and its merge/finalize kernels —
        by the exec's exact kernel-cache key, so streaming folds and
        batch oracle runs share the same compiled programs."""
        from ..exec.aggregate import TpuHashAggregateExec
        physical = self.session.plan(self._agg_plan)
        stack = [physical]
        while stack:
            node = stack.pop()
            if isinstance(node, TpuHashAggregateExec):
                return node
            stack.extend(getattr(node, "children", ()))
        raise StreamingUnsupported(
            "the aggregation did not plan onto the device "
            "(TpuHashAggregateExec not found — check explain() for CPU "
            "fallbacks); streaming state requires the device aggregate")

    def _recover(self) -> None:
        with self._lock:
            payload = self._ckpt.load_latest()
            if payload is None:
                return
            self.epochs_committed = payload["epoch"]
            self.rows_folded = payload["rows_total"]
            self._offsets.update(payload["offsets"])
            if payload["state"] is not None:
                self._state.restore(*payload["state"])
            self.recovered = True
            self.session.runtime.metrics.add(MN.NUM_STATE_RECOVERIES, 1)
            push_active(self.journal)
            try:
                journal_event("epoch", "recover",
                              epoch=self.epochs_committed,
                              offsets=dict(self._offsets),
                              state_bytes=self._state.device_bytes())
            finally:
                pop_active(self.journal)

    # -- triggers ------------------------------------------------------------

    def trigger_once(self) -> bool:
        """Run AT MOST one epoch over currently-unread data; returns
        whether an epoch committed."""
        with self._lock:
            self._check_usable()
            push_active(self.journal)
            try:
                return self._run_epoch()
            finally:
                pop_active(self.journal)

    def process_available(self, max_epochs: Optional[int] = None) -> int:
        """Drain-available trigger: run epochs until no unread data
        remains (or `max_epochs`); returns the number committed."""
        n = 0
        while max_epochs is None or n < max_epochs:
            if self._stopped or not self.trigger_once():
                break
            n += 1
        return n

    def start(self, interval_s: float = 0.1) -> "StreamingQuery":
        """Interval trigger: a background thread drains available data
        every `interval_s` until stop()."""
        with self._lock:
            self._check_usable()
            if self._thread is not None:
                return self
            self._thread = threading.Thread(
                target=self._interval_loop, args=(float(interval_s),),
                name=f"stream-{self.name}", daemon=True)
            self._thread.start()
        return self

    def _interval_loop(self, interval_s: float) -> None:
        while not self._stop_event.is_set():
            try:
                self.process_available()
            except BaseException as e:  # noqa: BLE001 — surfaced via error
                with self._lock:
                    self._error = e
                return
            self._stop_event.wait(interval_s)

    def _check_usable(self) -> None:
        if self._stopped:
            raise RuntimeError("streaming query is stopped")
        if self._error is not None:
            raise self._error

    # -- the epoch -----------------------------------------------------------

    def _run_epoch(self) -> bool:
        # the RLock is already held by trigger_once; re-entering keeps
        # every epoch-state write statically inside the lock
        with self._lock:
            sl = self.source.plan_epoch(
                self._offsets[self.source.identity], self.session.conf)
            if sl is None:
                return False
            metrics = self.session.runtime.metrics
            t0 = time.perf_counter()
            with metrics.timer(MN.EPOCH_TIME):
                journal_event("epoch", "slice",
                              source=self.source.identity,
                              start=sl.start, end=sl.end,
                              rows=sl.rows if sl.rows is not None else -1)
                delta_plan = L.LogicalAggregate(
                    self._agg_plan.grouping, self._delta_aggs,
                    _swap_scan(self._agg_plan.children[0],
                               self.source.identity, sl.scan))
                fut = self.session.submit(
                    delta_plan, priority=self.priority,
                    deadline_ms=self.epoch_deadline_ms)
                self._inflight = fut
                try:
                    delta = fut.result()
                finally:
                    self._inflight = None
                groups = self._state.fold(delta)
                self.epochs_committed += 1
                self.rows_folded += sl.rows if sl.rows is not None else 0
                self._offsets[self.source.identity] = sl.end
                if self._ckpt is not None:
                    self._ckpt.commit(self.epochs_committed, self._offsets,
                                      self._state.snapshot(),
                                      rows_total=self.rows_folded)
                journal_event("epoch", "commit",
                              epoch=self.epochs_committed, groups=groups,
                              state_bytes=self._state.device_bytes(),
                              plan_cache=fut.plan_cache)
                metrics.add(MN.NUM_EPOCHS, 1)
                self._last_output = self._compute_output(delta)
            sched = self.session.scheduler
            if sched is not None:
                sched.slo.observe("epoch", self.priority,
                                  time.perf_counter() - t0)
            return True

    def _compute_output(self, delta_table):
        """Finalize the resident state into the epoch's result table.
        `update` mode keeps only groups touched this epoch (key match
        against the delta, host-side); the stored pure-column projection
        (rollup's grouping-id drop) applies last."""
        import pyarrow as pa
        full = self._state.finalize_table()
        if full is None:
            return None
        if self.output_mode == "update":
            nk = len(self._agg_plan.grouping)
            touched = set(zip(*(delta_table.column(i).to_pylist()
                                for i in range(nk))))
            keep = [t in touched
                    for t in zip(*(full.column(i).to_pylist()
                                   for i in range(nk)))]
            full = full.filter(pa.array(keep, type=pa.bool_()))
        if self._proj is not None:
            full = pa.Table.from_arrays(
                [full.column(src) for src, _out in self._proj],
                names=[out for _src, out in self._proj])
        return full

    # -- results + shutdown --------------------------------------------------

    def result(self):
        """The latest committed epoch's output table (complete: all
        groups; update: groups touched in that epoch).  None before the
        first data-carrying epoch."""
        self._check_usable()
        return self._last_output

    @property
    def error(self) -> Optional[BaseException]:
        return self._error

    def stop(self) -> int:
        """Stop the query: cancel the in-flight epoch at its next
        lifecycle checkpoint, join the interval thread, release every
        state buffer this query owns (all tiers).  Returns owner bytes
        freed.  Idempotent; the checkpoint (if any) survives for a
        successor query to recover from."""
        self._stopped = True  # tpulint: disable=TPU009 deliberately lock-free: stop() must interrupt an epoch that HOLDS the lock; a monotonic flag read at trigger checkpoints
        self._stop_event.set()
        fut = self._inflight
        if fut is not None:
            fut.cancel("streaming query stopped")
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=60)
        return self._state.release()


def stream_query(session, source: StreamingSource, build,
                 **kwargs) -> StreamingQuery:
    """Convenience constructor (the streaming package's entry point)."""
    return StreamingQuery(session, source, build, **kwargs)
