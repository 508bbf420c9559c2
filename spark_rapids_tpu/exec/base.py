"""Physical execution operators.

Reference: sql-plugin/.../rapids/GpuExec.scala — every device operator is a
`TpuExec` producing an iterator of ColumnarBatch with standard metrics
(numOutputRows/numOutputBatches/totalTime).  The CPU fallback side
(`CpuExec`) runs on pyarrow Tables, playing the role CPU Spark plays for the
reference: anything the planner can't put on the device still executes, and
the pair gives the CPU-vs-TPU comparison oracle the test suite uses.
"""
from __future__ import annotations

import functools
from typing import Iterator, List, Optional, Sequence

from jax.profiler import TraceAnnotation

from ..columnar import ColumnarBatch
from ..config import TpuConf
from ..metrics import names as MN
# Metrics moved to the observability package (level gating + batched lazy
# fold + journal integration); re-exported here because mem/runtime.py and
# half the test suite import it from exec.base
from ..metrics.registry import Metrics  # noqa: F401
from ..metrics.roofline import cost_accounting_enabled
from ..types import Schema
from ..utils.tracing import SPAN_PREFIX


def record_output_batch(metrics: Metrics, batch, runtime=None) -> None:
    """Standard per-output-batch bookkeeping for device operators.

    * always: numOutputBatches, and numOutputRows whenever the count is
      host-known (both ESSENTIAL: free host-side increments);
    * DEBUG: exact numOutputRows resolved EAGERLY (one device sync per
      batch, counted against metrics.registry.DEVICE_SYNCS) plus a
      peakDevMemory sample of the accounting pool;
    * MODERATE: data-dependent numOutputRows accumulated as a LAZY device
      scalar (one device reduction per batch, folded into a single host
      transfer when the metrics are read — never a per-batch sync);
    * ESSENTIAL: data-dependent row counting skipped entirely (the count
      of a filtered batch would cost device work)."""
    metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
    # roofline cost declaration (metrics/roofline.py): every produced
    # batch is HBM the operator wrote.  device_size_bytes is a static
    # METADATA bound (shapes x dtype widths, never a sync), and the
    # metric is MODERATE-gated inside add(), so ESSENTIAL pays nothing.
    if metrics.level >= MN.MODERATE and cost_accounting_enabled():
        metrics.add(MN.HBM_BYTES_WRITTEN, batch.device_size_bytes())
    if batch.known_rows is not None:  # host-known: free at every level
        metrics.add(MN.NUM_OUTPUT_ROWS, batch.known_rows)
        if metrics.debug_active and runtime is not None:
            metrics.set_max(MN.PEAK_DEV_MEMORY,
                            runtime.device_store.current_size)
    elif metrics.debug_active:
        metrics.add_sync(MN.NUM_OUTPUT_ROWS, batch.num_rows_host)
        if runtime is not None:
            metrics.set_max(MN.PEAK_DEV_MEMORY,
                            runtime.device_store.current_size)
    elif metrics.level >= MN.MODERATE:
        metrics.add_lazy(MN.NUM_OUTPUT_ROWS, batch.num_rows())


def record_cost(metrics: Metrics, hbm_read: int = 0, hbm_written: int = 0,
                h2d: int = 0, d2h: int = 0, wire: int = 0, ici: int = 0,
                flops: float = 0) -> None:
    """Roofline cost declaration for one dispatch (metrics/roofline.py):
    bytes the operator moved per resource (HBM, host<->device link,
    socket wire) plus an estimated op count.  All values must be host-
    known metadata (batch capacities x dtype widths, expression-tree op
    counts, wire byte totals) — never a device sync.  The ledger joins
    these against measured span durations to name each plan node's
    bottleneck resource."""
    if metrics.level < MN.MODERATE or not cost_accounting_enabled():
        return
    if hbm_read:
        metrics.add(MN.HBM_BYTES_READ, hbm_read)
    if hbm_written:
        metrics.add(MN.HBM_BYTES_WRITTEN, hbm_written)
    if h2d:
        metrics.add(MN.H2D_BYTES, h2d)
    if d2h:
        metrics.add(MN.D2H_BYTES, d2h)
    if wire:
        metrics.add(MN.WIRE_BYTES, wire)
    if ici:
        metrics.add(MN.ICI_BYTES_MOVED, ici)
    if flops:
        metrics.add(MN.EST_FLOPS, flops)


class ExecContext:
    """Per-query execution context: conf, partition id, runtime services."""

    def __init__(self, conf: Optional[TpuConf] = None, partition_id: int = 0,
                 num_partitions: int = 1, runtime=None, cluster=None,
                 journal=None, query_execution=None):
        self.conf = conf or TpuConf()
        # roofline cost-accounting latch: observability-only, so
        # cross-query interleaving is safe
        from .. import config as _C
        from ..metrics.roofline import set_cost_accounting
        set_cost_accounting(self.conf.get(_C.ROOFLINE_COST_ENABLED))
        self.partition_id = partition_id
        self.num_partitions = num_partitions
        self.runtime = runtime  # mem.runtime.TpuRuntime when active
        self.cluster = cluster  # plugin.TpuCluster in multi-executor mode
        self.journal = journal  # metrics.journal.EventJournal per query
        # metrics.query.QueryExecution of the running query: adaptive
        # re-planning registers rewritten plan nodes through it so
        # EXPLAIN METRICS shows the final stage plan
        self.query_execution = query_execution
        # task-scoped cleanup callbacks (reference: task-completion
        # listeners releasing GPU resources, GpuSemaphore.scala:27-161 /
        # RapidsBufferCatalog task cleanup).  Operators register IDEMPOTENT
        # callbacks for resources that would otherwise orphan when a query
        # dies mid-flight; the engine runs them on task end, normal or not.
        self.cleanups: list = []

    def add_cleanup(self, cb) -> None:
        self.cleanups.append(cb)

    def run_cleanups(self) -> None:
        """Run registered callbacks newest-first; a failing callback does
        not prevent the rest from running."""
        while self.cleanups:
            cb = self.cleanups.pop()
            try:
                cb()
            except Exception as e:  # noqa: BLE001 — the rest must still run
                # a dropped cleanup is a potential buffer/file-handle
                # leak; keep teardown going but leave a trace + count
                from ..metrics.registry import count_swallowed
                count_swallowed("numCleanupErrors", "spark_rapids_tpu.exec",
                                "execution cleanup callback %r failed: %r",
                                cb, e, warn=True)

    def with_partition(self, pid: int, nparts: int) -> "ExecContext":
        ctx = ExecContext(self.conf, pid, nparts, self.runtime,
                          self.cluster, self.journal,
                          self.query_execution)
        ctx.cleanups = self.cleanups  # share the task scope
        return ctx


#: the entry points an operator is pulled through; `ExecNode` wraps each
#: one a subclass defines, once, when the class is created
_PULLED = ("execute", "execute_cpu", "execute_partitions")


def _pull_spans(node, it, name: str, args: dict):
    """`it`, with every `next()` on it inside a profiler annotation `name`
    (the bare `TraceAnnotation`: it starts when it is built, so one is built
    a pull; `name` and `args` are built once by the caller).  A child is
    pulled from inside its parent's pull, so the spans nest as the plan
    does and the innermost open one is the operator whose Python is
    running.  `node._in_pull` is set for the pull, so an entry point of the
    SAME node called from inside it opens no twin.  Closing this generator
    (a LIMIT that stops pulling) closes `it`; an exception leaves through
    the annotation's `__exit__`."""
    it = iter(it)
    try:
        while True:
            with TraceAnnotation(name, **args):
                node._in_pull = True
                try:
                    item = next(it)
                except StopIteration:
                    return
                finally:
                    node._in_pull = False
            yield item
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()


def _with_pull_span(fn):
    """Wrap one of `_PULLED` so that what it returns is pulled inside
    `srt:op:<ClassName>@<node id>` (no suffix before
    `QueryExecution._assign_ids` / `adopt` has numbered the node), with
    `q=<query id>` where the context knows the query.  `@`, not `#`: the
    profiler writes arguments as `name#k=v#` and splits at the first `#`,
    so a `#` in a name that carries arguments loses both.  One span a pull
    of a NODE: an entry point reached from inside the node's own pull (a
    subclass's `super().execute(ctx)`, an `execute` that drains
    `self.execute_partitions`) is handed on as it is."""
    @functools.wraps(fn)
    def pulled(self, ctx, *args, **kwargs):
        if self._in_pull:
            return fn(self, ctx, *args, **kwargs)
        name = f"{SPAN_PREFIX}op:{type(self).__name__}"
        nid = getattr(self, "_node_id", None)
        if nid is not None:
            name = f"{name}@{nid}"
        qe = getattr(ctx, "query_execution", None)
        # a plain method that hands on `super().execute(ctx)` calls the
        # inner entry point here, before anything is pulled
        self._in_pull = True
        try:
            it = fn(self, ctx, *args, **kwargs)
        finally:
            self._in_pull = False
        return _pull_spans(self, it, name,
                           {} if qe is None else {"q": qe.query_id})
    return pulled


class ExecNode:
    """Base physical operator."""

    def __init_subclass__(cls, **kwargs):
        # THE one place an operator gets its pull span: whatever entry
        # points the class itself defines, wrapped at class creation, so a
        # query pays nothing when it begins, nodes that adaptive
        # re-planning adds later are covered, and no operator opens the
        # span by hand (`metrics/query.py _instrument`'s journal spans are
        # the other system: another clock, off by default)
        super().__init_subclass__(**kwargs)
        for entry in _PULLED:
            fn = vars(cls).get(entry)
            if callable(fn):
                setattr(cls, entry, _with_pull_span(fn))

    #: this node is inside one of its own pulls (`_pull_spans`)
    _in_pull = False

    def __init__(self, *children: "ExecNode"):
        self.children: List[ExecNode] = list(children)
        self.metrics = Metrics()

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    @property
    def name(self) -> str:
        return type(self).__name__

    # columnar device path
    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        raise NotImplementedError(f"{self.name} has no device execution")

    # host path (pyarrow Tables)
    def execute_cpu(self, ctx: ExecContext):
        raise NotImplementedError(f"{self.name} has no CPU execution")

    def tree_string(self, indent: int = 0) -> str:
        lines = [" " * indent + self.describe()]
        for c in self.children:
            lines.append(c.tree_string(indent + 2))
        return "\n".join(lines)

    def describe(self) -> str:
        return self.name


class TpuExec(ExecNode):
    """Device columnar operator (GpuExec equivalent)."""

    # hint to the transition pass (reference: CoalesceGoal lattice)
    coalesce_after: bool = False
    # None | "single" | int target bytes — requirement on children batches
    child_coalesce_goal = None

    @property
    def is_device(self) -> bool:
        return True


class CpuExec(ExecNode):
    """Host operator running on pyarrow Tables."""

    @property
    def is_device(self) -> bool:
        return False
