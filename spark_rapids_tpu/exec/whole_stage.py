"""Whole-stage fused execution.

`TpuWholeStageExec` is the fusion unit the stage-fusion pass
(plan/fusion.py) creates: a maximal chain of row-local device operators
(project/filter/expand over scan-decode output) compiled as ONE XLA
program per batch shape and executed with STAGE-granularity OOM handling.
Reference analogue: Spark's WholeStageCodegenExec (`*(N)` operators in
EXPLAIN); the TPU twist is that "codegen" is jax tracing + XLA
compilation, so fusing a chain also collapses the number of distinct
compiled programs a query pays warmup for.

Execution contract per input batch:

  * the fused chain runs inside `with_retry` with the STAGE's input batch
    as the spillable checkpoint — one retry block for the whole chain
    instead of none at all (bare RowLocalExec has no retry);
  * `RetryOOM` escalation splits the input by row range and re-invokes
    the SAME compiled stage on each half; split pieces land in
    power-of-two capacity buckets (mem/retry.split_batch_rows ->
    columnar.bucket_rows), so recompiles stay bounded;
  * `RetryExhausted` falls back to executing the constituent operators
    ONE AT A TIME (each in its own retry block), and an operator that
    exhausts ITS retries falls back to its CPU twin for that batch —
    preserving the PR-1 ladder (spill-retry -> split -> CPU) at finer
    granularity;
  * exactly one ColumnarBatch materializes at the stage's fusion
    boundary (exchange, join build, sort, full aggregation).

Programs are AOT-compiled through `kernel_cache.stage_executable`, which
makes compile count and the trace-vs-compile time split observable
(numStageCompiles / stageCompileTime / journal kind `compile`).

Stages that thread per-batch state (monotonically_increasing_id row
offsets) or bake per-file constants (input_file_name) take the inherited
RowLocalExec path instead: still one fused program per batch, without the
stage-retry upgrades (the offset/file key cannot be re-threaded through
an arbitrary split).
"""
from __future__ import annotations

from typing import Iterator, List

from ..columnar import ColumnarBatch
from ..metrics import names as MN
from ..metrics.journal import journal_event
from ..utils.tracing import named_range
from .base import ExecContext, ExecNode, record_output_batch
from .basic import FusedPipelineExec, RowLocalExec, TpuExpandExec


class TpuWholeStageExec(FusedPipelineExec):
    """A fused stage of row-local operators with stage-level retry.

    Subclasses FusedPipelineExec so every consumer that fuses with a
    row-local child (the aggregate's whole-stage absorption, the
    exchange's bucketing fusion, the streaming-agg pre-kernel) composes
    with a whole stage exactly as it does with a legacy fused chain:
    `batch_fn()` is the composed chain, `children[0]` is the source.
    """

    def __init__(self, stages: List[RowLocalExec], child: ExecNode):
        super().__init__(stages, child)
        self.stage_id = 0  # assigned by plan/fusion.number_stages
        # set by plan/fusion's last-consumer analysis: True when this
        # stage may donate its input batches' buffers to the compiled
        # program (source yields fresh single-consumer device arrays)
        self.donate_inputs = False
        self._folded_batches = 0
        self._folded_rows = 0.0

    def describe(self):
        inner = " -> ".join(s.name for s in self.stages)
        return f"*({self.stage_id}) TpuWholeStageExec[{inner}]"

    def tree_string(self, indent: int = 0) -> str:
        lines = [" " * indent + self.describe()]
        for desc, _m in self.op_rows():
            lines.append(" " * (indent + 2) + desc)
        lines.append(self.children[0].tree_string(indent + 2))
        return "\n".join(lines)

    # ---- per-operator attribution (lazy) -----------------------------------

    def op_rows(self):
        """[(describe, metrics)] for the constituent operators, outermost
        first, with stage-level counts folded into each operator's own
        metrics LAZILY (at render time, never per batch) — the
        EXPLAIN-with-metrics surface for operators that no longer
        dispatch individually."""
        self._fold_op_attribution()
        return [(f"*({self.stage_id}) {s.describe()}", s.metrics)
                for s in reversed(self.stages)]

    def _fold_op_attribution(self) -> None:
        vals = self.metrics.snapshot()
        batches = vals.get(MN.NUM_OUTPUT_BATCHES, 0)
        d_batches = batches - self._folded_batches
        if d_batches > 0:
            self._folded_batches = batches
            for s in self.stages:
                s.metrics.add(MN.NUM_OUTPUT_BATCHES, d_batches)
        rows = vals.get(MN.NUM_OUTPUT_ROWS, 0.0)
        d_rows = rows - self._folded_rows
        if d_rows > 0 and self.stages:
            # only the stage BOUNDARY row count is known (intermediate
            # batches never materialize): attribute it to the last op
            self._folded_rows = rows
            self.stages[-1].metrics.add(MN.NUM_OUTPUT_ROWS, d_rows)

    # ---- execution ---------------------------------------------------------

    def _can_split(self) -> bool:
        """Row-range splitting re-runs the chain per piece and
        concatenates outputs in order; an Expand's projection fan-out
        interleaves rows differently when split, so stages containing one
        stay retry-only (exhaustion -> operator-at-a-time)."""
        return not any(isinstance(s, TpuExpandExec) for s in self.stages)

    def _reserve_estimate(self, batch: ColumnarBatch) -> int:
        nbytes = batch.device_size_bytes()
        out = nbytes
        for s in self.stages:
            if isinstance(s, TpuExpandExec):
                out *= max(1, len(s.projections))
        return max(nbytes, out)

    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        if self._needs_row_offset() or self._needs_input_file():
            yield from RowLocalExec.execute(self, ctx)
            return
        from ..utils.kernel_cache import (param_free_keys, record_dispatch,
                                          stage_executable)
        from .retryable import run_retryable
        from ..mem.retry import RetryExhausted, split_batch_rows
        from ..ops import expressions as E
        from .basic import bound_param_builder
        params = self.stage_params()
        if params:
            # plan-cache parameters: value-free stage key + the bound
            # values as a traced argument, so a literal-variant
            # re-submission reuses this stage's compiled executable
            with param_free_keys():
                key = self.kernel_key() + ("whole_stage_exec",)
            key += ("params", E.parameter_signature(params))
            slots = [p.slot for p in params]
            pvals = E.parameter_values(params)
            chain = bound_param_builder(self.batch_fn, slots)
        else:
            key = self.kernel_key() + ("whole_stage_exec",)
            pvals = None
            chain = self.batch_fn

        def builder():
            # written here, so kernel_cache names the program after this
            # module's layer (`stage.wholeStage-<id>`), whatever row-local
            # chain of exec/basic.py it fuses
            return chain()
        split = split_batch_rows if self._can_split() else None
        self.metrics.add(MN.NUM_FUSED_STAGES, 1)
        n_batches = 0
        from .. import config as C
        from ..mem import donation
        donate_ok = bool(ctx.conf.get(C.DONATION_ENABLED)) \
            and self.donate_inputs

        def attempt(b):
            if ctx.runtime is not None:
                ctx.runtime.reserve(self._reserve_estimate(b),
                                    site="wholeStage")
            args = (b,) if pvals is None else (b, pvals)
            # donation: decided per batch — a retry checkpoint or scan-
            # cache registration pins the batch, flipping later attempts
            # (and later batches) back to the copying executable
            don = donate_ok and donation.donatable(b)
            fn = stage_executable(key, builder, args,
                                  metrics=self.metrics,
                                  name=f"wholeStage-{self.stage_id}",
                                  donate_argnums=(0,) if don else ())
            record_dispatch()
            if don:
                donation.record_donated_dispatch(b, self.metrics)
            return fn(*args)

        from ..serve.lifecycle import ctx_checkpoint
        for batch in self.children[0].execute(ctx):
            n_batches += 1
            # stage-boundary lifecycle checkpoint (serve/lifecycle.py):
            # between batch dispatches nothing is mid-reservation, so a
            # cancel/deadline raises here and a preemption request may
            # SUSPEND here (spill own buffers, release the semaphore,
            # block for a FIFO-within-priority resume)
            ctx_checkpoint(ctx, allow_suspend=True)
            # captured BEFORE the dispatch: a donating executable
            # consumes the batch, so no metadata read may follow it
            self.count_input(batch.capacity)
            with named_range(f"whole_stage_{self.stage_id}", self.metrics,
                             MN.TOTAL_TIME):
                try:
                    outs = run_retryable(ctx, self.metrics, "wholeStage",
                                         attempt, [batch], split=split)
                except RetryExhausted:
                    if donation.consumed(batch):
                        # a failed dispatch already donated the input's
                        # buffers: de-fusing would re-read freed device
                        # memory (TPU008) — the exhaustion is terminal
                        raise
                    self.metrics.add(MN.NUM_FUSION_FALLBACKS, 1)
                    journal_event("fallback", self.name,
                                  reason="stage_retry_exhausted",
                                  stage=self.stage_id)
                    outs = self._run_ops_one_at_a_time(ctx, batch)
            for out in outs:
                record_output_batch(self.metrics, out, ctx.runtime)
                yield out
        journal_event("stage", f"wholeStage-{self.stage_id}",
                      ops=[s.name for s in self.stages],
                      batches=n_batches)

    # ---- fallback ladder ---------------------------------------------------

    def _run_ops_one_at_a_time(self, ctx: ExecContext,
                               batch: ColumnarBatch) -> List[ColumnarBatch]:
        """De-fused execution of ONE input batch: each constituent
        operator's kernel in its own retry block; an operator that
        exhausts its retries runs on its CPU twin for that batch (gated
        by the PR-1 cpuFallbackOnOom conf).  Split pieces flow through
        the remaining operators independently."""
        from .. import config as C
        from ..mem import donation
        from ..utils.kernel_cache import record_dispatch
        from .retryable import run_retryable
        from ..mem.retry import RetryExhausted, split_batch_rows
        cpu_ok = bool(ctx.conf.get(C.OOM_CPU_FALLBACK))
        donate_conf = bool(ctx.conf.get(C.DONATION_ENABLED))
        batches = [batch]
        for op_ix, op in enumerate(self.stages):
            # same kernel construction as RowLocalExec.execute's plain
            # path (parameter-threaded when the plan cache lifted
            # literals into this op), so a de-fuse under memory pressure
            # reuses any already-compiled per-op kernel
            fn = op.parameterized_kernel()
            # the first op consumes the STAGE's input (donatable only
            # when the fusion pass proved the source single-consumer);
            # later ops consume the previous op's fresh output
            op_donate = donate_conf and (op_ix > 0 or self.donate_inputs)
            fn_don = (op.parameterized_kernel(donate=True) if op_donate
                      else None)
            pre = op.metrics.snapshot()
            op_split = (split_batch_rows
                        if not isinstance(op, TpuExpandExec) else None)

            def attempt(b, _fn=fn, _fnd=fn_don):
                if ctx.runtime is not None:
                    ctx.runtime.reserve(b.device_size_bytes(),
                                        site="wholeStage.op")
                record_dispatch()
                if _fnd is not None and donation.donatable(b):
                    donation.record_donated_dispatch(b, self.metrics)
                    return _fnd(b)
                return _fn(b)

            outs: List[ColumnarBatch] = []
            for b in batches:
                try:
                    outs.extend(run_retryable(ctx, op.metrics,
                                              "wholeStageOp", attempt,
                                              [b], split=op_split))
                except RetryExhausted:
                    if not cpu_ok or donation.consumed(b):
                        # consumed: a failed donating dispatch already
                        # ate this batch's buffers — the CPU twin would
                        # D2H freed memory (TPU008); propagate instead
                        raise
                    # on the op (EXPLAIN's per-op rows) AND the stage node
                    # (the tree-walk aggregation only sees plan nodes)
                    op.metrics.add(MN.NUM_CPU_FALLBACKS, 1)
                    self.metrics.add(MN.NUM_CPU_FALLBACKS, 1)
                    journal_event("fallback", op.name,
                                  reason="stage_op_retry_exhausted",
                                  stage=self.stage_id)
                    outs.append(_cpu_apply(op, b, ctx))
            # mirror the op-level retry/split counts onto the STAGE node
            # (like numCpuFallbacks above): ops are not plan nodes, so
            # counts recorded only on op.metrics would never reach
            # QueryExecution.aggregate()/prometheus
            post = op.metrics.snapshot()
            for mk in ("wholeStageOpRetries", "wholeStageOpSplits"):
                d = post.get(mk, 0) - pre.get(mk, 0)
                if d > 0:
                    self.metrics.add(mk, d)
            batches = outs
        return batches


def _cpu_apply(op: RowLocalExec, batch: ColumnarBatch,
               ctx: ExecContext) -> ColumnarBatch:
    """Run one row-local operator on the CPU for one batch: D2H, the
    operator's CPU twin over a one-table source, H2D."""
    import pyarrow as pa
    from .basic import CpuScanMemoryExec
    table = batch.to_arrow()
    twin = op.cpu_twin(CpuScanMemoryExec(table, batch.schema))
    tables = list(twin.execute_cpu(ctx))
    out = tables[0] if len(tables) == 1 else pa.concat_tables(tables)
    return ColumnarBatch.from_arrow(out)
