"""Basic physical operators: scan (memory), project, filter, coalesce,
limit, union, expand, and the host<->device transitions.

Reference: rapids/basicPhysicalOperators.scala (project/filter/union),
GpuCoalesceBatches.scala, limit.scala, GpuExpandExec.scala,
GpuRowToColumnarExec/GpuColumnarToRowExec (transitions).

TPU-first difference from the reference: project/filter don't move data at
all — filter ANDs into the batch's selection mask and the transition pass
fuses maximal chains of row-local operators into ONE jitted per-batch
function (FusedPipelineExec), so XLA emits a single fused program where cuDF
would launch one kernel per operator.
"""
from __future__ import annotations

import functools
from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar import ColumnarBatch, Column, bucket_rows, concat_batches
from ..config import MAX_READER_BATCH_SIZE_ROWS
from ..ops import expressions as E
from ..metrics import names as MN
from ..ops.cpu_eval import (cpu_cols_to_table, cpu_eval, table_to_cpu_cols)
from ..types import BooleanType, Schema, StructField
from ..utils.tracing import named_range
from .base import (CpuExec, ExecContext, ExecNode, TpuExec,
                   record_cost, record_output_batch)


def _pred_keep(col: Column):
    """null predicate result filters the row out (SQL WHERE semantics)."""
    return jnp.logical_and(col.valid, col.data)


def bound_param_builder(builder, slots):
    """Wrap a batch_fn builder so the traced function takes the plan-cache
    parameter values as ONE extra runtime argument (a tuple of device
    scalars) and installs them as the active binding while the chain
    traces — Parameter.eval then broadcasts tracers instead of baking
    constants, so one compiled program serves every literal variant
    (serve/plan_cache.py)."""
    def build():
        inner = builder()

        def fn(batch, pvals):
            with E.bound_params(dict(zip(slots, pvals))):
                return inner(batch)
        return fn
    return build


class TpuScanMemoryExec(TpuExec):
    """In-memory arrow table scan -> device batches (the H2D edge)."""

    def __init__(self, table, schema: Schema, conf=None):
        super().__init__()
        # cache identity must be the ORIGINAL table: select() creates a new
        # pyarrow object every planning pass, so keying on it would miss
        # (and leak an entry) on every column-pruned query
        self._cache_table = table
        if list(table.column_names) != schema.names:
            table = table.select(schema.names)  # pushdown pruned the scan
        self.table = table
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from ..config import MEMORY_SCAN_CACHE_ENABLED
        from ..utils.scan_cache import MEMORY_SCAN_CACHE, resident_bound
        E.clear_input_file()  # in-memory rows have no file provenance
        rows = self.table.num_rows
        limit = min(ctx.conf.get(MAX_READER_BATCH_SIZE_ROWS), 1 << 20)
        use_cache = ctx.conf.get(MEMORY_SCAN_CACHE_ENABLED)
        names = tuple(self._schema.names)
        if use_cache:
            cached = MEMORY_SCAN_CACHE.get(self._cache_table, names, limit)
            if cached is not None:
                served = 0
                try:
                    for batch, nrows in cached:
                        self.metrics.add(MN.NUM_OUTPUT_ROWS, nrows)
                        self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
                        served += 1
                        yield batch
                finally:
                    self.metrics.add(MN.SCAN_CACHE_HIT_BATCHES, served)
                return
            max_cache = resident_bound(ctx.conf)
        # present and 0 where the table was uploaded
        self.metrics.add(MN.SCAN_CACHE_HIT_BATCHES, 0)
        produced = []
        produced_bytes = 0
        off = 0
        while off < rows or (rows == 0 and off == 0):
            chunk = self.table.slice(off, limit)
            with self.metrics.timer(MN.SCAN_TIME):
                batch = ColumnarBatch.from_arrow(chunk)
            self.metrics.add(MN.NUM_OUTPUT_ROWS, chunk.num_rows)
            self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
            # cost declaration: the H2D edge — the adopted batch crossed
            # the host->device link and landed in HBM
            record_cost(self.metrics, h2d=batch.device_size_bytes(),
                        hbm_written=batch.device_size_bytes())
            if use_cache:
                # pinned BEFORE the first consumer sees it: a cached
                # batch is re-served to later queries, so a downstream
                # whole-stage program must never donate its buffers
                from ..mem.donation import pin
                pin(batch)
                produced.append((batch, chunk.num_rows))
                produced_bytes += batch.device_size_bytes()
                if produced_bytes > max_cache:
                    # table can never fit: stop pinning batches so the scan
                    # streams with bounded live memory again
                    use_cache = False
                    produced = []
            yield batch
            off += limit
            if rows == 0:
                break
        if use_cache:
            MEMORY_SCAN_CACHE.put(self._cache_table, names, limit, produced,
                                  max_cache, produced_bytes)

    def describe(self):
        return f"TpuScanMemoryExec[rows={self.table.num_rows}]"


class RowLocalExec(TpuExec):
    """A device op whose per-batch work is a pure batch->batch function —
    the fusion unit for FusedPipelineExec."""

    # per-row op-count estimate of expressions(), cached lazily (roofline
    # cost declaration; None until the first batch)
    _flops_per_row = None

    def batch_fn(self):
        raise NotImplementedError

    def _record_batch_cost(self, batch: ColumnarBatch) -> None:
        """Roofline cost declaration for one dispatched input batch:
        the kernel reads the whole input footprint from HBM and runs
        ~flops-per-row x rows ops (metrics/roofline.py; the output
        write side is record_output_batch's)."""
        from ..metrics.roofline import cost_accounting_enabled
        if self.metrics.level < MN.MODERATE \
                or not cost_accounting_enabled():
            return
        if self._flops_per_row is None:
            from ..metrics.roofline import estimate_expr_flops
            self._flops_per_row = max(1, estimate_expr_flops(
                self.expressions()))
        rows = batch.known_rows if batch.known_rows is not None \
            else batch.capacity
        record_cost(self.metrics, hbm_read=batch.device_size_bytes(),
                    flops=self._flops_per_row * rows)

    def expressions(self) -> List[E.Expression]:
        return []

    def count_input(self, capacity: int, metrics=None) -> int:
        """Host-side counters for ONE input batch of `capacity` rows that
        went through `batch_fn`'s program, added to `metrics` (the plan
        node's whose counters reach the query's totals; this operator's
        own by default), and the capacity that came out.  Called by
        whoever launched the program: this operator, the stage it is
        fused in, or an aggregate that absorbed it.  Shapes only: never a
        device read."""
        return capacity

    def kernel_key(self) -> tuple:
        """Structural cache key; must fully determine batch_fn's closure."""
        from ..utils.kernel_cache import expr_key
        return (type(self).__name__,
                tuple(expr_key(e) for e in self.expressions()))

    def _needs_row_offset(self) -> bool:
        return any(E.tree_needs_row_offset(e) for e in self.expressions())

    def _needs_input_file(self) -> bool:
        return any(E.tree_needs_input_file(e) for e in self.expressions())

    def stage_params(self) -> list:
        """Plan-cache Parameters in this operator's expressions, slot
        order (serve/plan_cache.py lifts literals into these)."""
        return E.collect_parameters(self.expressions())

    def parameterized_kernel(self, extra_key: tuple = (),
                             donate: bool = False):
        """The cached jitted per-batch kernel as a batch->batch callable,
        with plan-cache parameters threaded as runtime arguments when
        present.  With parameters the cache key is VALUE-FREE (slot +
        dtype) and the current bound values ride into every dispatch, so
        a literal-variant re-submission reuses the compiled program; with
        no parameters this is exactly `cached_kernel(kernel_key(),
        batch_fn)`.

        `donate=True` builds the variant that donates the input batch's
        buffers to XLA (deleted after the call!) — callers must hold the
        last-consumer proof (mem/donation.py) per dispatch and fall back
        to the non-donated kernel otherwise; cached_kernel keys the two
        variants apart."""
        from ..utils.kernel_cache import cached_kernel, param_free_keys
        jit_kw = {"donate_argnums": (0,)} if donate else {}
        params = self.stage_params()
        if not params:
            return cached_kernel(self.kernel_key() + tuple(extra_key),
                                 self.batch_fn, **jit_kw)
        with param_free_keys():
            key = self.kernel_key()
        key += tuple(extra_key) + (
            "params", E.parameter_signature(params))
        slots = [p.slot for p in params]
        pvals = E.parameter_values(params)
        inner = cached_kernel(key, bound_param_builder(self.batch_fn,
                                                       slots), **jit_kw)

        def call(batch, _inner=inner, _pvals=pvals):
            return _inner(batch, _pvals)
        return call

    def cpu_twin(self, child: ExecNode) -> ExecNode:
        """CPU twin of THIS operator over `child` — the per-operator
        fallback unit the whole-stage retry ladder degrades to
        (exec/whole_stage.py)."""
        raise NotImplementedError(self.name)

    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from ..utils.kernel_cache import cached_kernel, record_dispatch
        key = self.kernel_key()
        needs_file = self._needs_input_file()
        if self._needs_row_offset():
            # stateful exprs (mono id / rand): thread the partition row
            # offset through as a traced argument; costs one host sync per
            # batch, paid only when such an expression is present.
            # input_file_name() may appear in the SAME projection — the
            # per-batch file key composes with the offset threading.
            offset = 0
            for batch in self.children[0].execute(ctx):
                fkey = key + ("row_offset",)
                if needs_file:
                    fkey += (E.current_input_file(),)
                fn = cached_kernel(
                    fkey,
                    lambda: functools.partial(E.eval_with_row_offset,
                                              self.batch_fn()))
                self._record_batch_cost(batch)
                self.count_input(batch.capacity)
                with named_range(self.name, self.metrics, MN.TOTAL_TIME):
                    record_dispatch()
                    out = fn(batch, jnp.int64(offset))
                offset += batch.num_rows_host()
                record_output_batch(self.metrics, out, ctx.runtime)
                yield out
            return
        if needs_file:
            # input_file_name()/block exprs bake the scan's current file
            # into the program as a constant; key the cache on it so each
            # file gets its own compiled constant (files are few, so the
            # recompile count is bounded — reference GpuInputFileBlock
            # reads the holder per task the same way)
            for batch in self.children[0].execute(ctx):
                fn = cached_kernel(key + (E.current_input_file(),),
                                   self.batch_fn)
                self._record_batch_cost(batch)
                self.count_input(batch.capacity)
                with named_range(self.name, self.metrics, MN.TOTAL_TIME):
                    record_dispatch()
                    out = fn(batch)
                record_output_batch(self.metrics, out, ctx.runtime)
                yield out
            return
        # plain path: parameter-threaded when the plan cache lifted
        # literals here (the row_offset / input_file paths above keep
        # value-inclusive keys — their per-batch key composition already
        # recompiles per constant, so baked Parameter values stay correct)
        fn = self.parameterized_kernel()
        for batch in self.children[0].execute(ctx):
            self._record_batch_cost(batch)
            self.count_input(batch.capacity)
            with named_range(self.name, self.metrics, MN.TOTAL_TIME):
                record_dispatch()
                out = fn(batch)
            record_output_batch(self.metrics, out, ctx.runtime)
            yield out


class TpuProjectExec(RowLocalExec):
    def __init__(self, exprs: Sequence[E.Expression], names: Sequence[str],
                 child: ExecNode):
        super().__init__(child)
        self.exprs = list(exprs)
        self._schema = Schema([StructField(n, e.dtype)
                               for n, e in zip(names, exprs)])

    @property
    def schema(self):
        return self._schema

    def batch_fn(self):
        exprs, schema = self.exprs, self._schema

        def fn(batch: ColumnarBatch) -> ColumnarBatch:
            cols = [e.eval(batch) for e in exprs]
            return ColumnarBatch(cols, batch.sel, schema)
        return fn

    def expressions(self):
        return list(self.exprs)

    def kernel_key(self):
        from ..utils.kernel_cache import schema_key
        return super().kernel_key() + (schema_key(self._schema),)

    def cpu_twin(self, child):
        return CpuProjectExec(self.exprs, self._schema.names, child)

    def describe(self):
        return f"TpuProjectExec[{', '.join(map(repr, self.exprs))}]"


class TpuFilterExec(RowLocalExec):
    def __init__(self, condition: E.Expression, child: ExecNode):
        super().__init__(child)
        self.condition = condition

    @property
    def schema(self):
        return self.children[0].schema

    def batch_fn(self):
        cond = self.condition

        def fn(batch: ColumnarBatch) -> ColumnarBatch:
            keep = _pred_keep(cond.eval(batch))
            return batch.filter(keep)
        return fn

    def expressions(self):
        return [self.condition]

    def cpu_twin(self, child):
        return CpuFilterExec(self.condition, child)

    def describe(self):
        return f"TpuFilterExec[{self.condition!r}]"


class FusedPipelineExec(RowLocalExec):
    """Maximal chain of row-local ops compiled as ONE jitted function.
    Created by the transition pass; this is where XLA fusion pays."""

    def __init__(self, stages: List[RowLocalExec], child: ExecNode):
        super().__init__(child)
        self.stages = stages

    @property
    def schema(self):
        return self.stages[-1].schema

    def batch_fn(self):
        fns = [s.batch_fn() for s in self.stages]

        def fn(batch):
            for f in fns:
                batch = f(batch)
            return batch
        return fn

    def expressions(self):
        out = []
        for s in self.stages:
            out.extend(s.expressions())
        return out

    def count_input(self, capacity, metrics=None):
        # the fused operators are not plan nodes: their counts go on this
        # node, whose metrics the query's totals are folded from
        for s in self.stages:
            capacity = s.count_input(capacity, metrics or self.metrics)
        return capacity

    def kernel_key(self):
        return ("FusedPipelineExec",
                tuple(s.kernel_key() for s in self.stages))

    def cpu_twin(self, child):
        for s in self.stages:
            child = s.cpu_twin(child)
        return child

    def describe(self):
        inner = " -> ".join(s.name for s in self.stages)
        return f"FusedPipelineExec[{inner}]"


class TpuCoalesceBatchesExec(TpuExec):
    """Concatenate small batches up to a goal (reference:
    GpuCoalesceBatches.scala; goals RequireSingleBatch / TargetSize)."""

    def __init__(self, child: ExecNode, goal="target", target_bytes=None):
        super().__init__(child)
        self.goal = goal
        self.target_bytes = target_bytes

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        target = self.target_bytes or ctx.conf.batch_size_bytes
        pending: List[ColumnarBatch] = []
        pending_bytes = 0
        for batch in self.children[0].execute(ctx):
            sz = batch.device_size_bytes()
            if self.goal != "single" and pending \
                    and pending_bytes + sz > target:
                yield self._flush(pending)
                pending, pending_bytes = [], 0
            pending.append(batch)
            pending_bytes += sz
        if pending:
            yield self._flush(pending)

    def _flush(self, pending):
        # cost declaration: a concat/compact reads every pending batch
        # out of HBM (the write side is record_output_batch's)
        record_cost(self.metrics,
                    hbm_read=sum(b.device_size_bytes() for b in pending))
        with self.metrics.timer(MN.CONCAT_TIME):
            if len(pending) == 1:
                out = pending[0].compact()
            else:
                out = concat_batches(pending)
        record_output_batch(self.metrics, out)
        return out

    def describe(self):
        return f"TpuCoalesceBatchesExec[{self.goal}]"


class TpuUnionExec(TpuExec):
    def __init__(self, children: Sequence[ExecNode]):
        super().__init__(*children)

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        for child in self.children:
            yield from child.execute(ctx)


class TpuLocalLimitExec(TpuExec):
    """Slice batches to the first n live rows (per partition)."""

    def __init__(self, n: int, child: ExecNode):
        super().__init__(child)
        self.n = n

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        remaining = self.n
        for batch in self.children[0].execute(ctx):
            if remaining <= 0:
                return
            batch = batch.compact()
            count = batch.num_rows_host()
            if count > remaining:
                sel = jnp.arange(batch.capacity, dtype=jnp.int32) < remaining
                batch = batch.with_sel(sel)
                count = remaining
            remaining -= count
            self.metrics.add(MN.NUM_OUTPUT_ROWS, count)  # host-known: free
            self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
            yield batch

    def describe(self):
        return f"TpuLocalLimitExec[{self.n}]"


class TpuGlobalLimitExec(TpuLocalLimitExec):
    """Same slice on the single merged stream (single partition upstream)."""

    def describe(self):
        return f"TpuGlobalLimitExec[{self.n}]"


class TpuExpandExec(RowLocalExec):
    """Projection-list fan-out (ROLLUP/CUBE).  Reference: GpuExpandExec.

    TPU shape discipline: output capacity = capacity * n_projections
    (static), built by interleaved concat, no scatter."""

    def __init__(self, projections: List[List[E.Expression]],
                 names: Sequence[str], child: ExecNode):
        super().__init__(child)
        self.projections = projections
        self._schema = Schema([StructField(n, e.dtype)
                               for n, e in zip(names, projections[0])])

    @property
    def schema(self):
        return self._schema

    def batch_fn(self):
        projections, schema = self.projections, self._schema

        def fn(batch: ColumnarBatch) -> ColumnarBatch:
            parts = []
            for proj in projections:
                cols = [e.eval(batch) for e in proj]
                parts.append(ColumnarBatch(cols, batch.sel, schema))
            ncols = []
            for ci in range(len(schema)):
                f = schema[ci]
                cs = [p.columns[ci] for p in parts]
                if f.dtype.is_string:
                    ml = max(c.max_len for c in cs)
                    cs = [c.pad_strings_to(ml) for c in cs]
                    ncols.append(Column(
                        jnp.concatenate([c.data for c in cs], axis=0),
                        jnp.concatenate([c.valid for c in cs]),
                        f.dtype,
                        jnp.concatenate([c.lengths for c in cs])))
                else:
                    ncols.append(Column(
                        jnp.concatenate([c.data for c in cs]),
                        jnp.concatenate([c.valid for c in cs]), f.dtype))
            sel = jnp.concatenate([batch.sel] * len(projections))
            return ColumnarBatch(ncols, sel, schema)
        return fn

    def expressions(self):
        return [e for proj in self.projections for e in proj]

    def count_input(self, capacity, metrics=None):
        metrics = metrics or self.metrics
        out = capacity * len(self.projections)
        metrics.add(MN.EXPAND_OUTPUT_ROWS, out)
        metrics.add(MN.EXPAND_BATCHES, 1)
        return out

    def kernel_key(self):
        from ..utils.kernel_cache import schema_key
        return super().kernel_key() + (
            tuple(len(p) for p in self.projections),
            schema_key(self._schema))

    def cpu_twin(self, child):
        return CpuExpandExec(self.projections, self._schema.names, child)

    def describe(self):
        return f"TpuExpandExec[{len(self.projections)} projections]"


# --------------------------------------------------------------------------
# transitions (reference: GpuRowToColumnarExec / GpuColumnarToRowExec /
# HostColumnarToGpu — ours are arrow<->device batch edges)
# --------------------------------------------------------------------------

class HostToDeviceExec(TpuExec):
    """Adopt host arrow tables from a CPU subtree into device batches."""

    def __init__(self, child: ExecNode):
        super().__init__(child)

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx):
        for table in self.children[0].execute_cpu(ctx):
            with self.metrics.timer(MN.H2D_TIME):
                batch = ColumnarBatch.from_arrow(table)
            self.metrics.add(MN.NUM_OUTPUT_ROWS, table.num_rows)
            self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
            record_cost(self.metrics, h2d=batch.device_size_bytes(),
                        hbm_written=batch.device_size_bytes())
            yield batch


class DeviceToHostExec(CpuExec):
    """Materialize device batches to host arrow tables."""

    def __init__(self, child: ExecNode):
        super().__init__(child)

    @property
    def schema(self):
        return self.children[0].schema

    def execute_cpu(self, ctx):
        for batch in self.children[0].execute(ctx):
            # cost declaration: the D2H edge reads the batch out of HBM
            # and moves it over the link to the host (shape metadata,
            # never a read)
            nbytes = batch.device_size_bytes()
            record_cost(self.metrics, d2h=nbytes, hbm_read=nbytes)
            # the wait for the device and the copy to the host
            with named_range("d2h", self.metrics, MN.D2H_TIME,
                             bytes=nbytes):
                table = batch.to_arrow()
            self.metrics.add(MN.NUM_OUTPUT_ROWS, table.num_rows)
            self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
            yield table


# --------------------------------------------------------------------------
# CPU fallback operators (the "CPU Spark" side of the oracle)
# --------------------------------------------------------------------------

class CpuScanMemoryExec(CpuExec):
    def __init__(self, table, schema: Schema):
        super().__init__()
        if list(table.column_names) != schema.names:
            table = table.select(schema.names)  # pushdown pruned the scan
        self.table = table
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def execute_cpu(self, ctx):
        yield self.table


class CpuProjectExec(CpuExec):
    def __init__(self, exprs, names, child):
        super().__init__(child)
        self.exprs = list(exprs)
        self._schema = Schema([StructField(n, e.dtype)
                               for n, e in zip(names, exprs)])

    @property
    def schema(self):
        return self._schema

    def execute_cpu(self, ctx):
        for table in self.children[0].execute_cpu(ctx):
            cols = table_to_cpu_cols(table)
            n = table.num_rows
            out = [cpu_eval(e, cols, n) for e in self.exprs]
            yield cpu_cols_to_table(out, self._schema)

    def describe(self):
        return f"CpuProjectExec[{', '.join(map(repr, self.exprs))}]"


class CpuFilterExec(CpuExec):
    def __init__(self, condition, child):
        super().__init__(child)
        self.condition = condition

    @property
    def schema(self):
        return self.children[0].schema

    def execute_cpu(self, ctx):
        for table in self.children[0].execute_cpu(ctx):
            cols = table_to_cpu_cols(table)
            n = table.num_rows
            v, m = cpu_eval(self.condition, cols, n)
            keep = m & v.astype(bool)
            yield table.filter(keep)

    def describe(self):
        return f"CpuFilterExec[{self.condition!r}]"


class CpuUnionExec(CpuExec):
    def __init__(self, children):
        super().__init__(*children)

    @property
    def schema(self):
        return self.children[0].schema

    def execute_cpu(self, ctx):
        for child in self.children:
            yield from child.execute_cpu(ctx)


class CpuLimitExec(CpuExec):
    def __init__(self, n, child):
        super().__init__(child)
        self.n = n

    @property
    def schema(self):
        return self.children[0].schema

    def execute_cpu(self, ctx):
        remaining = self.n
        for table in self.children[0].execute_cpu(ctx):
            if remaining <= 0:
                return
            if table.num_rows > remaining:
                table = table.slice(0, remaining)
            remaining -= table.num_rows
            yield table


class CpuExpandExec(CpuExec):
    def __init__(self, projections, names, child):
        super().__init__(child)
        self.projections = projections
        self._schema = Schema([StructField(n, e.dtype)
                               for n, e in zip(names, projections[0])])

    @property
    def schema(self):
        return self._schema

    def execute_cpu(self, ctx):
        import pyarrow as pa
        for table in self.children[0].execute_cpu(ctx):
            cols = table_to_cpu_cols(table)
            n = table.num_rows
            for proj in self.projections:
                out = [cpu_eval(e, cols, n) for e in proj]
                yield cpu_cols_to_table(out, self._schema)
