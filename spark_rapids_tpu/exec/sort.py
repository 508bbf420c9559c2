"""TPU sort.

Reference behavior: rapids/GpuSortExec.scala — per-batch cuDF Table.orderBy
with null-ordering support; global sorts rely on upstream range
partitioning.  TPU-first implementation: every sort column is encoded into
order-preserving integer keys (ops/sort_keys.py) and ONE stable argsort
(utils/packed_sort.py) orders the whole batch — no comparator kernels.
"""
from __future__ import annotations

from typing import Sequence

from ..columnar import ColumnarBatch, concat_batches
from ..ops import expressions as E
from ..ops.sort_keys import sort_order
from .base import ExecContext, ExecNode, TpuExec, record_output_batch
from ..metrics import names as MN

# which-path record per (sort kernel key, batch capacity), written at
# TRACE time by the kernel closure (the decision is static per
# key+shape — capacity drives both the power-of-two guard and the
# radix-pass threshold, so two shapes under one key may take different
# paths): lets the exec count numPackedSorts per dispatch even when the
# compiled kernel came from another exec instance's earlier build.
# Bounded: same cardinality as the jit shape cache, pruned defensively.
_PACKED_BY_KEY: dict = {}
_PACKED_BY_KEY_MAX = 4096


class _PrefetchedSource(TpuExec):
    """Exec wrapper over already-drained batches (feeds the internal range
    exchange of the external-sort path).  Consumed batches are dropped so
    the only long-lived copy is the exchange's spillable partition store —
    holding both would double peak HBM on exactly the inputs this path
    exists for."""

    def __init__(self, batches, schema):
        super().__init__()
        self._batches = list(batches)
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"_PrefetchedSource[{len(self._batches)} batches]"

    def execute(self, ctx: ExecContext):
        while self._batches:
            yield self._batches.pop(0)


class TpuSortExec(TpuExec):
    """Global sort.

    Small inputs: concat to one batch, one sort kernel.  Inputs past the
    batch target use Spark's own physical shape instead of a giant concat
    (the round-2 HBM cliff): a RANGE-partition exchange through the
    spillable shuffle store, then one sort per partition, yielded in
    bound order — partition order IS global order (reference:
    GpuRangePartitioner.scala:42-216 + per-partition GpuSortExec)."""

    def __init__(self, sort_exprs: Sequence[E.Expression],
                 ascending: Sequence[bool], nulls_first: Sequence[bool],
                 child: ExecNode):
        super().__init__(child)
        self.sort_exprs = list(sort_exprs)
        self.ascending = list(ascending)
        self.nulls_first = list(nulls_first)

    @property
    def schema(self):
        return self.children[0].schema

    def kernel_key(self):
        from ..utils.kernel_cache import expr_key
        return ("TpuSortExec",
                tuple(expr_key(e) for e in self.sort_exprs),
                tuple(self.ascending), tuple(self.nulls_first))

    def _make_sort_kernel(self, skey):
        """Builder for the per-batch sort kernel; records (at trace
        time, host-side) whether the packed-key path was taken for this
        kernel key so the exec can count numPackedSorts per dispatch."""
        exprs, asc, nf = self.sort_exprs, self.ascending, self.nulls_first

        def kern(batch: ColumnarBatch) -> ColumnarBatch:
            stats: dict = {}
            order = sort_order(batch, exprs, asc, nf, stats=stats)
            if len(_PACKED_BY_KEY) >= _PACKED_BY_KEY_MAX:
                _PACKED_BY_KEY.clear()
            _PACKED_BY_KEY[(skey, batch.capacity)] = stats.get("packed",
                                                               False)
            return batch.take(order)
        return kern

    def _cpu_twin(self):
        """CPU re-execution plan for OOM fallback (exec/retryable.py)."""
        from .basic import DeviceToHostExec
        from .cpu_relational import CpuSortExec
        return CpuSortExec(self.sort_exprs, self.ascending,
                           self.nulls_first,
                           DeviceToHostExec(self.children[0]))

    def execute(self, ctx: ExecContext):
        from .retryable import execute_with_cpu_fallback
        yield from execute_with_cpu_fallback(
            self, ctx, self._execute_device(ctx), self._cpu_twin)

    def _execute_device(self, ctx: ExecContext):
        from .. import config as C
        from ..utils.kernel_cache import cached_kernel
        from .retryable import run_retryable
        skey = self.kernel_key()
        fn = cached_kernel(skey, lambda: self._make_sort_kernel(skey))

        def attempt_sort(b):
            # retry-only block: splitting a global sort batch would break
            # total order; exhaustion falls back to the CPU sort instead.
            # The reserve marks the sort's working-set boundary.
            if ctx.runtime is not None:
                ctx.runtime.reserve(b.device_size_bytes(), site="sort")
            out = fn(b)
            if _PACKED_BY_KEY.get((skey, b.capacity)):
                self.metrics.add(MN.NUM_PACKED_SORTS, 1)
            return out

        batches = list(self.children[0].execute(ctx))
        if not batches:
            return
        total = sum(b.device_size_bytes() for b in batches)
        target = ctx.conf.get(C.BATCH_SIZE_BYTES)
        if len(batches) > 1 and total > target:
            # external sort: range exchange -> per-partition sort
            from .exchange import TpuShuffleExchangeExec
            n_parts = max(2, -(-total // max(target, 1)))
            ex = TpuShuffleExchangeExec(
                "range", self.sort_exprs, int(n_parts),
                _PrefetchedSource(batches, self.schema),
                ascending=self.ascending, nulls_first=self.nulls_first)
            del batches  # the source owns (and drains) the only reference
            for part in ex.execute(ctx):
                with self.metrics.timer(MN.SORT_TIME):
                    out = run_retryable(ctx, self.metrics, "sort",
                                        attempt_sort, [part])[0]
                record_output_batch(self.metrics, out, ctx.runtime)
                yield out
            return
        batch = batches[0] if len(batches) == 1 else concat_batches(batches)
        # a mostly-dead input (post-filter, post-aggregate) sorts at its
        # full capacity otherwise — shrink first (batch.shrink_to)
        batch = batch.maybe_shrink(batch.num_rows_host())
        with self.metrics.timer(MN.SORT_TIME):
            out = run_retryable(ctx, self.metrics, "sort",
                                attempt_sort, [batch])[0]
        record_output_batch(self.metrics, out, ctx.runtime)
        yield out

    def describe(self):
        parts = []
        for e, a, nf in zip(self.sort_exprs, self.ascending,
                            self.nulls_first):
            parts.append(f"{e!r} {'ASC' if a else 'DESC'} "
                         f"NULLS {'FIRST' if nf else 'LAST'}")
        return f"TpuSortExec[{', '.join(parts)}]"
