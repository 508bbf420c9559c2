"""Window execution operators.

TPU side (TpuWindowExec): coalesce to one batch, ONE sort by
(partition keys, order keys), then every window function is segmented-scan /
prefix-sum arithmetic on the sorted batch, un-permuted back to input order
(reference: rapids/GpuWindowExec.scala:92+ evaluates each window expression
with cuDF rolling windows; the sort-once design is the TPU-first
equivalent — see ops/windows.py).

CPU side (CpuWindowExec): a plain Python evaluation over host rows, serving
as the fallback executor and the comparison oracle.
"""
from __future__ import annotations

import math
from typing import Iterator, List, Sequence

import jax.numpy as jnp
import numpy as np

from ..columnar import Column, ColumnarBatch, concat_batches
from ..ops import expressions as E
from ..ops.windows import (UNBOUNDED, WindowFunc, eval_window_func,
                           segment_flags)
from ..types import Schema, StructField
from .base import (CpuExec, ExecContext, ExecNode, TpuExec,
                   record_output_batch)
from ..ops.sort_keys import sort_order
from ..metrics import names as MN
from ..utils.tracing import named_range


class TpuWindowExec(TpuExec):
    # "target", not "single": inputs under the batch target still coalesce
    # to one batch (the fast path), while oversized inputs arrive as
    # multiple batches and take the external partitioned path in execute()
    # (reference: GpuWindowExec requires a single batch per Spark partition,
    # and Spark's planner provides the hash exchange; here the exec inserts
    # its own, like TpuSortExec's external sort).
    child_coalesce_goal = "target"

    def __init__(self, part_exprs: Sequence[E.Expression],
                 order_exprs: Sequence[E.Expression],
                 ascending: Sequence[bool], nulls_first: Sequence[bool],
                 funcs: Sequence[WindowFunc], child: ExecNode):
        super().__init__(child)
        self.part_exprs = list(part_exprs)
        self.order_exprs = list(order_exprs)
        self.ascending = list(ascending)
        self.nulls_first = list(nulls_first)
        self.funcs = list(funcs)

    @property
    def schema(self):
        child = self.children[0].schema
        return Schema(list(child.fields)
                      + [StructField(f.name, f.dtype) for f in self.funcs])

    def describe(self):
        names = ", ".join(f.kind for f in self.funcs)
        return (f"TpuWindowExec[{names} over "
                f"partitionBy={len(self.part_exprs)} "
                f"orderBy={len(self.order_exprs)}]")

    def _window_kernel(self, batch: ColumnarBatch) -> ColumnarBatch:
        cap = batch.capacity
        all_exprs = self.part_exprs + self.order_exprs
        asc = [True] * len(self.part_exprs) + self.ascending
        nf = [True] * len(self.part_exprs) + self.nulls_first
        if all_exprs:
            order = sort_order(batch, all_exprs, asc, nf)
        else:
            order = jnp.arange(cap, dtype=jnp.int32)
        sorted_b = batch.take(order)
        seg_start, new_peer = segment_flags(sorted_b, self.part_exprs,
                                            self.order_exprs)
        # inverse permutation restores input row order
        inv = jnp.zeros(cap, dtype=jnp.int32).at[order].set(
            jnp.arange(cap, dtype=jnp.int32))
        out_cols = list(batch.columns)
        for f in self.funcs:
            wc = eval_window_func(f, sorted_b, seg_start, new_peer)
            out_cols.append(wc.take(inv))
        return ColumnarBatch(out_cols, batch.sel, self.schema)

    def kernel_key(self):
        from ..utils.kernel_cache import expr_key
        return ("TpuWindowExec",
                tuple(expr_key(e) for e in self.part_exprs),
                tuple(expr_key(e) for e in self.order_exprs),
                tuple(self.ascending), tuple(self.nulls_first),
                tuple((f.kind, f.frame, f.offset,
                       expr_key(f.child) if f.child is not None else None,
                       repr(f.default)) for f in self.funcs))

    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from .. import config as C
        from ..utils.kernel_cache import cached_kernel
        batches = list(self.children[0].execute(ctx))
        if not batches:
            return
        fn = cached_kernel(self.kernel_key(), lambda: self._window_kernel)
        total = sum(b.device_size_bytes() for b in batches)
        target = ctx.conf.get(C.BATCH_SIZE_BYTES)
        if len(batches) > 1 and total > target and self.part_exprs:
            # external window (the sort-exec shape, exec/sort.py:157-180):
            # a PARTITION-BY hash exchange through the spillable shuffle
            # store keeps every window partition whole within one hash
            # partition, so the single-batch kernel is per-partition and
            # peak HBM is bounded by the exchange target, not the input.
            # Spark's own physical plan for window is the same exchange
            # (hashpartitioning on the window partition spec); a global
            # window (no PARTITION BY) is a single Spark partition there
            # too, so it keeps the concat path below.
            from .exchange import TpuShuffleExchangeExec
            from .sort import _PrefetchedSource
            n_parts = max(2, -(-total // max(target, 1)))
            ex = TpuShuffleExchangeExec(
                "hash", self.part_exprs, int(n_parts),
                _PrefetchedSource(batches, self.children[0].schema))
            del batches  # the source owns (and drains) the only reference
            for part in ex.execute(ctx):
                out = self._run(fn, [part])
                record_output_batch(self.metrics, out, ctx.runtime)
                yield out
            return
        out = self._run(fn, batches)
        record_output_batch(self.metrics, out, ctx.runtime)
        yield out

    def _run(self, fn, batches) -> ColumnarBatch:
        """One launch of the window kernel over `batches` as one batch,
        inside a `srt:window` span (the coalesce and the launch; the
        child's own work is outside it) and counted at capacity."""
        with named_range("window", self.metrics, MN.WINDOW_TIME,
                         batches=len(batches),
                         rows=sum(b.capacity for b in batches)):
            batch = batches[0] if len(batches) == 1 \
                else concat_batches(batches)
            self.metrics.add(MN.WINDOW_ROWS, batch.capacity)
            self.metrics.add(MN.WINDOW_BATCHES, 1)
            return fn(batch)


# --------------------------------------------------------------------------
# CPU fallback / oracle
# --------------------------------------------------------------------------

def _order_key(v, ascending: bool, nulls_first: bool):
    """One sortable component; nulls placed per effective spec, NaN
    greatest (Spark ordering semantics)."""
    if v is None:
        return (0 if nulls_first else 2, 0)
    if isinstance(v, float) and math.isnan(v):
        v = float("inf")  # NaN greatest; desc negation flips it to first
    return (1, v if ascending else _neg(v))


class CpuWindowExec(CpuExec):
    def __init__(self, part_exprs, order_exprs, ascending, nulls_first,
                 funcs: Sequence[WindowFunc], child: ExecNode):
        super().__init__(child)
        self.part_exprs = list(part_exprs)
        self.order_exprs = list(order_exprs)
        self.ascending = list(ascending)
        self.nulls_first = list(nulls_first)
        self.funcs = list(funcs)

    @property
    def schema(self):
        child = self.children[0].schema
        return Schema(list(child.fields)
                      + [StructField(f.name, f.dtype) for f in self.funcs])

    def execute_cpu(self, ctx: ExecContext):
        import pyarrow as pa
        from ..ops.cpu_eval import cpu_eval, table_to_cpu_cols
        from ..types import to_arrow
        tables = list(self.children[0].execute_cpu(ctx))
        if not tables:
            return
        table = pa.concat_tables(tables) if len(tables) > 1 else tables[0]
        n = table.num_rows
        ccols = table_to_cpu_cols(table)

        def pylist(expr):
            vals, valid = cpu_eval(expr, ccols, n)
            return [v if ok else None for v, ok in
                    zip(vals.tolist(), valid.tolist())]

        # evaluate every key and value expression once over the whole table
        part_vals = [pylist(e) for e in self.part_exprs]
        order_vals = [pylist(e) for e in self.order_exprs]
        child_vals = {f.name: pylist(f.child)
                      for f in self.funcs if f.child is not None}

        def norm(v):
            return "\0nan" if isinstance(v, float) and math.isnan(v) else v

        # group rows by partition key
        groups: dict = {}
        for i in range(n):
            key = tuple(norm(pv[i]) for pv in part_vals)
            groups.setdefault(key, []).append(i)

        out = {f.name: [None] * n for f in self.funcs}
        for rows in groups.values():
            # sort within the partition by the order keys
            def sort_key(i):
                return [_order_key(ov[i], asc, nf)
                        for ov, asc, nf in zip(order_vals, self.ascending,
                                               self.nulls_first)]
            if self.order_exprs:
                rows = sorted(rows, key=sort_key)
            self._eval_partition(rows, order_vals, out, child_vals)
        arrays = [table.column(i) for i in range(table.num_columns)]
        names = list(table.column_names)
        for f in self.funcs:
            vals = out[f.name]
            if f.dtype.is_integral:
                # python-int accumulation is unbounded; Spark (non-ANSI)
                # and the device path wrap at int64 — match them
                vals = [None if v is None
                        else (int(v) + 2**63) % 2**64 - 2**63
                        for v in vals]
            arrays.append(pa.array(vals, type=to_arrow(f.dtype)))
            names.append(f.name)
        yield pa.table(arrays, names=names)

    def _eval_partition(self, rows: List[int], order_cols, out, child_vals):
        m = len(rows)
        order_vals = [tuple(oc[i] for oc in order_cols) for i in rows]

        def peers_equal(a, b):
            def nrm(v):
                return "\0nan" if isinstance(v, float) and math.isnan(v) \
                    else v
            return tuple(map(nrm, order_vals[a])) == \
                tuple(map(nrm, order_vals[b]))

        for f in self.funcs:
            vals = None
            if f.child is not None:
                allv = child_vals[f.name]
                vals = [allv[i] for i in rows]
            res = out[f.name]
            if f.kind == "RowNumber":
                for j, i in enumerate(rows):
                    res[i] = j + 1
                continue
            if f.kind == "Rank":
                rank = 1
                for j, i in enumerate(rows):
                    if j > 0 and not peers_equal(j, j - 1):
                        rank = j + 1
                    res[i] = rank
                continue
            if f.kind == "DenseRank":
                rank = 1
                for j, i in enumerate(rows):
                    if j > 0 and not peers_equal(j, j - 1):
                        rank += 1
                    res[i] = rank
                continue
            if f.kind in ("Lag", "Lead"):
                k = f.offset if f.kind == "Lag" else -f.offset
                for j, i in enumerate(rows):
                    src = j - k
                    res[i] = vals[src] if 0 <= src < m else f.default
                continue
            for j, i in enumerate(rows):
                a, b = self._frame(f, j, m, peers_equal)
                window = vals[a:b + 1] if vals is not None else [1] * max(
                    0, b - a + 1)
                if f.kind in ("First", "Last"):
                    # Spark first/last default ignoreNulls=False: the frame
                    # boundary row's value, null included
                    res[i] = None if not window else (
                        window[0] if f.kind == "First" else window[-1])
                    continue
                window = [v for v in window if v is not None]
                res[i] = self._agg(f.kind, window)

    @staticmethod
    def _frame(f: WindowFunc, j: int, m: int, peers_equal):
        if f.frame[0] == "whole":
            return 0, m - 1
        if f.frame[0] == "range_to_current":
            b = j
            while b + 1 < m and peers_equal(b + 1, j):
                b += 1
            return 0, b
        _r, start, end = f.frame
        a = 0 if start <= -UNBOUNDED else max(0, j + start)
        b = m - 1 if end >= UNBOUNDED else min(m - 1, j + end)
        return a, b

    @staticmethod
    def _agg(kind: str, window: list):
        if kind == "Count":
            return len(window)
        if not window:
            return None
        if kind == "Sum":
            return sum(window)
        if kind == "Average":
            return sum(window) / len(window)
        if kind in ("Min", "Max"):
            # Spark: NaN is GREATEST (python min/max mishandle NaN because
            # nan<x is always False)
            def key(v):
                if isinstance(v, float) and math.isnan(v):
                    return (1, 0.0)
                return (0, v)
            return (min if kind == "Min" else max)(window, key=key)
        if kind == "First":
            return window[0]
        if kind == "Last":
            return window[-1]
        raise AssertionError(kind)


def _neg(v):
    """Order-inverting transform for descending sort keys.  Strings become
    negated byte tuples with a terminator larger than any negated byte, so
    a prefix still sorts AFTER its extensions under DESC (b'ab' > b'a')."""
    if isinstance(v, bool):
        return not v
    if isinstance(v, (int, float)):
        return -v
    if isinstance(v, str):
        return tuple(-b for b in v.encode("utf-8")) + (1,)
    return v


def make_window_exec(meta, child: ExecNode, on_tpu: bool) -> ExecNode:
    r = meta.resolved
    if on_tpu:
        return TpuWindowExec(r["part_exprs"], r["order_exprs"],
                             r["ascending"], r["nulls_first"], r["funcs"],
                             child)
    return CpuWindowExec(r["part_exprs"], r["order_exprs"], r["ascending"],
                         r["nulls_first"], r["funcs"], child)
