"""Columnar batches: struct-of-arrays with a STATIC bucketed capacity.

The TPU analogue of Spark's ColumnarBatch over GpuColumnVector
(reference: sql-plugin/src/main/java/.../GpuColumnVector.java batch<->Table
conversions).  Design differences, deliberately TPU-first:

  * capacity is rounded up to power-of-two buckets so every (plan, bucket)
    pair compiles exactly once under jit (XLA static shapes);
  * the live row set is a boolean `sel` mask instead of a compacted length —
    filters just AND into the mask and defer compaction to batch boundaries
    (coalesce/shuffle/materialize), where one gather pays for many operators;
  * the whole batch is a pytree, so operator pipelines take and return batches
    inside a single traced function.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..types import (DataType, Schema, StructField, from_arrow, to_arrow,
                     StringType)
from .column import Column, bucket_strlen


def bucket_rows(n: int, minimum: int = 1024) -> int:
    """Round row count up to a power-of-two capacity bucket."""
    b = minimum
    while b < n:
        b <<= 1
    return b


@jax.tree_util.register_pytree_node_class
class ColumnarBatch:
    """columns + selection mask. `schema` and `capacity` are static."""

    # __weakref__: the donation-safety registry (mem/donation.py) pins
    # multi-owner batches in a WeakSet so pins die with the batch
    __slots__ = ("columns", "sel", "schema", "known_rows", "__weakref__")

    def __init__(self, columns: Sequence[Column], sel, schema: Schema):
        self.columns = tuple(columns)
        self.sel = sel
        self.schema = schema
        # host-known live-row count, when the producer already holds it
        # (scan chunk metadata, a join's fetched total): lets downstream
        # adaptive decisions (maybe_shrink) skip a device sync.  NOT part
        # of the pytree (values in the treedef would retrace per count);
        # any structural transform drops it back to None.
        self.known_rows = None

    def tree_flatten(self):
        return (self.columns, self.sel), self.schema

    @classmethod
    def tree_unflatten(cls, schema, children):
        columns, sel = children
        return cls(columns, sel, schema)

    # ---- static metadata ---------------------------------------------------

    @property
    def capacity(self) -> int:
        return int(self.sel.shape[0])

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    def column(self, i_or_name) -> Column:
        if isinstance(i_or_name, str):
            return self.columns[self.schema.index_of(i_or_name)]
        return self.columns[i_or_name]

    # ---- row-count (traced) ------------------------------------------------

    def num_rows(self):
        """Traced scalar count of live rows."""
        return jnp.sum(self.sel.astype(jnp.int32))

    def num_rows_host(self) -> int:
        if self.known_rows is not None:
            return self.known_rows
        return int(self.num_rows())

    def device_size_bytes(self) -> int:
        """Static upper bound on HBM footprint."""
        total = self.sel.size * 1
        for c in self.columns:
            total += c.data.size * c.data.dtype.itemsize
            total += c.valid.size
            if c.lengths is not None:
                total += c.lengths.size * 4
        return total

    # ---- structural transforms (jit-safe) ----------------------------------

    def with_sel(self, sel) -> "ColumnarBatch":
        return ColumnarBatch(self.columns, sel, self.schema)

    def filter(self, keep) -> "ColumnarBatch":
        """AND a predicate into the selection mask — no data movement."""
        return self.with_sel(jnp.logical_and(self.sel, keep))

    def take(self, indices, sel=None) -> "ColumnarBatch":
        cols = [c.take(indices) for c in self.columns]
        if sel is None:
            sel = jnp.take(self.sel, indices, mode="clip")
        return ColumnarBatch(cols, sel, self.schema)

    def shrink_to(self, new_cap: int) -> "ColumnarBatch":
        """Live rows gathered (stably) into a SMALLER-capacity batch.

        The sort/aggregate kernels cost O(capacity log capacity) no
        matter how few rows are live — a selective filter or a grouped
        aggregate leaves a handful of live rows in an input-capacity
        batch, and sorting 8M dead rows to order 6 live ones dominated
        TPC-H q1 (measured ~7s of its 19s).  One cumsum + scatter + per-
        column gather; caller guarantees new_cap >= num_rows."""
        pos = jnp.cumsum(self.sel.astype(jnp.int32)) - 1
        iota = jnp.arange(self.capacity, dtype=jnp.int32)
        idx = jnp.zeros(new_cap, jnp.int32).at[
            jnp.where(self.sel, pos, new_cap)].set(iota, mode="drop")
        cols = [c.take(idx) for c in self.columns]
        sel2 = jnp.arange(new_cap, dtype=jnp.int32) < self.num_rows()
        return ColumnarBatch(cols, sel2, self.schema)

    def maybe_shrink(self, n_live: int) -> "ColumnarBatch":
        """shrink_to a bucket when mostly dead (>=8x oversized, which
        with bucket_rows' 1024 floor means capacity >= 8192); host caller
        passes the synced live count."""
        new_cap = bucket_rows(max(n_live, 1))
        if self.capacity >= 8 * new_cap:
            return self.shrink_to(new_cap)
        return self

    def compact(self) -> "ColumnarBatch":
        """Gather live rows to the front (stable).  Capacity unchanged.

        The permutation is a 1-bit stable sort (utils/packed_sort): live
        rows keep relative order, dead rows go to the back; compact runs
        per batch in every concat/coalesce."""
        from ..utils.packed_sort import stable_argsort
        cap = self.capacity
        iota = jnp.arange(cap, dtype=jnp.int32)
        order = stable_argsort([((~self.sel).astype(jnp.uint64), 1)], cap)
        n = self.num_rows()
        new_sel = iota < n
        return self.take(order, sel=new_sel)

    def select_columns(self, indices: Sequence[int],
                       schema: Optional[Schema] = None) -> "ColumnarBatch":
        cols = [self.columns[i] for i in indices]
        if schema is None:
            schema = Schema([self.schema[i] for i in indices])
        return ColumnarBatch(cols, self.sel, schema)

    # ---- host interop ------------------------------------------------------

    @staticmethod
    def from_pydict(data: dict, schema: Schema,
                    capacity: Optional[int] = None) -> "ColumnarBatch":
        n = len(next(iter(data.values()))) if data else 0
        cap = capacity if capacity is not None else bucket_rows(max(n, 1))
        cols = []
        for f in schema:
            vals = data[f.name]
            if f.dtype.is_string:
                cols.append(Column.from_strings(vals, capacity=cap))
            else:
                valid = np.array([v is not None for v in vals], dtype=np.bool_)
                clean = np.array([0 if v is None else v for v in vals])
                cols.append(Column.from_numpy(clean, valid, f.dtype,
                                              capacity=cap))
        sel = jnp.arange(cap, dtype=jnp.int32) < n
        return ColumnarBatch(cols, sel, schema)

    @staticmethod
    def from_arrow(table, capacity: Optional[int] = None) -> "ColumnarBatch":
        """Build a device batch from a pyarrow Table (H2D transfer point)."""
        import pyarrow as pa
        import pyarrow.compute as pc
        n = table.num_rows
        cap = capacity if capacity is not None else bucket_rows(max(n, 1))
        fields = []
        cols = []
        for name, col in zip(table.column_names, table.columns):
            at = col.type
            dt = from_arrow(at)
            fields.append(StructField(name, dt))
            arr = col.combine_chunks() if col.num_chunks != 1 else col.chunk(0)
            if pa.types.is_dictionary(arr.type):
                arr = arr.dictionary_decode()
            if pa.types.is_decimal(arr.type):
                arr = pc.cast(arr, pa.float64())
            if dt.is_string:
                cols.append(Column.from_arrow_strings(arr, capacity=cap))
                continue
            if pa.types.is_date32(arr.type):
                arr = arr.view(pa.int32())
            elif pa.types.is_timestamp(arr.type):
                arr = pc.cast(arr, pa.timestamp("us", tz="UTC")).view(pa.int64())
            elif pa.types.is_boolean(arr.type):
                arr = pc.cast(arr, pa.uint8())
            valid_np = np.ones(n, dtype=np.bool_)
            if arr.null_count:
                valid_np = np.asarray(arr.is_valid())
                arr = arr.fill_null(0)
            vals = arr.to_numpy(zero_copy_only=False)
            if dt.np_dtype == np.bool_:
                vals = vals.astype(np.bool_)
            cols.append(Column.from_numpy(vals, valid_np, dt, capacity=cap))
        sel = jnp.arange(cap, dtype=jnp.int32) < n
        return ColumnarBatch(cols, sel, Schema(fields))

    def _live_rows(self):
        """Selector of live rows for the D2H tail.

        Returns (rows, n) where rows is an int prefix length, a numpy
        index array, or a DEVICE int32 index array (bucket-padded).  The
        device form triggers a per-column device gather in _host_rows so
        only ~n rows ever cross to the host: a static-shape aggregate or
        sort emits its handful of result rows in an input-capacity batch,
        and materializing 8M-row buffers to read 6 rows dominated collect
        (measured 17.8s of TPC-H q1's 18.2s steady state).  Indices pad
        to a power-of-two bucket so gather compiles stay bounded."""
        sel_np = np.asarray(self.sel)
        n = int(sel_np.sum())
        dense = bool(sel_np[:n].all())
        if self.capacity >= 8 * bucket_rows(n):
            import jax.numpy as jnp
            idx = (np.arange(n, dtype=np.int32) if dense
                   else np.flatnonzero(sel_np).astype(np.int32))
            padded = np.zeros(bucket_rows(max(n, 1)), np.int32)
            padded[:n] = idx
            return jnp.asarray(padded), n
        if dense:
            return n, n
        return np.flatnonzero(sel_np), n

    def to_arrow(self):
        """D2H: convert live rows to a pyarrow Table (vectorized — one
        buffer-level conversion per column, no per-row Python loop)."""
        import pyarrow as pa
        rows, n = self._live_rows()
        arrays = [c.to_arrow(rows, to_arrow(f.dtype), n=n)
                  for f, c in zip(self.schema, self.columns)]
        return pa.table(arrays, names=self.schema.names)

    def to_pylist(self) -> List[tuple]:
        rows, n = self._live_rows()
        cols = [c.to_pylist(rows, n=n) for c in self.columns]
        return list(zip(*cols)) if cols else [()] * n

    def __repr__(self):  # pragma: no cover
        return (f"ColumnarBatch(cap={self.capacity}, "
                f"schema={self.schema!r})")


def _normalize_devices(batches: Sequence[ColumnarBatch]
                       ) -> Sequence[ColumnarBatch]:
    """Move single-device batches committed to DIFFERENT devices onto
    one device before eager concatenation: the mesh shuffle tier serves
    reduce partition p as device p's shard of the exchanged chunks
    (shuffle/mesh_exchange.py), so a coalesced read or a chunk staging
    that concatenates across partitions mixes committed devices — which
    eager dynamic_update_slice rejects.  device_put is jax's TRANSFER
    path (D2D over ICI on a real mesh; bit-exact, unlike cross-shard
    eager compute).  Mesh-SHARDED (multi-device) inputs are left
    untouched — re-placing a global array would gather it."""
    devs = []
    for b in batches:
        d = getattr(b.sel, "devices", None)
        devs.append(d() if callable(d) else None)
    if any(d is None or len(d) != 1 for d in devs):
        return batches  # tracers / host arrays / sharded globals
    if len(set().union(*devs)) <= 1:
        return batches  # already co-located (the common case)
    target = next(iter(devs[0]))
    return [b if devs[i] == {target} else jax.device_put(b, target)
            for i, b in enumerate(batches)]


def concat_batches(batches: Sequence[ColumnarBatch],
                   capacity: Optional[int] = None) -> ColumnarBatch:
    """Concatenate batches (the coalesce primitive; reference:
    GpuCoalesceBatches.scala concatenates via cudf Table.concatenate).

    Host-driven: capacities are static per input, result capacity is the
    bucket of the sum of capacities (or caller-provided)."""
    assert batches, "concat of nothing"
    schema = batches[0].schema
    batches = _normalize_devices(batches)
    compacted = [b.compact() for b in batches]
    counts = [b.num_rows_host() for b in compacted]
    total = sum(counts)
    cap = capacity if capacity is not None else bucket_rows(max(total, 1))
    out_cols = []
    for ci, f in enumerate(schema):
        parts = [b.columns[ci] for b in compacted]
        if f.dtype.is_string:
            ml = max(p.max_len for p in parts)
            parts = [p.pad_strings_to(ml) for p in parts]
            data = jnp.zeros((cap, ml), dtype=jnp.uint8)
            lengths = jnp.zeros(cap, dtype=jnp.int32)
            valid = jnp.zeros(cap, dtype=jnp.bool_)
            off = 0
            for p, cnt in zip(parts, counts):
                data = jax.lax.dynamic_update_slice(data, p.data[:cnt],
                                                    (off, 0))
                lengths = jax.lax.dynamic_update_slice(lengths,
                                                       p.lengths[:cnt], (off,))
                valid = jax.lax.dynamic_update_slice(valid, p.valid[:cnt],
                                                     (off,))
                off += cnt
            out_cols.append(Column(data, valid, f.dtype, lengths))
        else:
            data = jnp.zeros(cap, dtype=f.dtype.jnp_dtype)
            valid = jnp.zeros(cap, dtype=jnp.bool_)
            off = 0
            for p, cnt in zip(parts, counts):
                data = jax.lax.dynamic_update_slice(data, p.data[:cnt], (off,))
                valid = jax.lax.dynamic_update_slice(valid, p.valid[:cnt],
                                                     (off,))
                off += cnt
            out_cols.append(Column(data, valid, f.dtype))
    sel = jnp.arange(cap, dtype=jnp.int32) < total
    return ColumnarBatch(out_cols, sel, schema)
