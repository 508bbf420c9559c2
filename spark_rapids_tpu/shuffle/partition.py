"""Device partitioners: per-row partition ids + batch split.

TPU-native analogue of the reference's partitioner family
(rapids/GpuHashPartitioning.scala — murmur3 on device matching Spark;
GpuRangePartitioner.scala:42-216 — host reservoir sampling for bounds,
device searchsorted; GpuRoundRobinPartitioning.scala; GpuSinglePartitioning
.scala) and of `Table.contiguousSplit` (Plugin.scala:54-83): one device sort
by partition id splits a batch into per-partition contiguous sub-batches.

All id kernels are pure jnp and trace into the surrounding program; the
split syncs ONCE to the host for the per-partition counts (the same sync
contiguousSplit's size array implies).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..columnar import Column, ColumnarBatch, bucket_rows
from ..ops.hashing import spark_hash_columns
from ..ops.sort_keys import column_sort_keys, sort_order
from ..utils.packed_sort import stable_argsort


# ---- partition id kernels (traced) -----------------------------------------

def hash_partition_ids(key_cols: Sequence[Column], n: int) -> jnp.ndarray:
    """Spark semantics: Pmod(Murmur3Hash(keys, 42), n) — non-negative."""
    h = spark_hash_columns(list(key_cols), seed=42)
    return ((h % jnp.int32(n)) + jnp.int32(n)) % jnp.int32(n)


def round_robin_partition_ids(capacity: int, n: int, start: int
                              ) -> jnp.ndarray:
    """Row-position round robin from a per-task start offset."""
    iota = jnp.arange(capacity, dtype=jnp.int32)
    return (iota + jnp.int32(start)) % jnp.int32(n)


def single_partition_ids(capacity: int) -> jnp.ndarray:
    return jnp.zeros(capacity, dtype=jnp.int32)


def range_partition_ids(batch: ColumnarBatch,
                        sort_exprs, ascending: Sequence[bool],
                        nulls_first: Sequence[bool],
                        bounds_batch: ColumnarBatch) -> jnp.ndarray:
    """Partition id = number of range bounds strictly below the row, under
    the sort-key ordering (nulls placed per spec).  The B bounds live in a
    small device batch; the compare is a static loop over B reusing the sort
    module's order-preserving key encoding — O(cap*B) elementwise, no
    searchsorted with dynamic shapes."""
    row_keys = _encoded_keys(batch, sort_exprs, ascending, nulls_first)
    # the bounds batch's columns are POSITIONAL (k0..km-1), not the child
    # schema — re-bind by ordinal, never by the original expressions
    bound_refs = [_bound_ref(i, e.dtype) for i, e in enumerate(sort_exprs)]
    bnd_keys = _encoded_keys(bounds_batch, bound_refs, ascending, nulls_first)
    B = bounds_batch.capacity
    nbounds = int(bounds_batch.num_rows_host())
    pid = jnp.zeros(batch.capacity, dtype=jnp.int32)
    for b in range(nbounds):
        gt = jnp.zeros(batch.capacity, dtype=jnp.bool_)
        eq = jnp.ones(batch.capacity, dtype=jnp.bool_)
        for rk, bk in zip(row_keys, bnd_keys):
            bkb = bk[b]
            gt = gt | (eq & (rk > bkb))
            eq = eq & (rk == bkb)
        # row beyond bound b (ties stay in the lower partition, like
        # Spark's RangePartitioner binary search with <=)
        pid = pid + gt.astype(jnp.int32)
    return pid


def _encoded_keys(batch: ColumnarBatch, sort_exprs, ascending,
                  nulls_first) -> List[jnp.ndarray]:
    keys: List[jnp.ndarray] = []
    for e, asc, nf in zip(sort_exprs, ascending, nulls_first):
        c = e.eval(batch)
        null_rank = jnp.where(c.valid, jnp.int32(1),
                              jnp.int32(0) if nf else jnp.int32(2))
        keys.append(null_rank)
        keys.extend(column_sort_keys(c, asc))
    return keys


# ---- range bound sampling (host side) --------------------------------------

def sample_range_bounds(batches: Sequence[ColumnarBatch], sort_exprs,
                        ascending: Sequence[bool],
                        nulls_first: Sequence[bool], n_parts: int,
                        sample_size: int = 4096,
                        seed: int = 42) -> Optional[ColumnarBatch]:
    """Reservoir-sample sort-key rows across batches on the HOST, order them
    with the device sort kernel, and pick n_parts-1 evenly spaced bounds
    (reference: GpuRangePartitioner.sketch/determineBounds,
    GpuRangePartitioner.scala:42-216 + SamplingUtils.scala).  Returns a
    small device batch of bound rows, or None when there is no data."""
    from ..types import Schema, StructField

    key_schema = Schema([StructField(f"k{i}", e.dtype)
                         for i, e in enumerate(sort_exprs)])
    rng = np.random.RandomState(seed)
    reservoir: List[tuple] = []
    seen = 0
    for b in batches:
        cols = [e.eval(b) for e in sort_exprs]
        kb = ColumnarBatch(cols, b.sel, key_schema)
        for row in kb.to_pylist():
            seen += 1
            if len(reservoir) < sample_size:
                reservoir.append(row)
            else:
                j = rng.randint(0, seen)
                if j < sample_size:
                    reservoir[j] = row
    if not reservoir:
        return None
    sample = ColumnarBatch.from_pydict(
        {f.name: [r[i] for r in reservoir]
         for i, f in enumerate(key_schema)}, key_schema)
    refs = [_bound_ref(i, e.dtype) for i, e in enumerate(sort_exprs)]
    order = sort_order(sample, refs, list(ascending), list(nulls_first))
    ordered = sample.take(order).compact()
    cnt = ordered.num_rows_host()
    picks = [min(cnt - 1, max(0, round((b + 1) * cnt / n_parts) - 1))
             for b in range(n_parts - 1)]
    rows = ordered.to_pylist()
    chosen = [rows[p] for p in picks]
    return ColumnarBatch.from_pydict(
        {f.name: [r[i] for r in chosen] for i, f in enumerate(key_schema)},
        key_schema, capacity=bucket_rows(max(len(chosen), 1)))


def _bound_ref(i: int, dtype):
    from ..ops import expressions as E
    return E.BoundReference(i, dtype, f"k{i}")


# ---- split (contiguousSplit equivalent) ------------------------------------

def split_by_partition(batch: ColumnarBatch, pids: jnp.ndarray, n: int,
                       min_bucket: int = 1024
                       ) -> List[Tuple[int, ColumnarBatch]]:
    """Split into per-partition compacted sub-batches.

    One stable device sort groups rows by partition id (dead rows pushed
    past all partitions), one host sync reads the n counts, then each
    non-empty partition is a clipped gather into a bucketed capacity.
    Returns [(partition_id, batch)] for non-empty partitions."""
    cap = batch.capacity
    live = batch.sel
    key = jnp.where(live, pids.astype(jnp.int64), jnp.int64(n))
    order = stable_argsort(
        [(key.astype(jnp.uint64), max(1, int(n).bit_length()))], cap)
    sorted_batch = batch.take(order)
    counts = np.asarray(jnp.bincount(
        jnp.where(live, pids, jnp.int32(n)), length=n + 1))[:n]
    out: List[Tuple[int, ColumnarBatch]] = []
    off = 0
    for p in range(n):
        cnt = int(counts[p])
        if cnt == 0:
            continue
        pcap = bucket_rows(cnt, min_bucket)
        idx = off + jnp.arange(pcap, dtype=jnp.int32)
        sel = jnp.arange(pcap, dtype=jnp.int32) < cnt
        sub = sorted_batch.take(idx, sel=sel)
        # the count is already host-known here: stamping it lets the
        # shuffle write path record map-output statistics (and the worker
        # report MapStatus rows) without a device sync per sub-batch
        sub.known_rows = cnt
        out.append((p, sub))
        off += cnt
    return out
