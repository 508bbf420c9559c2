"""Contiguous-buffer batches: a whole ColumnarBatch as ONE device buffer.

Reference analogue: GpuColumnVectorFromBuffer / ContiguousTable
(sql-plugin/src/main/java/.../GpuColumnVectorFromBuffer.java:1-95,
rapids/MetaUtils.scala:41-137) — cuDF carves every column out of one device
allocation so a shuffle partition or spill unit is one transferable buffer.

The TPU version packs on device with a single compiled kernel: every leaf is
bit-reinterpreted to bytes and concatenated into one uint8 array.  What that
buys here is TRANSFER granularity, not allocator control (XLA owns device
memory): device->host moves one array instead of 3-4 leaves per column,
which matters because every transfer pays the host link's round trip, and
for the shuffle transport's bounce-buffer staging.

XLA:TPU has no float64 byte bitcast (it carries a double as an f32 pair);
those leaves pack as the (hi, lo) f32 pair's bytes and unpack by summation —
exactly reversible for every value the device represents, the same envelope
as ops/hashing.f64_bits.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..types import Schema
from ..utils.kernel_cache import cached_kernel
from .batch import ColumnarBatch
from .column import Column


@dataclass
class LeafSlot:
    """Where one leaf lives inside the flat buffer."""
    offset: int
    nbytes: int
    shape: Tuple[int, ...]
    dtype: str          # logical jnp dtype of the leaf
    f64_pair: bool      # packed as (hi, lo) float32 pair


@dataclass
class ContiguousMeta:
    schema: Schema
    capacity: int
    slots: List[LeafSlot]           # per-column leaves, then sel last
    leaves_per_col: List[int]
    total_bytes: int


class ContiguousBatch:
    """One uint8 device buffer + reconstruction metadata."""

    __slots__ = ("buffer", "meta")

    def __init__(self, buffer, meta: ContiguousMeta):
        self.buffer = buffer
        self.meta = meta

    @property
    def nbytes(self) -> int:
        return self.meta.total_bytes


def _leaves_of(batch: ColumnarBatch):
    out = []
    per_col = []
    for c in batch.columns:
        ls = [c.data, c.valid] + ([c.lengths] if c.lengths is not None
                                  else [])
        out.extend(ls)
        per_col.append(len(ls))
    out.append(batch.sel)
    return out, per_col


def _to_bytes(x):
    """Device bit-reinterpret of one leaf to flat uint8; returns
    (byte_array, f64_pair_flag)."""
    if x.dtype == jnp.bool_:
        return x.astype(jnp.uint8).reshape(-1), False
    if x.dtype == jnp.float64 and jax.default_backend() != "cpu":
        hi = x.astype(jnp.float32)
        lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
        pair = jnp.stack([hi, lo], axis=-1)
        return jax.lax.bitcast_convert_type(pair, jnp.uint8).reshape(-1), \
            True
    return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1), False


def _layout(batch: ColumnarBatch):
    """Static layout (shapes/dtypes only — no device work)."""
    leaves, per_col = _leaves_of(batch)
    slots: List[LeafSlot] = []
    off = 0
    for x in leaves:
        if x.dtype == jnp.bool_:
            nb = int(np.prod(x.shape, dtype=np.int64))
            pair = False
        elif x.dtype == jnp.float64 and jax.default_backend() != "cpu":
            nb = int(np.prod(x.shape, dtype=np.int64)) * 8
            pair = True
        else:
            nb = int(np.prod(x.shape, dtype=np.int64)) * x.dtype.itemsize
            pair = False
        slots.append(LeafSlot(off, nb, tuple(x.shape), str(x.dtype), pair))
        off += nb
    return leaves, per_col, slots, off


def _layout_key(batch: ColumnarBatch) -> tuple:
    leaves, _ = _leaves_of(batch)
    return tuple((str(x.dtype), tuple(x.shape)) for x in leaves)


def pack_batch(batch: ColumnarBatch) -> ContiguousBatch:
    """batch -> one uint8 device buffer (a single compiled concat per
    layout)."""
    leaves, per_col, slots, total = _layout(batch)

    def build():
        def k(ls):
            return jnp.concatenate([_to_bytes(x)[0] for x in ls])
        return k

    fn = cached_kernel(("contig_pack", _layout_key(batch)), build)
    buf = fn(leaves)
    meta = ContiguousMeta(batch.schema, batch.capacity, slots, per_col,
                          total)
    return ContiguousBatch(buf, meta)


def _from_bytes(raw, slot: LeafSlot):
    dt = np.dtype(slot.dtype)
    if dt == np.bool_:
        return raw.reshape(slot.shape).astype(jnp.bool_)
    if slot.f64_pair:
        pair = jax.lax.bitcast_convert_type(
            raw.reshape(slot.shape + (2, 4)), jnp.float32)
        hi = pair[..., 0].astype(jnp.float64)
        lo = pair[..., 1].astype(jnp.float64)
        return hi + lo
    if dt.itemsize == 1:
        return raw.reshape(slot.shape).astype(dt)
    return jax.lax.bitcast_convert_type(
        raw.reshape(slot.shape + (dt.itemsize,)), dt)


def unpack_batch(cb: ContiguousBatch) -> ColumnarBatch:
    """One uint8 device buffer -> batch (single compiled slice kernel)."""
    meta = cb.meta

    def build():
        def k(buf):
            outs = []
            for slot in meta.slots:
                raw = jax.lax.slice(buf, (slot.offset,),
                                    (slot.offset + slot.nbytes,))
                outs.append(_from_bytes(raw, slot))
            return outs
        return k

    key = ("contig_unpack",
           tuple((s.offset, s.nbytes, s.shape, s.dtype, s.f64_pair)
                 for s in meta.slots))
    leaves = cached_kernel(key, build)(cb.buffer)
    cols = []
    i = 0
    for f, n_leaves in zip(meta.schema, meta.leaves_per_col):
        ls = leaves[i:i + n_leaves]
        i += n_leaves
        cols.append(Column(ls[0], ls[1], f.dtype,
                           ls[2] if n_leaves == 3 else None))
    sel = leaves[i]
    return ColumnarBatch(cols, sel, meta.schema)


def contiguous_to_host(batch: ColumnarBatch):
    """D2H as ONE transfer: pack on device, pull the single buffer, slice
    host leaves out as numpy views (zero-copy reinterpret)."""
    cb = pack_batch(batch)
    raw = np.asarray(jax.device_get(cb.buffer))
    leaves = []
    for slot, dt_str in [(s, s.dtype) for s in cb.meta.slots]:
        piece = raw[slot.offset:slot.offset + slot.nbytes]
        if slot.f64_pair:
            pair = piece.view(np.float32).reshape(slot.shape + (2,))
            leaves.append(pair[..., 0].astype(np.float64)
                          + pair[..., 1].astype(np.float64))
        elif dt_str == "bool":
            leaves.append(piece.view(np.uint8).astype(np.bool_)
                          .reshape(slot.shape))
        else:
            leaves.append(piece.view(np.dtype(dt_str)).reshape(slot.shape))
    return leaves, cb.meta
