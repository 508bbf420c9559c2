"""Device column vectors.

The TPU analogue of the reference's GpuColumnVector
(reference: sql-plugin/src/main/java/.../GpuColumnVector.java) — but instead of
wrapping a cuDF buffer, a column IS a small pytree of jnp arrays so whole
operator pipelines can be traced into one XLA program:

  * data  : jnp array [capacity]           (numeric/bool/date/timestamp)
            or uint8 [capacity, max_len]   (strings, padded UTF-8 bytes)
  * valid : bool [capacity]                (null bitmap; True = non-null)
  * lengths : int32 [capacity]             (strings only)

`capacity` is a STATIC bucketed size (see batch.py); the actual row count of a
batch is tracked by the batch's row mask.  Null slots hold zeros so reductions
can mask without NaN poisoning.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..types import (BooleanType, DataType, DoubleType, StringType)


@jax.tree_util.register_pytree_node_class
class Column:
    """One device column. Registered as a pytree: `data`/`valid`/`lengths`
    are traced leaves, `dtype` is static."""

    __slots__ = ("data", "valid", "lengths", "dtype")

    def __init__(self, data, valid, dtype: DataType, lengths=None):
        self.data = data
        self.valid = valid
        self.dtype = dtype
        self.lengths = lengths

    def tree_flatten(self):
        if self.dtype.is_string:
            return (self.data, self.valid, self.lengths), self.dtype
        return (self.data, self.valid), self.dtype

    @classmethod
    def tree_unflatten(cls, dtype, children):
        if dtype.is_string:
            data, valid, lengths = children
            return cls(data, valid, dtype, lengths)
        data, valid = children
        return cls(data, valid, dtype)

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    @property
    def max_len(self) -> int:
        assert self.dtype.is_string
        return self.data.shape[1]

    # ---- constructors ------------------------------------------------------

    @staticmethod
    def from_numpy(values: np.ndarray, valid: Optional[np.ndarray],
                   dtype: DataType, capacity: Optional[int] = None) -> "Column":
        """Build a (host-side) column from numpy, padding to `capacity`."""
        n = len(values)
        cap = capacity if capacity is not None else n
        assert cap >= n, (cap, n)
        if valid is None:
            valid = np.ones(n, dtype=np.bool_)
        vfull = np.zeros(cap, dtype=np.bool_)
        vfull[:n] = valid
        if dtype.is_string:
            raise ValueError("use Column.from_strings for string data")
        dfull = np.zeros(cap, dtype=dtype.np_dtype)
        arr = np.asarray(values, dtype=dtype.np_dtype)
        # zero out nulls so masked reductions are safe
        arr = np.where(valid, arr, np.zeros((), dtype=dtype.np_dtype))
        dfull[:n] = arr
        return Column(jnp.asarray(dfull), jnp.asarray(vfull), dtype)

    @staticmethod
    def from_strings(values, capacity: Optional[int] = None,
                     max_len: Optional[int] = None) -> "Column":
        """values: sequence of str | None."""
        n = len(values)
        cap = capacity if capacity is not None else n
        enc = [v.encode("utf-8") if v is not None else b"" for v in values]
        need = max((len(b) for b in enc), default=0)
        ml = max_len if max_len is not None else bucket_strlen(need)
        assert ml >= need, (ml, need)
        data = np.zeros((cap, ml), dtype=np.uint8)
        lengths = np.zeros(cap, dtype=np.int32)
        valid = np.zeros(cap, dtype=np.bool_)
        for i, (v, b) in enumerate(zip(values, enc)):
            if v is None:
                continue
            valid[i] = True
            lengths[i] = len(b)
            if b:
                data[i, :len(b)] = np.frombuffer(b, dtype=np.uint8)
        return Column(jnp.asarray(data), jnp.asarray(valid), StringType,
                      jnp.asarray(lengths))

    @staticmethod
    def from_arrow_strings(arr, capacity: Optional[int] = None) -> "Column":
        """A pyarrow `string` or `large_string` Array (one chunk, any
        `offset`) as the column `from_strings(arr.to_pylist())` builds,
        byte for byte, read from the array's own buffers (validity bitmap,
        offsets, data) with numpy: no Python loop over rows."""
        import pyarrow as pa
        n = len(arr)
        if n == 0:
            return Column.from_strings([], capacity=capacity)
        cap = capacity if capacity is not None else n
        bitmap, offsets, data = arr.buffers()[:3]
        wide = pa.types.is_large_string(arr.type)
        off_dtype = np.dtype(np.int64 if wide else np.int32)
        offs = np.frombuffer(offsets, off_dtype, n + 1,
                             arr.offset * off_dtype.itemsize).astype(np.int64)
        if bitmap is None or arr.null_count == 0:
            valid = np.ones(n, dtype=np.bool_)
        else:
            bits = np.unpackbits(np.frombuffer(bitmap, np.uint8),
                                 bitorder="little")
            valid = bits[arr.offset:arr.offset + n].astype(np.bool_)
        # a null holds no bytes, whatever its slot in the offsets spans
        lens = np.where(valid, np.diff(offs), 0)
        ml = bucket_strlen(int(lens.max()))
        matrix = np.zeros((cap, ml), dtype=np.uint8)
        total = int(lens.sum())
        src = np.frombuffer(data, np.uint8) if total else None
        width = int(lens[0])
        if total and valid.all() and (lens == width).all():
            # one width, no nulls (codes, flags): the bytes lie row after row
            matrix[:n, :width] = src[offs[0]:offs[0] + total].reshape(n, width)
        elif total:
            # every byte's row and its place in the row, then one gather
            rows = np.repeat(np.arange(n), lens)
            pos = np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens)
            matrix[rows, pos] = src[np.repeat(offs[:-1], lens) + pos]
        lengths = np.zeros(cap, dtype=np.int32)
        lengths[:n] = lens
        vfull = np.zeros(cap, dtype=np.bool_)
        vfull[:n] = valid
        return Column(jnp.asarray(matrix), jnp.asarray(vfull), StringType,
                      jnp.asarray(lengths))

    @staticmethod
    def all_null(dtype: DataType, capacity: int, max_len: int = 8) -> "Column":
        valid = jnp.zeros(capacity, dtype=jnp.bool_)
        if dtype.is_string:
            return Column(jnp.zeros((capacity, max_len), dtype=jnp.uint8),
                          valid, dtype, jnp.zeros(capacity, dtype=jnp.int32))
        return Column(jnp.zeros(capacity, dtype=dtype.jnp_dtype), valid, dtype)

    # ---- host materialization ---------------------------------------------

    def _host_rows(self, rows, n=None):
        """D2H the column, restricted to live rows.

        `rows` is an int n (prefix-dense: take [:n]), an np.ndarray of row
        indices (sparse selection), or a DEVICE index array (bucket-padded
        int32, see ColumnarBatch._live_rows): then the gather runs on
        device and only the compacted rows are materialized."""
        if not isinstance(rows, (int, np.ndarray)):
            import jax.numpy as jnp

            def pick(buf):
                return np.asarray(jnp.take(buf, rows, axis=0))[:n]
        else:
            def pick(buf):
                a = np.asarray(buf)
                return a[:rows] if isinstance(rows, int) else a[rows]
        valid = pick(self.valid)
        data = pick(self.data)
        lens = pick(self.lengths) if self.dtype.is_string else None
        return data, valid, lens

    def to_pylist(self, rows, n=None):
        """Materialize live rows as Python values (None=null).

        `rows`: int prefix length or index array (see _host_rows).
        Vectorized: one D2H per buffer, C-speed ndarray.tolist(), and a None
        splice only when nulls exist (no per-row .item() calls)."""
        data, valid, lens = self._host_rows(rows, n)
        n = len(valid)
        all_valid = bool(valid.all()) if n else True
        if self.dtype.is_string:
            lens = np.where(valid, lens, 0)
            ml = data.shape[1] if data.ndim == 2 else 0
            keep = np.arange(ml, dtype=np.int32)[None, :] < lens[:, None]
            flat = data[keep].tobytes()
            ends = np.zeros(n + 1, dtype=np.int64)
            np.cumsum(lens, out=ends[1:])
            out = [flat[ends[i]:ends[i + 1]].decode("utf-8", "replace")
                   for i in range(n)]
        else:
            out = data.tolist()
        if all_valid:
            return out
        return [v if ok else None for v, ok in zip(out, valid)]

    def to_arrow(self, rows, arrow_type=None, n=None):
        """Materialize live rows as a pyarrow Array.

        `rows`: int prefix length or index array (see _host_rows).
        Zero-copy-ish: numerics go numpy -> pa.array with a null mask;
        strings are rebuilt as a varbinary (offsets + flattened bytes)
        Arrow buffer triple — no per-row Python objects (reference contrast:
        GpuColumnarToRowExec copies D2H then iterates rows; here collect()
        and the writers consume whole Arrow columns)."""
        import pyarrow as pa
        from ..types import to_arrow as _to_arrow_type
        at = arrow_type if arrow_type is not None else _to_arrow_type(self.dtype)
        data, valid, lens = self._host_rows(rows, n)
        n = len(valid)
        if n == 0:
            return pa.nulls(0, type=at)
        valid = np.ascontiguousarray(valid)
        all_valid = bool(valid.all())
        if self.dtype.is_string:
            lens = np.where(valid, lens, 0).astype(np.int64)
            ml = data.shape[1] if data.ndim == 2 else 0
            keep = np.arange(ml, dtype=np.int32)[None, :] < lens[:, None]
            flat = np.ascontiguousarray(data[keep])
            offsets = np.zeros(n + 1, dtype=np.int64)
            np.cumsum(lens, out=offsets[1:])  # int64: no silent wrap at 2GiB
            validity = None if all_valid else pa.array(valid).buffers()[1]
            if offsets[-1] <= np.iinfo(np.int32).max:
                return pa.Array.from_buffers(
                    pa.utf8(), n,
                    [validity,
                     pa.py_buffer(offsets.astype(np.int32).tobytes()),
                     pa.py_buffer(flat.tobytes())])
            # >2GiB of string payload in one column: 64-bit offsets
            return pa.Array.from_buffers(
                pa.large_utf8(), n,
                [validity, pa.py_buffer(offsets.tobytes()),
                 pa.py_buffer(flat.tobytes())])
        vals = np.ascontiguousarray(data)
        mask = None if all_valid else ~valid
        return pa.array(vals, type=at, mask=mask)

    # ---- structural ops (all static-shape, jit-safe) -----------------------

    def take(self, indices) -> "Column":
        """Gather rows; indices out of range produce garbage rows the caller
        must mask."""
        if self.dtype.is_string:
            return Column(jnp.take(self.data, indices, axis=0,
                                   mode="clip"),
                          jnp.take(self.valid, indices, mode="clip"),
                          self.dtype,
                          jnp.take(self.lengths, indices, mode="clip"))
        return Column(jnp.take(self.data, indices, mode="clip"),
                      jnp.take(self.valid, indices, mode="clip"),
                      self.dtype)

    def with_valid(self, valid) -> "Column":
        return Column(self.data, valid, self.dtype, self.lengths)

    def mask_invalid(self) -> "Column":
        """Zero data in null slots (keeps reductions clean after ops that may
        have written garbage there)."""
        if self.dtype.is_string:
            lens = jnp.where(self.valid, self.lengths, 0)
            data = jnp.where(self.valid[:, None], self.data, 0)
            return Column(data, self.valid, self.dtype, lens)
        zero = jnp.zeros((), dtype=self.data.dtype)
        return Column(jnp.where(self.valid, self.data, zero), self.valid,
                      self.dtype)

    def pad_strings_to(self, max_len: int) -> "Column":
        assert self.dtype.is_string
        cur = self.max_len
        if cur == max_len:
            return self
        if cur < max_len:
            pad = jnp.zeros((self.capacity, max_len - cur), dtype=jnp.uint8)
            return Column(jnp.concatenate([self.data, pad], axis=1),
                          self.valid, self.dtype, self.lengths)
        raise ValueError(f"cannot shrink string column {cur} -> {max_len}")

    def __repr__(self):  # pragma: no cover
        return f"Column({self.dtype.name}, cap={self.capacity})"


def bucket_strlen(n: int, minimum: int = 8) -> int:
    """Round a string max-length up to a power-of-two bucket (static shapes)."""
    b = minimum
    while b < n:
        b <<= 1
    return b
