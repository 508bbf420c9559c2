"""Device-side parquet ENCODE.

The reference encodes parquet on the device and streams host buffers to the
output (GpuParquetFileFormat.scala:192-214 via Table.writeParquetChunked;
ColumnarOutputWriter.scala:62-139).  The TPU-native split:

  device - null-compaction of each column's values into PLAIN page payload
           order (one scatter), string [len][bytes] stream packing (one
           scatter over a 2-D index map), and column statistics (min/max/
           null-count reductions).  One D2H per column chunk — the encoded
           payload — instead of one per full column plus host-side encode.
  host   - the scalar control plane: definition-level RLE runs, page
           headers, optional snappy page compression (pyarrow codec), and
           the thrift-compact footer (the writer twin of the reader's
           `_Thrift` parser in io/parquet_device.py).

Layout written: parquet v1, one row group per file, one DATA_PAGE per
column, all columns OPTIONAL with definition levels, PLAIN encoding.
Readable by pyarrow/Spark; round-trip tests drive both engines over it
(tests/test_parquet_device_write.py).
"""
from __future__ import annotations

import struct
from typing import List, Optional, Tuple

import numpy as np

from ..columnar import Column, ColumnarBatch
from ..types import (BooleanType, ByteType, DataType, DateType, DoubleType,
                     FloatType, IntegerType, LongType, Schema, ShortType,
                     StringType, TimestampType)

MAGIC = b"PAR1"

# thrift compact type nibbles
_CT_BOOL_TRUE, _CT_BOOL_FALSE = 1, 2
_CT_I32, _CT_I64, _CT_BINARY, _CT_LIST, _CT_STRUCT = 5, 6, 8, 9, 12

# parquet physical types
_PT_BOOLEAN, _PT_INT32, _PT_INT64 = 0, 1, 2
_PT_FLOAT, _PT_DOUBLE, _PT_BYTE_ARRAY = 4, 5, 6

_PLAIN, _RLE = 0, 3
_UNCOMPRESSED, _SNAPPY = 0, 1

# (physical type, converted type or None) per framework dtype
_TYPE_MAP = {
    BooleanType: (_PT_BOOLEAN, None),
    ByteType: (_PT_INT32, 15),       # INT_8
    ShortType: (_PT_INT32, 16),      # INT_16
    IntegerType: (_PT_INT32, None),
    LongType: (_PT_INT64, None),
    FloatType: (_PT_FLOAT, None),
    DoubleType: (_PT_DOUBLE, None),
    DateType: (_PT_INT32, 6),        # DATE
    TimestampType: (_PT_INT64, 10),  # TIMESTAMP_MICROS
    StringType: (_PT_BYTE_ARRAY, 0),  # UTF8
}


class _ThriftWriter:
    """Thrift compact-protocol serializer (writer twin of
    io/parquet_device.py `_Thrift`)."""

    def __init__(self):
        self.buf = bytearray()
        self._last_fid = [0]

    # -- primitives --------------------------------------------------------
    def varint(self, v: int):
        while True:
            b = v & 0x7F
            v >>= 7
            if v:
                self.buf.append(b | 0x80)
            else:
                self.buf.append(b)
                return

    def zigzag(self, v: int):
        self.varint((v << 1) ^ (v >> 63))

    # -- struct fields -----------------------------------------------------
    def _field(self, fid: int, ctype: int):
        delta = fid - self._last_fid[-1]
        if 0 < delta <= 15:
            self.buf.append((delta << 4) | ctype)
        else:
            self.buf.append(ctype)
            self.zigzag(fid)
        self._last_fid[-1] = fid

    def f_i32(self, fid: int, v: int):
        self._field(fid, _CT_I32)
        self.zigzag(v)

    def f_i64(self, fid: int, v: int):
        self._field(fid, _CT_I64)
        self.zigzag(v)

    def f_binary(self, fid: int, v: bytes):
        self._field(fid, _CT_BINARY)
        self.varint(len(v))
        self.buf.extend(v)

    def f_list(self, fid: int, elem_ctype: int, n: int):
        self._field(fid, _CT_LIST)
        if n < 15:
            self.buf.append((n << 4) | elem_ctype)
        else:
            self.buf.append(0xF0 | elem_ctype)
            self.varint(n)

    def begin_struct(self, fid: int):
        self._field(fid, _CT_STRUCT)
        self._last_fid.append(0)

    def begin_list_struct(self):
        # struct as a LIST element has no field header
        self._last_fid.append(0)

    def end_struct(self):
        self.buf.append(0)  # STOP
        self._last_fid.pop()


def _rle_def_levels(valid: np.ndarray) -> bytes:
    """Definition levels (0/1, bit width 1) as parquet RLE: 4-byte LE
    length prefix + run-length runs (varint(count << 1) + value byte).
    Run boundaries come from one vectorized diff, so Python work is
    O(runs), not O(rows)."""
    out = bytearray()
    n = valid.size
    v = valid.astype(np.uint8)
    if n:
        bounds = np.flatnonzero(np.diff(v)) + 1
        starts = np.concatenate([[0], bounds])
        ends = np.concatenate([bounds, [n]])
        for s, e in zip(starts.tolist(), ends.tolist()):
            header = (e - s) << 1
            while True:
                b = header & 0x7F
                header >>= 7
                if header:
                    out.append(b | 0x80)
                else:
                    out.append(b)
                    break
            out.append(int(v[s]))
    return struct.pack("<I", len(out)) + bytes(out)


# --------------------------------------------------------------------------
# device payload kernels
# --------------------------------------------------------------------------

def _compact_values(col: Column, live) -> Tuple[np.ndarray, int, dict]:
    """Device: scatter the column's live non-null values into PLAIN payload
    order; returns (host payload array, non-null count, device stats)."""
    import jax.numpy as jnp

    from ..utils.kernel_cache import cached_kernel

    dtype = col.dtype
    cap = int(col.valid.shape[0])

    if dtype.is_string:
        width = int(col.data.shape[1])
        key = ("pq_encode_str", cap, width)

        def make():
            def k(data, lengths, ok):
                slot = 4 + width
                # byte offset of each value: 4+len of preceding non-nulls.
                # int64 accumulation: an int32 cumsum would silently wrap
                # (and corrupt the page) once total payload nears 2 GiB
                sizes = jnp.where(ok, 4 + lengths.astype(jnp.int64),
                                  jnp.int64(0))
                ends = jnp.cumsum(sizes)
                starts = ends - sizes
                total = ends[-1] if cap else jnp.int64(0)
                out = jnp.zeros(cap * slot, dtype=jnp.uint8)
                # little-endian 4-byte length prefix
                pos4 = jnp.arange(4, dtype=jnp.int32)[None, :]
                len_bytes = (lengths[:, None] >>
                             (pos4 * 8)).astype(jnp.uint8)
                idx4 = jnp.where(ok[:, None], starts[:, None] + pos4,
                                 cap * slot)
                out = out.at[idx4].set(len_bytes, mode="drop")
                posw = jnp.arange(width, dtype=jnp.int32)[None, :]
                in_str = posw < lengths[:, None]
                idxw = jnp.where(ok[:, None] & in_str,
                                 starts[:, None] + 4 + posw, cap * slot)
                out = out.at[idxw].set(data.astype(jnp.uint8), mode="drop")
                return out, total, jnp.sum(ok.astype(jnp.int64))
            return k

        fn = cached_kernel(key, make)
        ok = col.valid & live
        out, total, nn = fn(col.data, col.lengths.astype(jnp.int32), ok)
        payload = np.asarray(out)[: int(total)]
        return payload, int(nn), {}

    jnp_src = col.data
    if dtype is BooleanType:
        key = ("pq_encode_bool", cap)

        def make():
            def k(data, ok):
                pos = jnp.where(ok, jnp.cumsum(ok.astype(jnp.int32)) - 1,
                                cap)
                out = jnp.zeros(cap, dtype=jnp.uint8)
                out = out.at[pos].set(data.astype(jnp.uint8), mode="drop")
                return out, jnp.sum(ok.astype(jnp.int64))
            return k

        fn = cached_kernel(key, make)
        ok = col.valid & live
        out, nn = fn(jnp_src, ok)
        nn = int(nn)
        bits = np.packbits(np.asarray(out)[:nn], bitorder="little")
        return bits, nn, {}

    key = ("pq_encode_num", dtype.name, cap)

    def make():
        def k(data, ok):
            pos = jnp.where(ok, jnp.cumsum(ok.astype(jnp.int32)) - 1, cap)
            out = jnp.zeros(cap, dtype=data.dtype)
            out = out.at[pos].set(data, mode="drop")
            if jnp.issubdtype(data.dtype, jnp.floating):
                hi = jnp.array(jnp.finfo(data.dtype).max, data.dtype)
                lo = jnp.array(jnp.finfo(data.dtype).min, data.dtype)
            else:
                hi = jnp.array(jnp.iinfo(data.dtype).max, data.dtype)
                lo = jnp.array(jnp.iinfo(data.dtype).min, data.dtype)
            mn = jnp.min(jnp.where(ok, data, hi))
            mx = jnp.max(jnp.where(ok, data, lo))
            return out, jnp.sum(ok.astype(jnp.int64)), mn, mx
        return k

    fn = cached_kernel(key, make)
    ok = col.valid & live
    out, nn, mn, mx = fn(jnp_src, ok)
    nn = int(nn)
    np_dtype = {"byte": np.int32, "short": np.int32, "int": np.int32,
                "date": np.int32, "long": np.int64,
                "timestamp": np.int64, "float": np.float32,
                "double": np.float64}[dtype.name]
    payload = np.asarray(out)[:nn].astype(np_dtype, copy=False)
    stats = {}
    if nn:
        mn_v, mx_v = np.asarray(mn), np.asarray(mx)
        if not (dtype.is_floating and (np.isnan(mn_v) or np.isnan(mx_v))):
            stats = {"min": mn_v.astype(np_dtype).tobytes(),
                     "max": mx_v.astype(np_dtype).tobytes()}
    return payload.view(np.uint8), nn, stats


# --------------------------------------------------------------------------
# file assembly
# --------------------------------------------------------------------------

def _page(valid: np.ndarray, payload: bytes, num_values: int,
          codec: int) -> Tuple[bytes, int, int]:
    """One v1 data page: header + def levels + payload; returns
    (page bytes, uncompressed size, compressed size)."""
    body = _rle_def_levels(valid) + payload
    un = len(body)
    if codec == _SNAPPY:
        import pyarrow as pa
        body = bytes(memoryview(pa.Codec("snappy").compress(body)))
    comp = len(body)
    t = _ThriftWriter()
    t.f_i32(1, 0)                 # type = DATA_PAGE
    t.f_i32(2, un)                # uncompressed_page_size
    t.f_i32(3, comp)              # compressed_page_size
    t.begin_struct(5)             # data_page_header
    t.f_i32(1, num_values)
    t.f_i32(2, _PLAIN)
    t.f_i32(3, _RLE)              # definition levels
    t.f_i32(4, _RLE)              # repetition levels
    t.end_struct()
    t.buf.append(0)               # PageHeader STOP
    return bytes(t.buf) + body, un, comp


def encode_parquet_file(batch: ColumnarBatch, compression: str = "snappy"
                        ) -> bytes:
    """Encode one device batch as a complete single-row-group parquet
    file; device kernels produce every page payload."""
    import jax.numpy as jnp

    schema = batch.schema
    live_np = np.asarray(batch.sel)
    order = np.flatnonzero(live_np)
    num_rows = int(order.size)
    codec = _SNAPPY if compression == "snappy" else _UNCOMPRESSED

    out = bytearray(MAGIC)
    chunks = []  # (name, phys, conv, num_values, un, comp, offset,
                 #  stats, null_count)
    for f, col in zip(schema, batch.columns):
        if f.dtype not in _TYPE_MAP:
            raise NotImplementedError(f"parquet encode {f.dtype.name}")
        payload, nn, stats = _compact_values(col, batch.sel)
        valid_live = np.asarray(col.valid)[live_np]
        page, un, comp = _page(valid_live, bytes(payload), num_rows, codec)
        hdr = len(page) - comp
        offset = len(out)
        out.extend(page)
        phys, conv = _TYPE_MAP[f.dtype]
        chunks.append((f.name, phys, conv, num_rows, un + hdr, comp + hdr,
                       offset, stats, num_rows - nn))

    meta = _ThriftWriter()
    meta.f_i32(1, 1)  # version
    meta.f_list(2, _CT_STRUCT, len(schema) + 1)  # schema elements
    meta.begin_list_struct()                     # root
    meta.f_binary(4, b"schema")
    meta.f_i32(5, len(schema))
    meta.end_struct()
    for f in schema:
        phys, conv = _TYPE_MAP[f.dtype]
        meta.begin_list_struct()
        meta.f_i32(1, phys)
        meta.f_i32(3, 1)  # OPTIONAL
        meta.f_binary(4, f.name.encode())
        if conv is not None:
            meta.f_i32(6, conv)
        meta.end_struct()
    meta.f_i64(3, num_rows)
    meta.f_list(4, _CT_STRUCT, 1)  # one row group
    meta.begin_list_struct()
    meta.f_list(1, _CT_STRUCT, len(chunks))
    total_bytes = 0
    for (name, phys, conv, nv, un, comp, offset, stats, nulls) in chunks:
        total_bytes += un
        meta.begin_list_struct()           # ColumnChunk
        meta.f_i64(2, offset)              # file_offset
        meta.begin_struct(3)               # ColumnMetaData
        meta.f_i32(1, phys)
        meta.f_list(2, _CT_I32, 2)
        meta.zigzag(_PLAIN)
        meta.zigzag(_RLE)
        meta.f_list(3, _CT_BINARY, 1)
        meta.varint(len(name.encode()))
        meta.buf.extend(name.encode())
        meta.f_i32(4, codec)
        meta.f_i64(5, nv)
        meta.f_i64(6, un)
        meta.f_i64(7, comp)
        meta.f_i64(9, offset)              # data_page_offset
        if stats:
            meta.begin_struct(12)          # Statistics
            meta.f_binary(1, stats["max"])  # max (legacy)
            meta.f_binary(2, stats["min"])  # min (legacy)
            meta.f_i64(3, nulls)
            meta.f_binary(5, stats["max"])  # max_value
            meta.f_binary(6, stats["min"])  # min_value
            meta.end_struct()
        meta.end_struct()                  # ColumnMetaData
        meta.end_struct()                  # ColumnChunk
    meta.f_i64(2, total_bytes)
    meta.f_i64(3, num_rows)
    meta.end_struct()                      # RowGroup
    meta.f_binary(6, b"spark-rapids-tpu device encoder")
    # column_orders: TypeDefinedOrder per column so readers trust
    # min_value/max_value (parquet.thrift ColumnOrder union, field 1)
    meta.f_list(7, _CT_STRUCT, len(schema))
    for _ in schema:
        meta.begin_list_struct()           # ColumnOrder union
        meta.begin_struct(1)               # TYPE_ORDER: TypeDefinedOrder{}
        meta.end_struct()
        meta.end_struct()
    meta.buf.append(0)                     # FileMetaData STOP

    out.extend(meta.buf)
    out.extend(struct.pack("<I", len(meta.buf)))
    out.extend(MAGIC)
    return bytes(out)
