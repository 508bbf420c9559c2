"""Device-side ORC ENCODE.

Reference behavior: the reference encodes ORC on the device and streams
host buffers to the output (GpuOrcFileFormat.scala:1-164 via
Table.writeORCChunked; ColumnarOutputWriter.scala:62-139).  The TPU-native
split mirrors the parquet encoder (io/parquet_device_write.py):

  device - null-compaction of every column's live non-null values into
           stream payload order (cumsum-position scatter), contiguous
           string byte packing + lengths, and min/max/count statistics
           reductions.  The compacted payload is the only D2H transfer.
  host   - the scalar control plane: RLEv1 varint runs for integer
           streams, byte-RLE for PRESENT/boolean bitmaps, and the
           protobuf stripe footer / metadata / footer / postscript — the
           writer twin of io/orc_device.py's `_Proto` reader.

Layout written: one stripe, uncompressed (CompressionKind NONE), version
[0,11] with DIRECT (RLEv1) integer encodings — the broadly readable
subset (pyarrow/Spark/Hive read it).  File-level AND stripe-level
statistics are emitted, so this framework's own stripe-statistics
pruning (io/scan.py _orc_stats_can_match) works on its own output.

Scope: BOOLEAN/BYTE/SHORT/INT/LONG/FLOAT/DOUBLE/DATE/STRING columns;
timestamps (dual-stream 2015-epoch encoding) fall back to the host arrow
writer, like the reader's column-granular fallback in reverse.
"""
from __future__ import annotations

import struct
from typing import List, Tuple

import numpy as np

from ..columnar import Column, ColumnarBatch
from ..types import (BooleanType, ByteType, DataType, DateType, DoubleType,
                     FloatType, IntegerType, LongType, ShortType,
                     StringType)

MAGIC = b"ORC"

# orc_proto.Type.Kind
_ORC_KIND = {
    BooleanType: 0, ByteType: 1, ShortType: 2, IntegerType: 3,
    LongType: 4, FloatType: 5, DoubleType: 6, StringType: 7,
    DateType: 15,
}
_STRUCT_KIND = 12

# orc_proto.Stream.Kind
_K_PRESENT, _K_DATA, _K_LENGTH = 0, 1, 2

ORC_ENCODABLE = frozenset(_ORC_KIND)


# --------------------------------------------------------------------------
# protobuf writer (the `_Proto` reader's twin)
# --------------------------------------------------------------------------

class _ProtoWriter:
    def __init__(self):
        self.buf = bytearray()

    def varint(self, v: int) -> "_ProtoWriter":
        while True:
            b = v & 0x7F
            v >>= 7
            if v:
                self.buf.append(b | 0x80)
            else:
                self.buf.append(b)
                return self

    def f_varint(self, fid: int, v: int) -> "_ProtoWriter":
        self.varint((fid << 3) | 0)
        return self.varint(v)

    def f_zigzag64(self, fid: int, v: int) -> "_ProtoWriter":
        return self.f_varint(fid, (v << 1) ^ (v >> 63) if v < 0
                             else v << 1)

    def f_double(self, fid: int, v: float) -> "_ProtoWriter":
        self.varint((fid << 3) | 1)
        self.buf.extend(struct.pack("<d", v))
        return self

    def f_bytes(self, fid: int, b: bytes) -> "_ProtoWriter":
        self.varint((fid << 3) | 2)
        self.varint(len(b))
        self.buf.extend(b)
        return self

    def f_message(self, fid: int, sub: "_ProtoWriter") -> "_ProtoWriter":
        return self.f_bytes(fid, bytes(sub.buf))


# --------------------------------------------------------------------------
# host run-length encoders (scalar control plane)
# --------------------------------------------------------------------------

def _byte_rle_literals(data: bytes) -> bytes:
    """Byte-RLE with literal runs only (control byte 256-n for n in
    1..128) — always valid, and PRESENT/boolean streams are tiny."""
    out = bytearray()
    pos = 0
    while pos < len(data):
        n = min(128, len(data) - pos)
        out.append(256 - n)
        out.extend(data[pos:pos + n])
        pos += n
    return bytes(out)


def _varint_bytes(vals: np.ndarray, signed: bool) -> bytearray:
    """Base-128 varints (zigzag when signed) for one literal run."""
    out = bytearray()
    if signed:
        vals = (vals.astype(np.int64) << 1) ^ (vals.astype(np.int64) >> 63)
    for v in vals.tolist():
        v &= 0xFFFFFFFFFFFFFFFF
        while True:
            b = v & 0x7F
            v >>= 7
            if v:
                out.append(b | 0x80)
            else:
                out.append(b)
                break
    return out


def _int_rle_v1_literals(vals: np.ndarray, signed: bool = True) -> bytes:
    """RLEv1 with literal runs only (control byte 256-n, then n varints)."""
    out = bytearray()
    pos = 0
    n_all = len(vals)
    while pos < n_all:
        n = min(128, n_all - pos)
        out.append(256 - n)
        out.extend(_varint_bytes(vals[pos:pos + n], signed))
        pos += n
    return bytes(out)


# --------------------------------------------------------------------------
# device payload kernels
# --------------------------------------------------------------------------

def _compact_strings(col: Column, live) -> Tuple[np.ndarray, np.ndarray]:
    """Device: pack live non-null strings' bytes contiguously (no length
    prefixes — ORC carries lengths in a separate RLE stream) and return
    (payload bytes, lengths int64[nn])."""
    import jax.numpy as jnp

    from ..utils.kernel_cache import cached_kernel

    cap = int(col.valid.shape[0])
    width = int(col.data.shape[1])
    key = ("orc_encode_str", cap, width)

    def make():
        def k(data, lengths, ok):
            sizes = jnp.where(ok, lengths.astype(jnp.int64), jnp.int64(0))
            ends = jnp.cumsum(sizes)
            starts = ends - sizes
            total = ends[-1] if cap else jnp.int64(0)
            out = jnp.zeros(cap * width, dtype=jnp.uint8)
            posw = jnp.arange(width, dtype=jnp.int64)[None, :]
            in_str = posw < lengths[:, None]
            idx = jnp.where(ok[:, None] & in_str, starts[:, None] + posw,
                            cap * width)
            out = out.at[idx].set(data.astype(jnp.uint8), mode="drop")
            # compacted lengths in value order
            pos = jnp.where(ok, jnp.cumsum(ok.astype(jnp.int32)) - 1, cap)
            lens_out = jnp.zeros(cap, dtype=jnp.int64)
            lens_out = lens_out.at[pos].set(sizes, mode="drop")
            return out, lens_out, total, jnp.sum(ok.astype(jnp.int64))
        return k

    fn = cached_kernel(key, make)
    ok = col.valid & live
    out, lens_out, total, nn = fn(col.data,
                                  col.lengths.astype(np.int32), ok)
    nn = int(nn)
    return np.asarray(out)[: int(total)], np.asarray(lens_out)[:nn]


def _compact_bools(col: Column, live) -> Tuple[np.ndarray, int]:
    """Device: compacted live non-null booleans as bytes (bit packing is
    MSB-first per the ORC spec, done host-side on the 1-bit stream)."""
    import jax.numpy as jnp

    from ..utils.kernel_cache import cached_kernel

    cap = int(col.valid.shape[0])
    key = ("orc_encode_bool", cap)

    def make():
        def k(data, ok):
            pos = jnp.where(ok, jnp.cumsum(ok.astype(jnp.int32)) - 1, cap)
            out = jnp.zeros(cap, dtype=jnp.uint8)
            out = out.at[pos].set(data.astype(jnp.uint8), mode="drop")
            return out, jnp.sum(ok.astype(jnp.int64))
        return k

    fn = cached_kernel(key, make)
    ok = col.valid & live
    out, nn = fn(col.data, ok)
    nn = int(nn)
    return np.asarray(out)[:nn], nn


# --------------------------------------------------------------------------
# column statistics
# --------------------------------------------------------------------------

def _column_statistics(dtype: DataType, nn: int, has_null: bool,
                       stats: dict) -> _ProtoWriter:
    cs = _ProtoWriter()
    cs.f_varint(1, nn)  # numberOfValues
    if stats and nn:
        if dtype.is_integral or dtype is BooleanType:
            sub = _ProtoWriter()
            sub.f_zigzag64(1, int(stats["min"]))
            sub.f_zigzag64(2, int(stats["max"]))
            cs.f_message(2, sub)
        elif dtype.is_floating:
            sub = _ProtoWriter()
            sub.f_double(1, float(stats["min"]))
            sub.f_double(2, float(stats["max"]))
            cs.f_message(3, sub)
        elif dtype is StringType:
            sub = _ProtoWriter()
            sub.f_bytes(1, stats["min"])
            sub.f_bytes(2, stats["max"])
            cs.f_message(4, sub)
        elif dtype is DateType:
            sub = _ProtoWriter()
            v_min, v_max = int(stats["min"]), int(stats["max"])
            sub.f_varint(1, ((v_min << 1) ^ (v_min >> 63))
                         & 0xFFFFFFFFFFFFFFFF)
            sub.f_varint(2, ((v_max << 1) ^ (v_max >> 63))
                         & 0xFFFFFFFFFFFFFFFF)
            cs.f_message(7, sub)
    cs.f_varint(10, 1 if has_null else 0)  # hasNull
    return cs


# --------------------------------------------------------------------------
# file assembly
# --------------------------------------------------------------------------

def encode_orc_file(batch: ColumnarBatch) -> bytes:
    """Encode one device batch as a complete single-stripe uncompressed
    ORC file; device kernels produce every stream payload."""
    from .parquet_device_write import _compact_values

    schema = batch.schema
    for f in schema:
        if f.dtype not in _ORC_KIND:
            raise NotImplementedError(f"orc encode {f.dtype.name}")
    live_np = np.asarray(batch.sel)
    num_rows = int(live_np.sum())

    out = bytearray(MAGIC)
    stripe_start = len(out)
    streams: List[Tuple[int, int, int]] = []  # (kind, column_id, length)
    col_stats: List[_ProtoWriter] = []
    # root struct statistics (column id 0)
    root = _ProtoWriter()
    root.f_varint(1, num_rows)
    root.f_varint(10, 0)
    col_stats.append(root)

    def emit(kind: int, cid: int, data: bytes) -> None:
        streams.append((kind, cid, len(data)))
        out.extend(data)

    for ci, (f, col) in enumerate(zip(schema, batch.columns)):
        cid = ci + 1  # type/column ids offset past the root struct
        valid_live = np.asarray(col.valid)[live_np]
        nn = int(valid_live.sum())
        has_null = nn < num_rows
        if has_null:
            present = _byte_rle_literals(
                np.packbits(valid_live, bitorder="big").tobytes())
            emit(_K_PRESENT, cid, present)
        stats: dict = {}
        if f.dtype is StringType:
            payload, lens = _compact_strings(col, batch.sel)
            emit(_K_DATA, cid, payload.tobytes())
            emit(_K_LENGTH, cid, _int_rle_v1_literals(lens, signed=False))
            if nn:
                # lexicographic min/max over the (host) compacted payload:
                # a handful of comparisons on already-transferred bytes
                offs = np.zeros(nn + 1, dtype=np.int64)
                np.cumsum(lens, out=offs[1:])
                vals = [payload[offs[i]:offs[i + 1]].tobytes()
                        for i in range(nn)]
                stats = {"min": min(vals), "max": max(vals)}
        elif f.dtype is BooleanType:
            vals, nn2 = _compact_bools(col, batch.sel)
            emit(_K_DATA, cid, _byte_rle_literals(
                np.packbits(vals.astype(bool), bitorder="big").tobytes()))
            if nn:
                stats = {"min": int(vals.min()), "max": int(vals.max())}
        else:
            payload, nn2, pstats = _compact_values(col, batch.sel)
            np_dtype = {"byte": np.int32, "short": np.int32,
                        "int": np.int32, "date": np.int32,
                        "long": np.int64, "float": np.float32,
                        "double": np.float64}[f.dtype.name]
            vals = payload.view(np_dtype)
            if f.dtype.is_floating:
                emit(_K_DATA, cid, vals.tobytes())  # raw IEEE LE payload
            else:
                emit(_K_DATA, cid,
                     _int_rle_v1_literals(vals.astype(np.int64)))
            if pstats:
                stats = {"min": np.frombuffer(pstats["min"], np_dtype)[0],
                         "max": np.frombuffer(pstats["max"], np_dtype)[0]}
        col_stats.append(_column_statistics(f.dtype, nn, has_null, stats))

    data_len = len(out) - stripe_start

    # stripe footer
    sf = _ProtoWriter()
    for kind, cid, length in streams:
        s = _ProtoWriter()
        s.f_varint(1, kind)
        s.f_varint(2, cid)
        s.f_varint(3, length)
        sf.f_message(1, s)
    for _ in range(len(schema) + 1):  # root + columns, all DIRECT
        enc = _ProtoWriter()
        enc.f_varint(1, 0)  # DIRECT (RLEv1 era)
        sf.f_message(2, enc)
    out.extend(sf.buf)
    stripe_footer_len = len(sf.buf)

    # metadata section: one StripeStatistics (this file has one stripe) —
    # feeds the reader's stripe-statistics pruning
    meta = _ProtoWriter()
    ss = _ProtoWriter()
    for cs in col_stats:
        ss.f_message(1, cs)
    meta.f_message(1, ss)
    metadata_off = len(out)
    out.extend(meta.buf)

    # footer
    ft = _ProtoWriter()
    ft.f_varint(1, len(MAGIC))          # headerLength
    ft.f_varint(2, metadata_off)        # contentLength
    si = _ProtoWriter()
    si.f_varint(1, stripe_start)        # offset
    si.f_varint(2, 0)                   # indexLength
    si.f_varint(3, data_len)            # dataLength
    si.f_varint(4, stripe_footer_len)   # footerLength
    si.f_varint(5, num_rows)            # numberOfRows
    ft.f_message(3, si)
    root_t = _ProtoWriter()
    root_t.f_varint(1, _STRUCT_KIND)
    for ci in range(len(schema)):
        root_t.f_varint(2, ci + 1)      # subtypes
    for f in schema:
        root_t.f_bytes(3, f.name.encode())
    ft.f_message(4, root_t)
    for f in schema:
        t = _ProtoWriter()
        t.f_varint(1, _ORC_KIND[f.dtype])
        ft.f_message(4, t)
    ft.f_varint(6, num_rows)            # numberOfRows
    for cs in col_stats:                # file statistics
        ft.f_message(7, cs)
    ft.f_varint(8, 0)                   # rowIndexStride (no indexes)
    footer_off = len(out)
    out.extend(ft.buf)

    # postscript
    ps = _ProtoWriter()
    ps.f_varint(1, len(out) - footer_off)      # footerLength
    ps.f_varint(2, 0)                          # CompressionKind NONE
    ps.f_varint(3, 0)                          # compressionBlockSize
    ps.f_varint(4, 0)                          # version [0, 11]
    ps.f_varint(4, 11)
    ps.f_varint(5, footer_off - metadata_off)  # metadataLength
    ps.f_varint(6, 1)                          # writerVersion
    ps.f_bytes(8000, MAGIC)
    assert len(ps.buf) < 256
    out.extend(ps.buf)
    out.append(len(ps.buf))
    return bytes(out)
