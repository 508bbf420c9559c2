"""Device-side Parquet decode for flat numeric/bool columns.

Reference behavior: the signature move of the reference reader is host
footer clipping + DEVICE page decode (GpuParquetScan.scala:316-345,536-569 —
the clipped buffer goes to `Table.readParquet` on the GPU).  The TPU-first
split keeps the same boundary but places it where this hardware wants it:

  host control plane (scalar, tiny):
    * thrift-compact PageHeader parsing (pure python, ~bytes per page)
    * RLE/bit-packed run headers (a handful of varints per page)
    * definition levels -> validity bitmap (numpy bit ops on 1 bit/row)
    * decompression via pyarrow's codec (no python-snappy in the image)
  device data plane (vector, the actual megabytes):
    * PLAIN fixed-width value decode (byte matrix -> typed lanes, VPU
      shifts; float64 reconstructed from bit fields on TPU where u64->f64
      bitcast is unavailable)
    * bit-packed dictionary-index unpacking (gather + shift + mask)
    * dictionary gather and null-expansion (cumsum+gather, no scatter)

Scope (planner falls back to the pyarrow host path otherwise, like the
reference's fallback flags): PLAIN / RLE_DICTIONARY(+PLAIN_DICTIONARY) /
DELTA_BINARY_PACKED (ints) / BYTE_STREAM_SPLIT (floats+ints) encodings,
UNCOMPRESSED or pyarrow-supported codecs, flat non-nested columns of
INT32/INT64/FLOAT/DOUBLE/BOOLEAN/BYTE_ARRAY (strings dictionary-encoded,
PLAIN — the host scans the length-prefixed layout into offsets, a native
single pass, and the device gathers the payload bytes into the padded
matrix — and DELTA_LENGTH_BYTE_ARRAY, whose lengths decode through the
DELTA_BINARY_PACKED kernel; DELTA_BYTE_ARRAY's incremental prefixes are
inherently sequential and fall back), data page v1/v2.
"""
from __future__ import annotations

import struct
import threading
from typing import List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from ..columnar import Column
from ..columnar.batch import bucket_rows
from ..types import DataType
from ..utils.kernel_cache import cached_kernel


class DeviceDecodeUnsupported(Exception):
    """Raised when a chunk needs a shape this decoder does not cover; the
    caller falls back to the pyarrow host path."""


# --------------------------------------------------------------------------
# thrift compact protocol (just enough for PageHeader)
# --------------------------------------------------------------------------

class _Thrift:
    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def _byte(self) -> int:
        b = self.buf[self.pos]
        self.pos += 1
        return b

    def varint(self) -> int:
        out = shift = 0
        while True:
            b = self._byte()
            out |= (b & 0x7F) << shift
            if not b & 0x80:
                return out
            shift += 7

    def zigzag(self) -> int:
        v = self.varint()
        return (v >> 1) ^ -(v & 1)

    def read_struct(self) -> dict:
        """Generic struct read -> {field_id: value}; nested structs become
        dicts, unneeded field types are skipped."""
        out = {}
        fid = 0
        while True:
            head = self._byte()
            if head == 0:  # STOP
                return out
            delta = head >> 4
            ftype = head & 0x0F
            fid = fid + delta if delta else self.zigzag()
            out[fid] = self._value(ftype)

    def _value(self, ftype: int):
        if ftype == 1:
            return True
        if ftype == 2:
            return False
        if ftype == 3:
            return self.zigzag()  # byte
        if ftype in (4, 5, 6):
            return self.zigzag()  # i16/i32/i64
        if ftype == 7:
            v = struct.unpack_from("<d", self.buf, self.pos)[0]
            self.pos += 8
            return v
        if ftype == 8:  # binary
            n = self.varint()
            v = self.buf[self.pos:self.pos + n]
            self.pos += n
            return v
        if ftype == 12:
            return self.read_struct()
        if ftype in (9, 10):  # list/set
            head = self._byte()
            n = head >> 4
            etype = head & 0x0F
            if n == 15:
                n = self.varint()
            return [self._value(etype) for _ in range(n)]
        raise DeviceDecodeUnsupported(f"thrift type {ftype}")


# page type enum
_DATA_PAGE, _INDEX_PAGE, _DICT_PAGE, _DATA_PAGE_V2 = 0, 1, 2, 3
# encodings
_PLAIN, _PLAIN_DICT, _RLE, _BITPACK_DEP, _DELTA = 0, 2, 3, 4, 5
_RLE_DICT = 8


_DELTA_BP = 5   # Encoding.DELTA_BINARY_PACKED
_DELTA_LBA = 6  # Encoding.DELTA_LENGTH_BYTE_ARRAY
_BSS = 9        # Encoding.BYTE_STREAM_SPLIT


def _uvarint(buf: bytes, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def _delta_bp_plan(payload: bytes, n_values: int):
    """Walk DELTA_BINARY_PACKED block/miniblock headers (a handful per
    page).  Returns (first, n_delta, bitpos runs, width runs, dest runs,
    per-delta min_deltas, consumed_bytes)."""
    pos = 0
    block, pos = _uvarint(payload, pos)
    minis, pos = _uvarint(payload, pos)
    total, pos = _uvarint(payload, pos)
    fz, pos = _uvarint(payload, pos)
    first = (fz >> 1) ^ -(fz & 1)
    if total != n_values:
        raise DeviceDecodeUnsupported(
            f"delta count {total} != page values {n_values}")
    vpm = block // max(minis, 1)
    n_delta = max(total - 1, 0)

    bitpos_l, width_l, dest_l, mind_l = [], [], [], []
    taken = 0
    while taken < n_delta:
        mz, pos = _uvarint(payload, pos)
        min_d = (mz >> 1) ^ -(mz & 1)
        widths = payload[pos:pos + minis]
        pos += minis
        for mi in range(minis):
            if taken >= n_delta:
                break
            w = widths[mi]
            take = min(vpm, n_delta - taken)
            if w:
                bitpos_l.append(pos * 8 + np.arange(take, dtype=np.int64)
                                * w)
                width_l.append(np.full(take, w, np.int64))
                dest_l.append(taken + np.arange(take, dtype=np.int64))
                pos += (vpm * w + 7) // 8   # padded to FULL miniblock
            mind_l.append(np.full(take, min_d, np.int64))
            taken += take
    return first, n_delta, bitpos_l, width_l, dest_l, mind_l, pos


def _delta_lengths_host(payload: bytes, n_values: int):
    """DELTA_BINARY_PACKED decode entirely on the HOST (numpy): used for
    DELTA_LENGTH_BYTE_ARRAY string lengths, which only ever feed
    host-side offset computation — a device round trip per page would
    stall the decode on a D2H sync for values the device never uses.
    Returns (int64 values[n_values], consumed_bytes)."""
    first, n_delta, bitpos_l, _width_l, dest_l, mind_l, consumed = \
        _delta_bp_plan(payload, n_values)
    deltas = np.zeros(max(n_delta, 1), np.int64)
    pad = np.concatenate([np.frombuffer(payload, np.uint8),
                          np.zeros(9, np.uint8)])
    for b, w, d in zip(bitpos_l, _width_l, dest_l):
        byte0 = (b // 8).astype(np.int64)
        win = pad[byte0[:, None] + np.arange(9)]
        word = (win[:, :8].astype(np.uint64)
                << (np.arange(8, dtype=np.uint64) * np.uint64(8))
                ).sum(axis=1).astype(np.uint64)
        spill = win[:, 8].astype(np.uint64)
        sh = (b % 8).astype(np.uint64)
        lo = word >> sh
        hi = np.where(sh > 0,
                      spill << ((np.uint64(64) - sh) & np.uint64(63)),
                      np.uint64(0))
        width = int(w[0])
        mask = np.uint64(0xFFFFFFFFFFFFFFFF) if width >= 64 else \
            np.uint64((1 << width) - 1)
        deltas[d] = ((lo | hi) & mask).astype(np.int64)
    if mind_l:
        mind = np.concatenate(mind_l)
        deltas[:n_delta] += mind[:n_delta]
    out = np.empty(max(n_values, 1), np.int64)[:n_values]
    if n_values:
        out[0] = first
        if n_delta:
            out[1:] = first + np.cumsum(deltas[:n_delta])
    return out, consumed


def _delta_bp_decode(payload: bytes, n_values: int, cap: int):
    """DELTA_BINARY_PACKED ints: host walks the block/miniblock headers
    (_delta_bp_plan), the DEVICE unpacks every miniblock's little-endian
    bit-packed deltas in one vectorized gather+shift, adds the per-block
    min deltas, and rebuilds values with one masked cumsum.  The format
    stores first_value + (n-1) deltas; miniblocks are padded to full
    size, so padding lanes are masked out of the cumsum."""
    import jax
    import jax.numpy as jnp

    from ..utils.kernel_cache import cached_kernel

    first, n_delta, bitpos_l, width_l, dest_l, mind_l, _pos = \
        _delta_bp_plan(payload, n_values)

    from ..columnar.batch import bucket_rows
    dcap = bucket_rows(max(n_delta, 1))
    mind = np.zeros(dcap, np.int64)
    if mind_l:
        md = np.concatenate(mind_l)
        mind[:md.size] = md
    n_packed = sum(b.size for b in bitpos_l)
    pbucket = bucket_rows(max(n_packed, 1))
    bitpos = np.zeros(pbucket, np.int64)
    widths_a = np.zeros(pbucket, np.int64)
    dests = np.full(pbucket, dcap, np.int64)
    o = 0
    for b, w, d in zip(bitpos_l, width_l, dest_l):
        bitpos[o:o + b.size] = b
        widths_a[o:o + b.size] = w
        dests[o:o + b.size] = d
        o += b.size
    rbucket = bucket_rows(max(len(payload), 1))
    raw = np.zeros(rbucket, np.uint8)
    raw[:len(payload)] = np.frombuffer(payload, np.uint8)

    def build():
        def k(raw_v, bitpos_v, widths_v, dests_v, mind_v, first_v,
              n_delta_v):
            # little-endian 9-byte window (parquet packs lsb-first)
            byte0 = bitpos_v // 8
            idx = byte0[:, None] + jnp.arange(9, dtype=jnp.int64)[None]
            win = jnp.take(raw_v, jnp.clip(idx, 0, raw_v.shape[0] - 1),
                           mode="clip").astype(jnp.uint64)
            shifts = (jnp.arange(9, dtype=jnp.uint64) * 8)[:8]
            word = jnp.sum(win[:, :8] << shifts, axis=1, dtype=jnp.uint64)
            spill = win[:, 8]
            b = (bitpos_v % 8).astype(jnp.uint64)
            lo = word >> b
            # b == 0 would shift by 64 (UB); the where() discards that
            # lane, so clamp the shift to stay defined
            hi = jnp.where(
                b > 0,
                spill << jnp.clip(jnp.uint64(64) - b, jnp.uint64(0),
                                  jnp.uint64(63)), jnp.uint64(0))
            mask = jnp.where(
                widths_v >= 64, jnp.uint64(0xFFFFFFFFFFFFFFFF),
                (jnp.uint64(1) << jnp.clip(widths_v, 0, 63
                                           ).astype(jnp.uint64))
                - jnp.uint64(1))
            u = ((lo | hi) & mask).astype(jnp.int64)
            deltas = jnp.zeros(dcap, jnp.int64).at[dests_v].set(
                u, mode="drop")
            lane = jnp.arange(dcap, dtype=jnp.int64)
            deltas = jnp.where(lane < n_delta_v, deltas + mind_v, 0)
            c = jnp.cumsum(deltas)
            vals = jnp.zeros(cap, jnp.int64).at[0].set(first_v)
            n_out = jnp.minimum(n_delta_v + 1, cap)
            take_idx = jnp.clip(jnp.arange(cap) - 1, 0, dcap - 1)
            vals = jnp.where(
                (jnp.arange(cap) >= 1) & (jnp.arange(cap) < n_out),
                first_v + jnp.take(c, take_idx, mode="clip"), vals)
            return vals
        return k

    fn = cached_kernel(("pq_delta_bp", cap, dcap, pbucket, rbucket), build)
    return fn(jnp.asarray(raw), jnp.asarray(bitpos), jnp.asarray(widths_a),
              jnp.asarray(dests), jnp.asarray(mind),
              jnp.int64(first), jnp.int64(n_delta))


def _parse_page_header(buf: bytes, pos: int):
    t = _Thrift(buf, pos)
    s = t.read_struct()
    return {
        "type": s.get(1),
        "uncompressed_size": s.get(2),
        "compressed_size": s.get(3),
        "data_v1": s.get(5),
        "dict": s.get(7),
        "data_v2": s.get(8),
    }, t.pos


# --------------------------------------------------------------------------
# RLE / bit-packed hybrid (host: run headers; device: heavy unpacking)
# --------------------------------------------------------------------------

def _rle_segments(buf: bytes, bit_width: int, num_values: int):
    """Scan the hybrid run structure -> [("rle", count, value) |
    ("bp", count, byte_off, byte_len)]; positions only, no unpacking."""
    segs = []
    t = _Thrift(buf)
    got = 0
    vw = (bit_width + 7) // 8
    while got < num_values:
        header = t.varint()
        if header & 1:  # bit-packed: groups of 8 values
            groups = header >> 1
            count = groups * 8
            blen = groups * bit_width
            segs.append(("bp", min(count, num_values - got), t.pos, blen))
            t.pos += blen
        else:
            count = header >> 1
            value = int.from_bytes(t.buf[t.pos:t.pos + vw], "little") \
                if vw else 0
            t.pos += vw
            segs.append(("rle", min(count, num_values - got), value))
        if count == 0:
            # malformed zero-length run would spin forever; surface it as
            # an unsupported shape so the caller falls back to pyarrow
            raise DeviceDecodeUnsupported("zero-length RLE run")
        got += count
    return segs


def _decode_levels(buf: bytes, bit_width: int, num_values: int) -> np.ndarray:
    """Definition/repetition levels on the host (1-2 bits/row control
    plane).  Returns int32[num_values].  Delegates to the shared
    vectorized hybrid-run decoder (one byte-window pass, not a
    per-segment unpackbits)."""
    out = np.zeros(num_values, dtype=np.int32)
    _indices_decode_host(bytes([bit_width]) + buf, num_values, out, 0)
    return out


# --------------------------------------------------------------------------
# device kernels (shapes bucketed; cached via kernel_cache)
# --------------------------------------------------------------------------

def _pad_bytes(raw: bytes, to_len: int) -> np.ndarray:
    a = np.frombuffer(raw, dtype=np.uint8)
    if len(a) < to_len:
        a = np.concatenate([a, np.zeros(to_len - len(a), dtype=np.uint8)])
    return a


_PLAIN_NP = {"INT32": np.int32, "INT64": np.int64,
             "FLOAT": np.float32, "DOUBLE": np.float64}


def _plain_decode(raw: bytes, n_values: int, phys: str, cap: int):
    """PLAIN fixed-width decode -> jnp array [cap] (tail garbage beyond
    n_values; callers mask by validity).

    PLAIN pages ARE the device representation: raw little-endian IEEE
    values, byte-identical to what the typed device buffer wants.  The
    right amount of decode compute is therefore ZERO — a host frombuffer
    view and one typed H2D transfer.  (An earlier version shipped the u8
    bytes and reassembled words with shift/or lanes on device; that spent
    8 VPU ops per value to recreate bytes the host already had laid out,
    and on the emulated-f64 chip the u64->f64 bit-field rebuild via ldexp
    was the single hottest kernel of the q6 scan.)  Encodings that
    actually expand (dictionary, bit-pack, delta) still decode on device."""
    dt = np.dtype(_PLAIN_NP[phys])
    if len(raw) < n_values * dt.itemsize:
        raise DeviceDecodeUnsupported(
            f"truncated PLAIN page ({len(raw)} bytes for {n_values} "
            f"{phys})")
    vals = np.frombuffer(raw, dtype=dt, count=n_values)
    if n_values < cap:
        out = np.zeros(cap, dtype=vals.dtype)
        out[:n_values] = vals
        vals = out
    return jnp.asarray(vals)


def _plain_decode_bool(raw: bytes, n_values: int, cap: int):
    """PLAIN boolean: LSB-first bitpacked."""
    nbytes = (cap + 7) // 8
    host = _pad_bytes(raw[:(n_values + 7) // 8], nbytes)

    def build():
        def k(u8):
            idx = jnp.arange(cap, dtype=jnp.int32)
            byte = jnp.take(u8, idx >> 3, mode="clip")
            return ((byte >> (idx & 7).astype(jnp.uint8)) & 1).astype(
                jnp.bool_)
        return k

    fn = cached_kernel(("pq_bool", cap), build)
    return fn(host)


def _single_bp_runs(value_pieces):
    """When EVERY piece is a dictionary page whose index stream is one
    bit-packed run (the standard writer layout), return
    [(body_bytes, bit_width, count)] for the batched decoder; else None.
    The per-page fallback loop costs O(pages * chunk_capacity) in copy
    kernels plus a dispatch per page — a 951-page chunk spent 2.2s in
    index decode and 1.3s in range copies before batching."""
    out = []
    for kind, payload, nonnull in value_pieces:
        if kind != "dict" or not payload:
            return None
        bw = payload[0]
        if bw == 0 or bw > 24:
            return None
        segs = _rle_segments(payload[1:], bw, nonnull)
        if len(segs) != 1 or segs[0][0] != "bp":
            return None
        _, count, bo, blen = segs[0]
        if count != nonnull:
            return None
        out.append((payload[1 + bo:1 + bo + blen], bw, nonnull))
    return out


def _dict_indices_batched(runs, vcap: int):
    """All pieces' bit-packed index runs -> ONE compact int32[vcap] of
    dictionary indices: pages stack on a leading axis ([P, bytes] bytes,
    per-page width/count arrays), unpack and ragged-flatten in a single
    kernel (one H2D, one dispatch for the whole chunk)."""
    P = len(runs)
    pbucket = 1 << max(3, (P - 1).bit_length())
    pmax = bucket_rows(max(c for (_b, _w, c) in runs))
    # power-of-two byte bucket: the exact max body length varies per
    # chunk (bit width x last-page truncation) and would recompile the
    # kernel chunk by chunk; reads clip, so zero padding is free
    raw_bmax = max(len(b) for (b, _w, _c) in runs) + 4
    bmax = 1 << max(6, (raw_bmax - 1).bit_length())
    stacked = np.zeros((pbucket, bmax), np.uint8)
    bws = np.zeros(pbucket, np.int32)
    counts = np.zeros(pbucket, np.int32)
    for p, (body, bw, count) in enumerate(runs):
        stacked[p, :len(body)] = np.frombuffer(body, np.uint8)
        bws[p] = bw
        counts[p] = count

    def build():
        def k(u8, bw_v, cnt_v):
            # unpack: value i of page p starts at bit i*bw[p]
            i = jnp.arange(pmax, dtype=jnp.int32)[None, :]
            bitpos = i * bw_v[:, None]
            b0 = bitpos >> 3
            sh = (bitpos & 7).astype(jnp.uint32)
            take = lambda off: jnp.take_along_axis(  # noqa: E731
                u8, jnp.clip(b0 + off, 0, u8.shape[1] - 1),
                axis=1).astype(jnp.uint32)
            w = (take(0) | (take(1) << 8) | (take(2) << 16)
                 | (take(3) << 24))
            mask = (jnp.uint32(1) << bw_v[:, None].astype(jnp.uint32)) \
                - jnp.uint32(1)
            vals = ((w >> sh) & mask).astype(jnp.int32)  # [P, pmax]
            # ragged flatten: page p's rows land at starts[p]..
            ends = jnp.cumsum(cnt_v)
            starts = ends - cnt_v
            o = jnp.arange(vcap, dtype=jnp.int32)
            page = jnp.searchsorted(ends, o, side="right").astype(
                jnp.int32)
            pc = jnp.clip(page, 0, pbucket - 1)
            r = o - jnp.take(starts, pc)
            flat = vals[pc, jnp.clip(r, 0, pmax - 1)]
            return jnp.where(o < ends[-1], flat, 0)
        return k

    fn = cached_kernel(("pq_bp_batched", pbucket, bmax, pmax, vcap),
                       build)
    return fn(jnp.asarray(stacked), jnp.asarray(bws), jnp.asarray(counts))


def _copy_range(buf, vals, off: int, count: int):
    """Masked range write on the leading axis: buf[off:off+count] =
    vals[:count], one compiled kernel per (buf_shape, vals_shape, dtype).
    Unlike dynamic_update_slice this never clamps the start (a
    bucket-padded `vals` may be longer than the space remaining in
    `buf`)."""

    def build():
        def k(b, v, o, c):
            i = jnp.arange(b.shape[0], dtype=jnp.int32)
            src = jnp.take(v, jnp.clip(i - o, 0, v.shape[0] - 1),
                           mode="clip", axis=0)
            m = (i >= o) & (i < o + c)
            if b.ndim > 1:
                m = m.reshape((-1,) + (1,) * (b.ndim - 1))
            return jnp.where(m, src, b)
        return k

    fn = cached_kernel(("pq_copy", buf.shape, vals.shape,
                        str(buf.dtype)), build)
    return fn(buf, vals, jnp.int32(off), jnp.int32(count))


def _indices_decode_host(payload: bytes, n_values: int,
                         out: np.ndarray, base: int) -> None:
    """Dictionary-index stream -> int32 values written into
    out[base:base+n_values] (host numpy; one vectorized pass per run).
    The batched chunk decoder uses this to build ONE index array for a
    whole chunk — a single H2D + dictionary gather replaces a device
    dispatch pair per page."""
    if not payload:
        raise DeviceDecodeUnsupported("empty index page")
    bw = payload[0]
    if bw == 0:
        out[base:base + n_values] = 0
        return
    if bw > 24:
        raise DeviceDecodeUnsupported(f"index bit width {bw}")
    from ..native import pq_rle_decode
    if pq_rle_decode(payload[1:], bw, n_values, out, base):
        return
    buf = np.concatenate([np.frombuffer(payload, np.uint8),
                          np.zeros(4, np.uint8)]).astype(np.uint32)
    # one vectorized 4-byte-window extraction over ALL bit-packed
    # segments (a page can carry dozens of alternating rle/bp runs;
    # per-segment unpackbits was overhead-bound)
    bp_pos: list = []
    bp_dst: list = []
    off = base
    for seg in _rle_segments(payload[1:], bw, n_values):
        if seg[0] == "rle":
            _, count, value = seg
            out[off:off + count] = value
        else:
            _, count, bo, blen = seg
            bp_pos.append((1 + bo) * 8
                          + np.arange(count, dtype=np.int64) * bw)
            bp_dst.append((off, count))
        off += count
    if bp_pos:
        pos = np.concatenate(bp_pos)
        b0 = pos >> 3
        w = (buf[b0] | (buf[b0 + 1] << 8) | (buf[b0 + 2] << 16)
             | (buf[b0 + 3] << 24))
        vals = ((w >> (pos & 7).astype(np.uint32))
                & np.uint32((1 << bw) - 1)).astype(np.int32)
        vo = 0
        for dst, count in bp_dst:
            out[dst:dst + count] = vals[vo:vo + count]
            vo += count


def _chunk_dict_indices(value_pieces, vcap: int):
    """Every dictionary page of a chunk -> ONE compact int32[vcap] index
    array on the device, for numbers and strings alike.  Uniform single
    bit-packed runs unpack on the DEVICE in one dispatch; mixed
    RLE/bit-packed runs (the common pyarrow layout for low-cardinality
    columns) expand on the host page after page into one array (control
    plane on host, like the CSV tokenizer) and ship in one H2D."""
    runs = _single_bp_runs(value_pieces)
    if runs:
        return _dict_indices_batched(runs, vcap)
    host_idx = np.zeros(vcap, np.int32)
    off = 0
    for (_k, payload, nonnull) in value_pieces:
        _indices_decode_host(payload, nonnull, host_idx, off)
        off += nonnull
    return jnp.asarray(host_idx)


# --------------------------------------------------------------------------
# column chunk decode
# --------------------------------------------------------------------------

_PHYS_OK = {"INT32", "INT64", "FLOAT", "DOUBLE", "BOOLEAN", "BYTE_ARRAY"}


def _bss_decode(payload: bytes, n_values: int, phys: str, cap: int):
    """BYTE_STREAM_SPLIT: value i's k-th byte lives in byte plane k
    (payload[k*n + i]) — decode is ONE device gather over the plane
    layout plus a little-endian byte combine.  float32 bitcasts on
    device; float64 combines on host (f64<->int bitcasts are
    unimplemented on the emulated-f64 chip — the same carve-out as the
    sort keys, ops/sort_keys.py:float_sort_keys)."""
    import jax
    import jax.numpy as jnp

    from ..utils.kernel_cache import cached_kernel

    width = 4 if phys in ("FLOAT", "INT32") else 8
    if len(payload) < n_values * width:
        raise DeviceDecodeUnsupported("BYTE_STREAM_SPLIT short payload")
    if phys == "DOUBLE":
        planes = np.frombuffer(payload[:n_values * 8], np.uint8
                               ).reshape(8, n_values)
        vals = np.ascontiguousarray(planes.T).reshape(-1).view(np.float64)
        out = np.zeros(cap, np.float64)
        out[:n_values] = vals
        return jnp.asarray(out)
    raw = np.zeros(bucket_rows(max(len(payload), 1)), np.uint8)
    raw[:len(payload)] = np.frombuffer(payload, np.uint8)

    def build():
        def k(raw_v, n_v):
            lane = jnp.arange(cap, dtype=jnp.int64)
            idx = (jnp.arange(width, dtype=jnp.int64)[None, :] * n_v
                   + lane[:, None])
            b = jnp.take(raw_v, jnp.clip(idx, 0, raw_v.shape[0] - 1),
                         mode="clip").astype(jnp.uint32 if width == 4
                                             else jnp.uint64)
            sh = (jnp.arange(width, dtype=b.dtype) * 8)
            word = jnp.sum(b << sh[None, :], axis=1, dtype=b.dtype)
            word = jnp.where(lane < n_v, word, jnp.zeros((), b.dtype))
            if phys == "FLOAT":
                return jax.lax.bitcast_convert_type(word, jnp.float32)
            if phys == "INT32":
                return word.astype(jnp.int32)
            return word.astype(jnp.int64)
        return k

    fn = cached_kernel(("pq_bss", phys, cap, int(raw.size)), build)
    return fn(jnp.asarray(raw), jnp.int64(n_values))


def _scan_plain_byte_array(payload: bytes, n: int):
    """PLAIN BYTE_ARRAY page body -> (payload u8 array, offsets, lengths).
    The sequential length-prefix walk is host control-plane work (native
    single pass, python fallback); the payload bytes go to the device
    gather untouched."""
    from ..native import pq_byte_array_scan
    arr = np.frombuffer(payload, dtype=np.uint8)
    res = pq_byte_array_scan(arr, n)
    if res is not None:
        return arr, res[0], res[1]
    offs = np.empty(n, np.int64)
    lens = np.empty(n, np.int64)
    pos = 0
    for i in range(n):
        if pos + 4 > len(payload):
            raise DeviceDecodeUnsupported("truncated byte_array page")
        ln = int.from_bytes(payload[pos:pos + 4], "little")
        pos += 4
        if pos + ln > len(payload):
            raise DeviceDecodeUnsupported("truncated byte_array value")
        offs[i] = pos
        lens[i] = ln
        pos += ln
    return arr, offs, lens


def _byte_array_gather(payload: np.ndarray, offsets: np.ndarray,
                       lengths: np.ndarray, cap: int, width: int):
    """Device gather of length-prefixed values into a padded byte matrix:
    mat[i, j] = payload[offsets[i] + j] masked to j < lengths[i].
    The payload is padded to a power-of-two bucket so the kernel-cache
    key space stays bounded across pages (raw page sizes are
    data-dependent and would force one compile per page)."""
    n = len(offsets)
    offs = np.zeros(cap, np.int32)
    offs[:n] = offsets
    lens = np.zeros(cap, np.int32)
    lens[:n] = lengths
    from ..utils import pow2_bucket
    pcap = pow2_bucket(max(int(payload.size), 1))
    if payload.size < pcap:
        payload = np.concatenate(
            [payload, np.zeros(pcap - payload.size, np.uint8)])

    def build():
        def k(buf, o, ln):
            j = jnp.arange(width, dtype=jnp.int32)[None, :]
            idx = o[:, None] + j
            mat = jnp.take(buf, jnp.clip(idx, 0, buf.shape[0] - 1),
                           mode="clip")
            return jnp.where(j < ln[:, None], mat,
                             jnp.zeros((), jnp.uint8))
        return k

    lens_dev = jnp.asarray(lens)
    fn = cached_kernel(("pq_ba_gather", cap, width, pcap), build)
    return fn(jnp.asarray(payload), jnp.asarray(offs), lens_dev), lens_dev


def _parse_byte_array_dict(data: bytes, n: int):
    """PLAIN byte_array dictionary page -> (byte matrix [n_cap, L],
    lengths [n_cap]) as numpy.  The dictionary is the SMALL side of a
    dictionary-encoded column (distinct values only) — host parsing it is
    control-plane work; the per-row index decode and gather stay on
    device."""
    from ..columnar.column import bucket_strlen
    vals = []
    pos = 0
    for _ in range(n):
        if pos + 4 > len(data):
            raise DeviceDecodeUnsupported("truncated dictionary page")
        ln = int.from_bytes(data[pos:pos + 4], "little")
        pos += 4
        if pos + ln > len(data):
            # a short read here would silently store truncated string
            # values; fall back to the pyarrow reader instead
            raise DeviceDecodeUnsupported("truncated dictionary value")
        vals.append(data[pos:pos + ln])
        pos += ln
    n_cap = bucket_rows(max(n, 1))
    L = bucket_strlen(max((len(v) for v in vals), default=1) or 1)
    mat = np.zeros((n_cap, L), dtype=np.uint8)
    lens = np.zeros(n_cap, dtype=np.int32)
    for i, v in enumerate(vals):
        mat[i, :len(v)] = np.frombuffer(v, dtype=np.uint8)
        lens[i] = len(v)
    return mat, lens


_CODECS: dict = {}
_DECOMP_POOL = None
_POOL_INIT_LOCK = threading.Lock()


def _decomp_pool():
    """Shared thread pool for page decompression: pyarrow's codecs release
    the GIL, so snappy/zstd across a chunk's pages parallelizes.  Built
    under a lock: concurrent first-touch from scheduler worker threads
    must not build (and leak) two executors (TPU009)."""
    global _DECOMP_POOL
    if _DECOMP_POOL is None:
        import os
        from concurrent.futures import ThreadPoolExecutor
        with _POOL_INIT_LOCK:
            if _DECOMP_POOL is None:
                _DECOMP_POOL = ThreadPoolExecutor(
                    max_workers=min(8, os.cpu_count() or 1),
                    thread_name_prefix="pq-decomp")
    return _DECOMP_POOL


_COLUMN_POOL = None


def _column_pool():
    """Thread pool for whole-COLUMN decode tasks.  Distinct from
    _decomp_pool on purpose: a column task blocks on its decompression
    range tasks, so sharing one pool would deadlock once every worker
    holds a column task."""
    global _COLUMN_POOL
    if _COLUMN_POOL is None:
        import os
        from concurrent.futures import ThreadPoolExecutor
        with _POOL_INIT_LOCK:
            if _COLUMN_POOL is None:
                _COLUMN_POOL = ThreadPoolExecutor(
                    max_workers=min(8, os.cpu_count() or 1),
                    thread_name_prefix="pq-column")
    return _COLUMN_POOL


def _pages_from_table(raw: bytes, pages: dict, codec: str, num_rows: int,
                      max_def: int):
    """Native page table (native.pq_page_walk) -> (value_pieces,
    valid bool[num_rows], decompressed dict page or None).  Mirrors the
    python page walk byte for byte, raising DeviceDecodeUnsupported for
    the same out-of-scope shapes; levels decode + nonnull counting happen
    in one native call per page."""
    from ..native import pq_def_levels
    ptype = pages["ptype"]
    data_off = pages["data_off"]
    comp = pages["comp_size"]
    uncomp = pages["uncomp_size"]
    nvals_a = pages["n_vals"]
    enc_a = pages["enc"]
    dl_enc_a = pages["dl_enc"]
    dl_len_a = pages["dl_len"]
    rl_len_a = pages["rl_len"]
    comp_flag_a = pages["comp_flag"]
    n_pages = len(ptype)
    bw_def = max(max_def.bit_length(), 1)

    def _payload(i):
        po = int(data_off[i])
        pl = raw[po:po + int(comp[i])]
        t = int(ptype[i])
        if t == _DATA_PAGE_V2:
            dl = max(int(dl_len_a[i]), 0)
            rl = max(int(rl_len_a[i]), 0)
            body = pl[dl + rl:]
            if int(comp_flag_a[i]):
                body = _decompress(codec, body, int(uncomp[i]) - dl - rl)
            return pl[:dl + rl] + body
        return _decompress(codec, pl, int(uncomp[i]))

    if codec != "UNCOMPRESSED" and n_pages >= 64:
        # ~8 range tasks, each decompressing its span sequentially: one
        # future per PAGE was overhead-bound (57KB pages, 1200+ futures)
        import os
        n_tasks = min(8, os.cpu_count() or 1)
        step = (n_pages + n_tasks - 1) // n_tasks
        spans = [range(lo, min(lo + step, n_pages))
                 for lo in range(0, n_pages, step)]
        parts = _decomp_pool().map(
            lambda sp: [_payload(i) for i in sp], spans)
        datas = [d for part in parts for d in part]
    else:
        datas = [_payload(i) for i in range(n_pages)]

    total_vals = int(sum(int(nvals_a[i]) for i in range(n_pages)
                         if int(ptype[i]) in (_DATA_PAGE, _DATA_PAGE_V2)))
    valid_np = np.zeros(max(total_vals, num_rows), dtype=np.uint8)
    value_pieces: List[Tuple] = []
    dict_raw = None
    rows_seen = 0
    for i in range(n_pages):
        t = int(ptype[i])
        data = datas[i]
        if t == _DICT_PAGE:
            dict_raw = (data, int(pages["dict_n"][i]))
            continue
        if t == _INDEX_PAGE:
            continue
        if t not in (_DATA_PAGE, _DATA_PAGE_V2):
            raise DeviceDecodeUnsupported(f"page type {t}")
        n_vals = int(nvals_a[i])
        enc = int(enc_a[i])
        dpos = 0
        if t == _DATA_PAGE:
            if max_def > 0:
                if int(dl_enc_a[i]) != _RLE:
                    raise DeviceDecodeUnsupported("def level encoding")
                ln = struct.unpack_from("<i", data, 0)[0]
                nn = pq_def_levels(data[4:4 + ln], bw_def, n_vals, max_def,
                                   valid_np, rows_seen)
                if nn is None:
                    dl = _decode_levels(data[4:4 + ln], bw_def, n_vals)
                    eq = dl == max_def
                    valid_np[rows_seen:rows_seen + n_vals] = eq
                    nn = int(eq.sum())
                dpos = 4 + ln
            else:
                valid_np[rows_seen:rows_seen + n_vals] = 1
                nn = n_vals
        else:
            if int(rl_len_a[i]) > 0:
                raise DeviceDecodeUnsupported("repetition levels")
            dl_len = max(int(dl_len_a[i]), 0)
            if max_def > 0 and dl_len:
                nn = pq_def_levels(data[:dl_len], bw_def, n_vals, max_def,
                                   valid_np, rows_seen)
                if nn is None:
                    dl = _decode_levels(data[:dl_len], bw_def, n_vals)
                    eq = dl == max_def
                    valid_np[rows_seen:rows_seen + n_vals] = eq
                    nn = int(eq.sum())
            elif max_def > 0:
                # v2 page for a NULLABLE column with zero level bytes:
                # levels default to 0 != max_def, i.e. all null (the
                # python walk's np.full(n_vals, 0) branch)
                nn = 0
            else:
                valid_np[rows_seen:rows_seen + n_vals] = 1
                nn = n_vals
            dpos = dl_len
        if enc == _PLAIN:
            value_pieces.append(("plain", data[dpos:], nn))
        elif enc in (_RLE_DICT, _PLAIN_DICT):
            value_pieces.append(("dict", data[dpos:], nn))
        elif enc == _DELTA_BP:
            value_pieces.append(("delta_bp", data[dpos:], nn))
        elif enc == _DELTA_LBA:
            value_pieces.append(("delta_lba", data[dpos:], nn))
        elif enc == _BSS:
            value_pieces.append(("bss", data[dpos:], nn))
        else:
            raise DeviceDecodeUnsupported(f"value encoding {enc}")
        rows_seen += n_vals

    if rows_seen < num_rows:
        raise DeviceDecodeUnsupported("pages cover fewer rows than chunk")
    return value_pieces, valid_np[:num_rows].view(bool), dict_raw


def _decompress(codec: str, payload: bytes, uncompressed_size: int) -> bytes:
    if codec == "UNCOMPRESSED":
        return payload
    c = _CODECS.get(codec)
    if c is None:
        import pyarrow as pa
        try:
            with _POOL_INIT_LOCK:
                c = _CODECS.get(codec)
                if c is None:
                    c = _CODECS[codec] = pa.Codec(codec.lower())
        except Exception as ex:
            raise DeviceDecodeUnsupported(f"codec {codec}: {ex}")
    out = c.decompress(payload, uncompressed_size)
    return out.to_pybytes() if hasattr(out, "to_pybytes") else bytes(out)


def decode_column_chunk(path: str, col_meta, phys: str, dtype: DataType,
                        num_rows: int, max_def: int, cap: int,
                        counts: Optional[dict] = None) -> Column:
    """One row-group column chunk -> device Column with `cap` capacity.
    `counts`, where given, gets `page_copies` raised by the `_copy_range`
    calls the assembly made for a page or a page group (the caller's own
    dict: the decode runs on the column pool).

    Raises DeviceDecodeUnsupported for any page shape outside scope."""
    if phys not in _PHYS_OK:
        raise DeviceDecodeUnsupported(f"physical type {phys}")
    encs = set(col_meta.encodings)
    if not encs <= {"PLAIN", "RLE", "PLAIN_DICTIONARY", "RLE_DICTIONARY",
                    "BIT_PACKED", "DELTA_BINARY_PACKED",
                    "BYTE_STREAM_SPLIT", "DELTA_LENGTH_BYTE_ARRAY"}:
        raise DeviceDecodeUnsupported(f"encodings {encs}")
    if "DELTA_BINARY_PACKED" in encs and phys not in ("INT32", "INT64"):
        raise DeviceDecodeUnsupported("DELTA_BINARY_PACKED non-int")
    if "DELTA_LENGTH_BYTE_ARRAY" in encs and phys != "BYTE_ARRAY":
        raise DeviceDecodeUnsupported("DELTA_LENGTH_BYTE_ARRAY non-string")
    if "BYTE_STREAM_SPLIT" in encs and phys not in ("FLOAT", "DOUBLE",
                                                    "INT32", "INT64"):
        raise DeviceDecodeUnsupported("BYTE_STREAM_SPLIT phys type")
    start = col_meta.dictionary_page_offset \
        if col_meta.dictionary_page_offset is not None \
        else col_meta.data_page_offset
    with open(path, "rb") as f:
        f.seek(start)
        raw = f.read(col_meta.total_compressed_size)
    codec = col_meta.compression

    def _assembled(value_pieces, valid_np, dict_raw):
        col, copies = _assemble_chunk(value_pieces, valid_np, dict_raw,
                                      phys, dtype, num_rows, cap)
        if counts is not None:
            counts["page_copies"] = counts.get("page_copies", 0) + copies
        return col

    from ..native import pq_page_walk
    pages = pq_page_walk(raw, num_rows)
    if pages is not None:
        # native header walk + per-page native level decode + pooled
        # decompression; mirrors the python loop below exactly
        return _assembled(*_pages_from_table(raw, pages, codec, num_rows,
                                             max_def))
    dict_raw = None   # (decompressed dictionary page, its value count)
    def_levels: List[np.ndarray] = []
    value_pieces: List[Tuple] = []   # ("plain"|"dict", payload, n_nonnull)
    pos = 0
    rows_seen = 0
    while rows_seen < num_rows and pos < len(raw):
        header, pos = _parse_page_header(raw, pos)
        payload = raw[pos:pos + header["compressed_size"]]
        pos += header["compressed_size"]
        ptype = header["type"]
        if ptype == _DICT_PAGE:
            info = header["dict"] or {}
            n_dict = info.get(1, 0)
            dict_raw = (_decompress(codec, payload,
                                    header["uncompressed_size"]), n_dict)
            continue
        if ptype == _DATA_PAGE:
            info = header["data_v1"]
            n_vals = info.get(1)
            enc = info.get(2)
            dl_enc = info.get(3)
            data = _decompress(codec, payload, header["uncompressed_size"])
            dpos = 0
            if max_def > 0:
                if dl_enc != _RLE:
                    raise DeviceDecodeUnsupported("def level encoding")
                ln = struct.unpack_from("<i", data, dpos)[0]
                dpos += 4
                dl = _decode_levels(data[dpos:dpos + ln],
                                    max(max_def.bit_length(), 1), n_vals)
                dpos += ln
            else:
                dl = np.full(n_vals, 0, dtype=np.int32)
        elif ptype == _DATA_PAGE_V2:
            info = header["data_v2"]
            n_vals = info.get(1)
            enc = info.get(4)
            dl_len = info.get(5, 0)
            rl_len = info.get(6, 0)
            compressed_flag = info.get(7, True)
            if rl_len:
                raise DeviceDecodeUnsupported("repetition levels")
            lv = payload[:dl_len]
            body = payload[dl_len:]
            if compressed_flag:
                body = _decompress(
                    codec, body,
                    header["uncompressed_size"] - dl_len - rl_len)
            if max_def > 0 and dl_len:
                dl = _decode_levels(lv, max(max_def.bit_length(), 1),
                                    n_vals)
            else:
                dl = np.full(n_vals, 0, dtype=np.int32)
            data = body
            dpos = 0
        elif ptype == _INDEX_PAGE:
            continue
        else:
            raise DeviceDecodeUnsupported(f"page type {ptype}")

        nonnull = int((dl == max_def).sum()) if max_def > 0 else len(dl)
        def_levels.append((dl == max_def) if max_def > 0
                          else np.ones(len(dl), dtype=bool))
        if enc == _PLAIN:
            value_pieces.append(("plain", data[dpos:], nonnull))
        elif enc in (_RLE_DICT, _PLAIN_DICT):
            value_pieces.append(("dict", data[dpos:], nonnull))
        elif enc == _DELTA_BP:
            value_pieces.append(("delta_bp", data[dpos:], nonnull))
        elif enc == _DELTA_LBA and phys == "BYTE_ARRAY":
            value_pieces.append(("delta_lba", data[dpos:], nonnull))
        elif enc == _BSS:
            value_pieces.append(("bss", data[dpos:], nonnull))
        else:
            raise DeviceDecodeUnsupported(f"value encoding {enc}")
        rows_seen += n_vals

    if rows_seen < num_rows:
        raise DeviceDecodeUnsupported("pages cover fewer rows than chunk")

    valid_np = np.concatenate(def_levels)[:num_rows] if def_levels \
        else np.ones(0, dtype=bool)
    return _assembled(value_pieces, valid_np, dict_raw)


def _dict_numpy(dict_raw, phys: str):
    """A fixed-width dictionary page as numpy, straight from the
    decompressed page (host assembly never takes a device round trip);
    None without a dictionary page or for another physical type."""
    if dict_raw is None or phys not in _PLAIN_NP:
        return None
    data_b, n_dict = dict_raw
    dt = np.dtype(_PLAIN_NP[phys])
    if len(data_b) < n_dict * dt.itemsize:
        raise DeviceDecodeUnsupported("truncated dictionary page")
    return np.frombuffer(data_b, dt, count=n_dict)


def _assemble_numeric_host(value_pieces, valid_np, valid_host, dict_raw,
                           phys, dtype: DataType, num_rows: int, cap: int,
                           vcap: int, total_nonnull: int):
    """CPU-backend numeric assembly entirely in numpy + ONE typed transfer.

    On a chip the device-side dictionary gather minimizes host-link
    bytes (packed indices + small dictionary instead of full-width
    values), so the device path stays the default there.  On the CPU
    backend the 'transfer' is a memcpy and every device-side assembly
    kernel is pure overhead — host gather + host null-expand + one
    jnp.asarray is the oracle-speed layout.  Returns None when out of
    scope (caller uses the device path)."""
    import jax
    if jax.default_backend() != "cpu" \
            or phys not in ("INT32", "INT64", "FLOAT", "DOUBLE"):
        return None
    kinds = {k for (k, _p, n) in value_pieces if n > 0}
    if not kinds <= {"plain", "dict"}:
        return None
    if "dict" in kinds:
        dict_np = _dict_numpy(dict_raw, phys)
        if dict_np is None:
            raise DeviceDecodeUnsupported("dict page missing")
    np_dt = _PLAIN_NP[phys]
    out_np = np.zeros(vcap, np_dt)
    off = 0
    for kind, payload, nonnull in value_pieces:
        if nonnull == 0:
            continue
        if kind == "plain":
            if len(payload) < nonnull * np.dtype(np_dt).itemsize:
                raise DeviceDecodeUnsupported("truncated PLAIN page")
            out_np[off:off + nonnull] = np.frombuffer(payload, np_dt,
                                                      count=nonnull)
        else:
            idx = np.zeros(nonnull, np.int32)
            _indices_decode_host(payload, nonnull, idx, 0)
            out_np[off:off + nonnull] = np.take(dict_np, idx, mode="clip")
        off += nonnull
    target = np.dtype(dtype.jnp_dtype)
    if total_nonnull == num_rows and vcap == cap:
        data = out_np
    else:
        data = np.zeros(cap, np_dt)
        data[:num_rows][valid_np] = out_np[:total_nonnull]
    return Column(jnp.asarray(data.astype(target, copy=False)),
                  jnp.asarray(valid_host), dtype)


def _assemble_strings(value_pieces, valid_np, valid_host, dict_raw,
                      dtype: DataType, num_rows: int, cap: int, vcap: int):
    """A BYTE_ARRAY chunk's pages -> device string Column in a number of
    launches that does not depend on the number of pages (the page-wise
    loop this replaces rewrote the chunk's 1M-row buffers twice a page:
    606 pages a query cost TPC-H Q1 6.4 of its 7.5 s).  What the chunk
    holds decides the road:

      every page dictionary-coded (the standard writer layout): ONE
        chunk-wide index array (`_chunk_dict_indices`) and ONE program
        that expands it to row positions and gathers the dictionary's
        bytes and lengths by it;
      anything else (PLAIN, DELTA_LENGTH_BYTE_ARRAY, or a dictionary
        prefix with a PLAIN suffix after the writer's dictionary
        overflowed): every value is an (offset, length) into ONE host
        payload, the dictionary page being one more stretch of it (it IS
        a PLAIN byte-array page); nulls expand on the host, where the
        offsets already are, and ONE `_byte_array_gather` at `cap` lays
        out the rows.

    Rows that are null or past `num_rows` come out zeroed, length 0."""
    from ..columnar.column import bucket_strlen
    if not dtype.is_string:
        raise DeviceDecodeUnsupported("byte_array into non-string")
    kinds = {k for (k, _p, _n) in value_pieces}
    if not kinds <= {"plain", "delta_lba", "dict"}:
        raise DeviceDecodeUnsupported(f"byte_array via {sorted(kinds)}")
    if "dict" in kinds and dict_raw is None:
        raise DeviceDecodeUnsupported("dict page missing")
    no_nulls = bool(valid_np.all())
    valid_dev = jnp.asarray(valid_host)
    # all-null pages hold no values
    live = [vp for vp in value_pieces if vp[2] > 0]

    if kinds == {"dict"}:
        dmat, dlens = _parse_byte_array_dict(*dict_raw)
        n_cap, width = dmat.shape
        idx = _chunk_dict_indices(live, vcap)
        # no nulls at full capacity: the compact index IS the row index
        expand = not (no_nulls and vcap == cap)

        def build_sdict():
            def k(dm, dl, ix, valid_v):
                if expand:
                    vi = jnp.cumsum(valid_v.astype(jnp.int32)) - 1
                    ix = jnp.take(ix, jnp.clip(vi, 0, vcap - 1),
                                  mode="clip")
                data = jnp.take(dm, ix, axis=0, mode="clip")
                lens = jnp.take(dl, ix, mode="clip")
                return (jnp.where(valid_v[:, None], data,
                                  jnp.zeros((), jnp.uint8)),
                        jnp.where(valid_v, lens, 0))
            return k

        fn = cached_kernel(("pq_sdict", n_cap, width, vcap, cap, expand),
                           build_sdict)
        data, lengths = fn(dmat, dlens, idx, valid_dev)
        return Column(data, valid_dev, dtype, lengths)

    payloads, offs_l, lens_l = [], [], []
    base = 0
    max_len = 1
    if "dict" in kinds:
        darr, doffs, dlens = _scan_plain_byte_array(*dict_raw)
        payloads.append(darr)
        base = int(darr.size)
        if dlens.size:
            max_len = max(max_len, int(dlens.max()))
    for kind, payload, nonnull in live:
        if kind == "dict":
            idx = np.zeros(nonnull, np.int32)
            _indices_decode_host(payload, nonnull, idx, 0)
            offs_l.append(np.take(doffs, idx, mode="clip"))
            lens_l.append(np.take(dlens, idx, mode="clip"))
            continue
        if kind == "plain":
            arr, offs, lens = _scan_plain_byte_array(payload, nonnull)
        else:
            # DELTA_LENGTH_BYTE_ARRAY: the lengths are a small
            # DELTA_BINARY_PACKED block decoded on the host; the byte
            # payload follows it, so offsets are one cumsum
            lens, consumed = _delta_lengths_host(payload, nonnull)
            if (lens < 0).any():
                raise DeviceDecodeUnsupported("negative string length")
            offs = np.zeros(nonnull, np.int64)
            np.cumsum(lens[:-1], out=offs[1:])
            offs += consumed
            arr = np.frombuffer(payload, np.uint8)
            if int(offs[-1] + lens[-1]) > arr.size:
                raise DeviceDecodeUnsupported(
                    "truncated delta_length byte payload")
        payloads.append(arr)
        offs_l.append(offs + base)
        lens_l.append(lens)
        base += int(arr.size)
    if base >= 1 << 31:
        raise DeviceDecodeUnsupported("chunk payload past int32 offsets")
    offs = np.concatenate(offs_l) if offs_l else np.zeros(0, np.int64)
    lens = np.concatenate(lens_l) if lens_l else np.zeros(0, np.int64)
    if lens.size:
        max_len = max(max_len, int(lens.max()))
    if not no_nulls:
        row_offs = np.zeros(num_rows, np.int64)
        row_lens = np.zeros(num_rows, np.int64)
        row_offs[valid_np] = offs
        row_lens[valid_np] = lens
        offs, lens = row_offs, row_lens
    payload = np.concatenate(payloads) if payloads \
        else np.zeros(0, np.uint8)
    data, lengths = _byte_array_gather(payload, offs, lens, cap,
                                       bucket_strlen(max_len))
    return Column(data, valid_dev, dtype, lengths)


def _assemble_chunk(value_pieces, valid_np, dict_raw, phys,
                    dtype: DataType, num_rows: int, cap: int):
    """Page pieces -> (device Column, `_copy_range` calls made): compact
    non-null values assemble with batched per-kind dispatches, then
    null-expand to row positions.  `dict_raw` is the decompressed
    dictionary page and its value count, or None."""
    total_nonnull = int(valid_np.sum())
    vcap = bucket_rows(max(total_nonnull, 1))
    valid_host = np.zeros(cap, dtype=bool)
    valid_host[:num_rows] = valid_np

    col = _assemble_numeric_host(value_pieces, valid_np, valid_host,
                                 dict_raw, phys, dtype, num_rows, cap,
                                 vcap, total_nonnull)
    if col is not None:
        return col, 0
    if phys == "BYTE_ARRAY":
        return _assemble_strings(value_pieces, valid_np, valid_host,
                                 dict_raw, dtype, num_rows, cap, vcap), 0
    if dict_raw is None:
        dict_values = None
    elif phys == "BOOLEAN":
        raise DeviceDecodeUnsupported("boolean dictionary")
    else:
        dict_values = _plain_decode(*dict_raw, phys,
                                    bucket_rows(max(dict_raw[1], 1)))
    copies = 0

    # assemble compact (non-null) value array on device.  The two
    # standard whole-chunk layouts take ONE-dispatch batched paths; mixed
    # layouts (writer dictionary overflow etc.) go group by group below.
    # all-null pages contribute nothing; dropping them up front keeps
    # the batched whole-chunk paths eligible
    value_pieces = [vp for vp in value_pieces if vp[2] > 0]
    kinds = {k for (k, _p, _n) in value_pieces}
    if kinds == {"dict"} and dict_values is not None:
        idx = _chunk_dict_indices(value_pieces, vcap)
        compact = jnp.take(dict_values, idx, mode="clip").astype(
            dtype.jnp_dtype)
        return _expand_to_rows(compact, valid_host, vcap, cap, dtype,
                               total_nonnull == num_rows), copies
    if kinds == {"plain"} and phys in ("INT32", "INT64", "FLOAT",
                                       "DOUBLE"):
        width = 4 if phys in ("INT32", "FLOAT") else 8
        joined = b"".join(p[:n * width] for (_k, p, n) in value_pieces)
        compact = _plain_decode(joined, total_nonnull, phys, vcap).astype(
            dtype.jnp_dtype)
        return _expand_to_rows(compact, valid_host, vcap, cap, dtype,
                               total_nonnull == num_rows), copies
    if phys == "BOOLEAN":
        compact = jnp.zeros(vcap, dtype=jnp.bool_)
    else:
        compact = jnp.zeros(vcap, dtype=dtype.jnp_dtype)
    # group CONSECUTIVE same-kind pages: the standard mixed layout (writer
    # dictionary overflow) is a dict-page prefix + plain suffix, which
    # decodes as TWO device dispatches + two range copies instead of a
    # dispatch pair per page (the per-page loop was 887 eager binds on a
    # 24-chunk q6 scan)
    groups: List[Tuple[str, List[Tuple[bytes, int]]]] = []
    for kind, payload, nonnull in value_pieces:
        if groups and groups[-1][0] == kind:
            groups[-1][1].append((payload, nonnull))
        else:
            groups.append((kind, [(payload, nonnull)]))
    off = 0
    for kind, pieces in groups:
        gn = sum(n for (_p, n) in pieces)
        pcap = bucket_rows(gn)
        if kind == "plain" and phys != "BOOLEAN":
            width = 4 if phys in ("INT32", "FLOAT") else 8
            joined = b"".join(p[:n * width] for (p, n) in pieces)
            piece = _plain_decode(joined, gn, phys, pcap).astype(
                dtype.jnp_dtype)
        elif kind == "dict":
            if dict_values is None:
                raise DeviceDecodeUnsupported("dict page missing")
            host_idx = np.zeros(pcap, np.int32)
            o = 0
            for p, n in pieces:
                _indices_decode_host(p, n, host_idx, o)
                o += n
            piece = jnp.take(dict_values, jnp.asarray(host_idx),
                             mode="clip").astype(dtype.jnp_dtype)
        else:
            # rare page shapes stay per-page (boolean plain bitpacked
            # pages can't join mid-byte; delta/bss carry per-page headers)
            for p, n in pieces:
                sub_cap = bucket_rows(n)
                if kind == "plain":
                    sub = _plain_decode_bool(p, n, sub_cap)
                elif kind == "delta_bp":
                    sub = _delta_bp_decode(p, n, sub_cap).astype(
                        dtype.jnp_dtype)
                elif kind == "bss":
                    sub = _bss_decode(p, n, phys, sub_cap).astype(
                        dtype.jnp_dtype)
                else:
                    raise DeviceDecodeUnsupported(f"value kind {kind}")
                compact = _copy_range(compact, sub, off, n)
                copies += 1
                off += n
            continue
        compact = _copy_range(compact, piece, off, gn)
        copies += 1
        off += gn

    return _expand_to_rows(compact, valid_host, vcap, cap, dtype,
                           total_nonnull == num_rows), copies


def _expand_to_rows(compact, valid_host, vcap: int, cap: int,
                    dtype, no_nulls: bool = False) -> Column:
    """out[r] = compact[cumsum(valid)-1] — null expansion, no scatter."""
    if vcap == cap and no_nulls:
        # no nulls among the live rows (the common case for fact-table
        # measures): the compact array IS the row data — skip the
        # cumsum/take kernel.  Tail rows (>= num_rows) keep whatever the
        # decode produced; their valid bits are False, the same contract
        # every bucketed-capacity column already carries.
        return Column(compact, jnp.asarray(valid_host), dtype)

    def build_expand():
        def k(compact_v, valid_v):
            vi = jnp.cumsum(valid_v.astype(jnp.int32)) - 1
            out = jnp.take(compact_v, jnp.clip(vi, 0, compact_v.shape[0] - 1),
                           mode="clip")
            return jnp.where(valid_v, out,
                             jnp.zeros_like(out))
        return k

    fn = cached_kernel(("pq_expand", vcap, cap, str(compact.dtype)),
                       build_expand)
    data = fn(compact, valid_host)
    return Column(data, jnp.asarray(valid_host), dtype)
