"""Device-side CSV decode.

The reference decodes CSV on the device by copying the split into a host
buffer and handing it to a native parse kernel (GpuBatchScanExec.scala:
309-477, cuDF Table.readCSV).  The TPU-native equivalent splits the work by
what each side is good at:

  host   - ONE vectorized numpy scan over the raw bytes finds every
           delimiter and validates the rectangular structure (rows x cols);
           this is index arithmetic, not parsing, and is O(bytes) with no
           Python per-row loop.  Quoted files route through the native C
           tokenizer instead (host_runtime.cpp csv_tokenize), which
           handles embedded separators/newlines and doubled-quote escapes
           in one stateful pass;
  device - the raw byte buffer is uploaded ONCE per file; each column's
           field bytes are gathered into a padded byte matrix by a 2-D
           take, and the existing string->value parse kernels (ops/cast.py
           _parse_integral/_parse_float/_parse_bool/_parse_date/
           _parse_timestamp) turn text into typed columns — the same
           whole-column Horner-scan parsers the cast path compiles.

Spark CSV null semantics match the host reader (io/scan.py
_read_csv_arrow): unquoted empty, NULL and null tokens are null for every
type; quoted tokens stay literal.  One deliberate divergence: an
UNPARSEABLE quoted value in a numeric column decodes as null (Spark's
PERMISSIVE mode) where the pyarrow host reader raises — the device path
follows Spark.  Files outside the tokenizers' scope (CR line endings, jagged
rows, multi-byte separators, >2 GiB offsets) raise
`CsvDeviceUnsupported` and the scan exec falls back to the host arrow
reader for that file — the same file-granular fallback discipline as the
parquet device decoder's column-granular one (io/parquet_device.py).
"""
from __future__ import annotations

import os
from typing import Iterator, List, Optional

import numpy as np

from ..columnar import Column, ColumnarBatch
from ..columnar.batch import bucket_rows
from ..columnar.column import bucket_strlen
from ..types import Schema, StringType
from ..metrics import names as MN
from ..utils.tracing import named_range

_NL = 0x0A
_CR = 0x0D
_QUOTE = 0x22


class CsvDeviceUnsupported(Exception):
    pass


def _tokenize(raw: np.ndarray, sep: int, header: bool):
    """Host control plane: (starts, lengths) int64 matrices of shape
    (rows, ncols-as-found) from one delimiter scan.  Raises
    CsvDeviceUnsupported for structures the device gather cannot express."""
    if _QUOTE in raw:
        # quoting needs stateful scanning (embedded separators/newlines,
        # doubled-quote escapes) — one pass in the native tokenizer
        # (which understands CRLF in unquoted context)
        return _tokenize_native(raw, sep, header)
    if _CR in raw:
        # CRLF files: strip the CRs in one vectorized pass (every CR must
        # precede a NL — a bare CR is the old-Mac line ending, out of
        # scope like pyarrow's default)
        cr = np.flatnonzero(raw == _CR)
        nxt = cr + 1
        if nxt[-1] >= raw.size or not (raw[nxt] == _NL).all():
            raise CsvDeviceUnsupported("bare CR line endings")
        raw = np.delete(raw, cr)
    if raw.size and raw[-1] != _NL:
        raw = np.concatenate([raw, np.array([_NL], dtype=np.uint8)])
    data_start = 0
    if header:
        nl = np.flatnonzero(raw == _NL)
        if nl.size == 0:
            raise CsvDeviceUnsupported("header line missing")
        data_start = int(nl[0]) + 1
    body = raw[data_start:]
    rows = int(np.count_nonzero(body == _NL))
    if rows == 0:
        return raw, np.zeros((0, 1), np.int64), np.zeros((0, 1), np.int64), \
            None
    d = np.flatnonzero((body == sep) | (body == _NL)).astype(np.int64)
    if d.size % rows != 0:
        raise CsvDeviceUnsupported("jagged rows")
    ncols = d.size // rows
    bounds = d.reshape(rows, ncols)
    # every row must end in newline with separators elsewhere, or some row
    # had a different field count (jagged) and the reshape misaligned
    if not (body[bounds[:, -1]] == _NL).all() \
            or (ncols > 1 and not (body[bounds[:, :-1]] == sep).all()):
        raise CsvDeviceUnsupported("jagged rows")
    starts = np.empty((rows, ncols), dtype=np.int64)
    starts[0, 0] = 0
    if rows > 1:
        starts[1:, 0] = bounds[:-1, -1] + 1
    if ncols > 1:
        starts[:, 1:] = bounds[:, :-1] + 1
    lengths = bounds - starts
    return raw, starts + data_start, lengths, None


def _tokenize_native(raw: np.ndarray, sep: int, header: bool):
    """Quote-aware tokenization through the C scanner
    (native/src/host_runtime.cpp csv_tokenize): handles embedded
    separators/newlines and doubled-quote escapes; escaped fields are
    rewritten into a side buffer appended to the upload.  Returns
    (raw, starts, lengths, quoted) with `quoted` marking fields whose
    emptiness/NULL token must NOT read as null (quoted semantics)."""
    from ..native import csv_tokenize

    if raw.size and raw[-1] != _NL:
        raw = np.concatenate([raw, np.array([_NL], dtype=np.uint8)])
    tok = csv_tokenize(raw, sep)
    if tok is None:
        raise CsvDeviceUnsupported("quoted fields (native tokenizer "
                                   "unavailable or malformed quoting)")
    starts, lens, flags, nf = tok
    if nf == 0:
        return raw, np.zeros((0, 1), np.int64), np.zeros((0, 1), np.int64), \
            None
    row_last = np.flatnonzero(flags & 4)
    ncols = int(row_last[0]) + 1
    rows = row_last.size
    if nf != rows * ncols or not (
            row_last == np.arange(1, rows + 1) * ncols - 1).all():
        raise CsvDeviceUnsupported("jagged rows")
    # unescape the (rare) fields with doubled quotes into a side buffer
    esc = np.flatnonzero((flags & 3) == 2)
    if esc.size:
        side = bytearray()
        base = int(raw.size)
        for i in esc.tolist():
            s, l = int(starts[i]), int(lens[i])
            fixed = raw[s:s + l].tobytes().replace(b'""', b'"')
            starts[i] = base + len(side)
            lens[i] = len(fixed)
            side.extend(fixed)
        raw = np.concatenate([raw, np.frombuffer(bytes(side),
                                                 dtype=np.uint8)])
    starts = starts.reshape(rows, ncols)
    lengths = lens.reshape(rows, ncols)
    quoted = ((flags & 3) > 0).reshape(rows, ncols)
    if header:
        starts, lengths, quoted = starts[1:], lengths[1:], quoted[1:]
    return raw, starts, lengths, quoted


def _decode_chunk(raw_dev, starts: np.ndarray, lengths: np.ndarray,
                  schema: Schema, conf,
                  quoted: "np.ndarray | None" = None) -> ColumnarBatch:
    """Gather each column's field bytes on device and parse to the target
    dtype.  `starts`/`lengths` are the chunk's host token structure;
    `quoted` marks fields whose null-token forms stay literal (a quoted
    "" is the empty string, a quoted "NULL" is the word — pyarrow's
    quoted_strings_can_be_null=False semantics)."""
    import jax.numpy as jnp

    from ..ops import cast as castmod
    from ..utils.kernel_cache import cached_kernel

    rows = starts.shape[0]
    cap = bucket_rows(max(rows, 1))
    cols: List[Column] = []
    live = np.zeros(cap, dtype=bool)
    live[:rows] = True
    sel = jnp.asarray(live)
    for i, f in enumerate(schema):
        width = bucket_strlen(int(lengths[:, i].max()) if rows else 0)
        s = np.zeros(cap, dtype=np.int32)
        ln = np.zeros(cap, dtype=np.int32)
        s[:rows] = starts[:, i]
        ln[:rows] = lengths[:, i]
        qm = np.zeros(cap, dtype=bool)
        if quoted is not None and rows:
            qm[:rows] = quoted[:, i]
        key = ("csv_decode", f.dtype.name, cap, width)

        def make(dtype=f.dtype, width=width):
            def fn(raw, s, ln, sel, qm):
                pos = jnp.arange(width, dtype=jnp.int32)[None, :]
                idx = jnp.clip(s[:, None] + pos, 0, raw.shape[0] - 1)
                in_field = pos < ln[:, None]
                data = jnp.where(in_field, raw[idx], 0)
                # Spark CSV null tokens: empty, NULL, null (for all
                # types) — but only for UNQUOTED fields
                is_null = (ln == 0)
                for tok in (b"NULL", b"null"):
                    t = np.frombuffer(tok, dtype=np.uint8)
                    if width >= len(t):
                        m = (ln == len(t))
                        for j, b in enumerate(t):
                            m = m & (data[:, j] == b)
                        is_null = is_null | m
                valid = sel & ~(is_null & ~qm)
                c = Column(data, valid, StringType, ln.astype(jnp.int32))
                if dtype.is_string:
                    return c.mask_invalid()
                parser = castmod._DISPATCH[("string", dtype.name)]
                return parser(c, dtype)
            return fn

        fn = cached_kernel(key, make)
        cols.append(fn(raw_dev, jnp.asarray(s), jnp.asarray(ln), sel,
                       jnp.asarray(qm)))
    return ColumnarBatch(cols, sel, schema)


def device_csv_batches(files, schema: Schema, options: dict, conf,
                       metrics=None) -> Iterator[ColumnarBatch]:
    """Per-file device decode honoring the reader chunk-row bound; raises
    CsvDeviceUnsupported (caller falls back to the host reader)."""
    import jax.numpy as jnp

    from .. import config as C
    from ..ops.expressions import clear_input_file, publish_input_file

    from .scan import _opt_bool

    sep = options.get("sep", options.get("delimiter", ","))
    if not isinstance(sep, str) or len(sep.encode()) != 1:
        raise CsvDeviceUnsupported("multi-byte separator")
    sep_b = sep.encode()[0]
    header = _opt_bool(options.get("header", False))
    max_rows = min(conf.get(C.MAX_READER_BATCH_SIZE_ROWS), 1 << 20)

    try:
        for path in files:
            raw = np.fromfile(path, dtype=np.uint8)
            raw, starts, lengths, quoted = _tokenize(raw, sep_b, header)
            if raw.size >= 2**31:
                # the decode kernel carries int32 byte offsets; a bigger
                # buffer would wrap silently — host reader handles it
                raise CsvDeviceUnsupported(">2 GiB file offsets")
            rows, ncols = starts.shape
            if rows and ncols != len(schema):
                # single empty-string column: an empty line is one empty
                # field
                raise CsvDeviceUnsupported(
                    f"found {ncols} fields, expected {len(schema)}")
            if not rows:
                starts = np.zeros((0, len(schema)), np.int64)
                lengths = np.zeros((0, len(schema)), np.int64)
            publish_input_file(path)
            raw_dev = jnp.asarray(raw)
            off = 0
            while off < rows or (rows == 0 and off == 0):
                hi = min(off + max_rows, rows)
                qchunk = quoted[off:hi] if quoted is not None else None
                with named_range("scan_decode", metrics, MN.SCAN_TIME,
                                 rows=hi - off):
                    batch = _decode_chunk(raw_dev, starts[off:hi],
                                          lengths[off:hi], schema, conf,
                                          qchunk)
                yield batch, hi - off
                off = hi
                if rows == 0:
                    break
    finally:
        clear_input_file()
