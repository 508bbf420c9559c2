"""File scans: Parquet / ORC / CSV into device columnar batches.

Reference behavior (structure, not code):
  * GpuParquetScan.scala:249-620 — the CPU clips row groups & columns to the
    split and rebuilds a minimal file, then the DEVICE decodes it; batches
    are bounded by reader.batchSizeRows/Bytes; schema evolution inserts
    null columns.
  * GpuOrcScan.scala:247-711 — same at stripe granularity.
  * GpuBatchScanExec.scala:309-477 — CSV split copied to host, header
    stripped, schema-directed parse.

TPU-first shape: the row-group/stripe clipping survives (that part was
always host-side footer work), but decode goes through Arrow on the host
and one H2D transfer into the bucketed `ColumnarBatch` layout.  A device
PLAIN/RLE Pallas decode path is the planned burn-down (the reference's
bring-up had the same host-decode fallback, flagged), and the host decode
is already columnar — no row materialization anywhere.
"""
from __future__ import annotations

import glob as _glob
import os
from typing import Iterator, List, Optional

from .. import config as C
from ..columnar import ColumnarBatch
from ..exec.base import CpuExec, ExecContext, TpuExec
from ..types import Schema, StructField, from_arrow, to_arrow
from ..plan import logical as L
from ..metrics import names as MN
from ..utils.tracing import named_range


# --------------------------------------------------------------------------
# path + schema discovery (driver side)
# --------------------------------------------------------------------------

def _opt_bool(v) -> bool:
    """Spark-style option parsing: the string \"false\" is False."""
    if isinstance(v, str):
        return v.strip().lower() in ("true", "1", "yes")
    return bool(v)


def expand_paths(paths) -> List[str]:
    """Expand files/dirs/globs into a sorted file list."""
    out: List[str] = []
    for p in paths:
        if isinstance(p, (list, tuple)):
            out.extend(expand_paths(p))
        elif os.path.isdir(p):
            for root, _dirs, files in os.walk(p):
                for f in sorted(files):
                    if not f.startswith((".", "_")):
                        out.append(os.path.join(root, f))
        elif any(ch in p for ch in "*?["):
            out.extend(sorted(_glob.glob(p)))
        else:
            out.append(p)
    if not out:
        raise FileNotFoundError(f"no input files for {paths}")
    return out


def _schema_from_arrow(arrow_schema) -> Schema:
    fields = []
    for f in arrow_schema:
        fields.append(StructField(f.name, from_arrow(f.type)))
    return Schema(fields)


def parquet_schema(files: List[str]) -> Schema:
    import pyarrow.parquet as pq
    return _schema_from_arrow(pq.ParquetFile(files[0]).schema_arrow)


def orc_schema(files: List[str]) -> Schema:
    from pyarrow import orc
    return _schema_from_arrow(orc.ORCFile(files[0]).schema)


def csv_schema(files: List[str], options: dict) -> Schema:
    """Infer a schema by letting Arrow parse the first file."""
    table = _read_csv_arrow(files[0], None, options)
    return _schema_from_arrow(table.schema)


def discover_partitions(base_paths, files):
    """Hive-style `name=value` directory discovery between each base path
    and its files (Spark: PartitioningAwareFileIndex; values percent-
    unescaped, `__HIVE_DEFAULT_PARTITION__` -> null, types inferred as
    int/long/double/string).  Returns (fields, {abs_file: {name: value}})."""
    import urllib.parse
    bases = []
    for p in base_paths:
        ap = os.path.abspath(str(p)).rstrip(os.sep)
        bases.append(ap if os.path.isdir(ap) else os.path.dirname(ap))
    per_file = {}
    names_order: Optional[List[str]] = None
    for f in files:
        af = os.path.abspath(f)
        base = None
        for b in sorted(bases, key=len, reverse=True):
            if af.startswith(b + os.sep) or af == b:
                base = b
                break
        raw = {}
        if base:
            rel = os.path.relpath(os.path.dirname(af), base)
            if rel != ".":
                for seg in rel.split(os.sep):
                    if "=" in seg:
                        k, v = seg.split("=", 1)
                        raw[k] = urllib.parse.unquote(v)
        per_file[af] = raw
        if raw and names_order is None:
            names_order = list(raw)
    if not names_order:
        return [], {}
    fields = []
    typed = {f: {} for f in per_file}
    for name in names_order:
        raws = [per_file[f].get(name) for f in per_file]
        dtype = _infer_partition_type(raws)
        fields.append(StructField(name, dtype))
        for f in per_file:
            typed[f][name] = _parse_partition_value(per_file[f].get(name),
                                                    dtype)
    return fields, typed


_HIVE_NULL = "__HIVE_DEFAULT_PARTITION__"


def _infer_partition_type(raws):
    from ..types import DoubleType, IntegerType, LongType, StringType
    vals = [r for r in raws if r is not None and r != _HIVE_NULL]
    if not vals:
        return StringType
    try:
        ints = [int(v) for v in vals]
        if all(-(2**31) <= i < 2**31 for i in ints):
            return IntegerType
        return LongType
    except ValueError:
        pass  # tpulint: disable=TPU006 type-inference fallthrough: not all ints, try float next
    try:
        for v in vals:
            float(v)
        return DoubleType
    except ValueError:
        return StringType


def _parse_partition_value(raw, dtype):
    if raw is None or raw == _HIVE_NULL:
        return None
    if dtype.is_integral:
        return int(raw)
    if dtype.is_floating:
        return float(raw)
    return raw


def scan_info(paths, fmt: str, options: dict,
              user_schema: Optional[Schema] = None):
    """Driver-side scan planning: expand paths, discover Hive partitions,
    build the full schema.  Returns (files, schema, options) with the
    per-file partition values stashed in options['__partitions__']."""
    files = expand_paths(paths)
    part_fields, typed = discover_partitions(paths, files)
    if user_schema is not None:
        # a user schema may name discovered partition columns: they stay
        # partition columns (sourced from the directory names, with the
        # user-declared dtype), and must not be read from the data files
        part_names = {f.name for f in part_fields}
        file_schema = Schema([f for f in user_schema.fields
                              if f.name not in part_names])
        by_name = {f.name: f for f in user_schema.fields}
        part_fields = [by_name.get(f.name, f) for f in part_fields]
        if typed and part_fields:
            typed = {fl: {k: _parse_partition_value(
                              None if v is None else str(v),
                              by_name[k].dtype) if k in by_name else v
                          for k, v in vals.items()}
                     for fl, vals in typed.items()}
    elif fmt == "parquet":
        file_schema = parquet_schema(files)
    elif fmt == "orc":
        file_schema = orc_schema(files)
    elif fmt == "csv":
        file_schema = csv_schema(files, options)
    else:
        raise NotImplementedError(fmt)
    part_fields = [f for f in part_fields
                   if f.name not in file_schema.names]
    schema = Schema(list(file_schema.fields) + part_fields)
    opts = dict(options)
    if typed and part_fields:
        keep = {f.name for f in part_fields}
        opts["__partitions__"] = {
            f: {k: v for k, v in vals.items() if k in keep}
            for f, vals in typed.items()}
    return files, schema, opts


def _read_csv_arrow(path: str, schema: Optional[Schema], options: dict):
    import pyarrow as pa
    import pyarrow.csv as pacsv
    header = _opt_bool(options.get("header", False))
    sep = options.get("sep", options.get("delimiter", ","))
    read_opts = pacsv.ReadOptions(autogenerate_column_names=not header)
    # ignore_empty_lines=False: a single-string-column table's null row is
    # written as an empty line and must survive the round trip
    parse_opts = pacsv.ParseOptions(delimiter=sep,
                                    ignore_empty_lines=False)
    # Spark CSV semantics: only empty/NULL tokens are null ("nan" is a float
    # value, not null — pyarrow's default null_values would eat it); an
    # unquoted empty field is null but a quoted "" is the empty string
    col_types = {f.name: to_arrow(f.dtype) for f in schema} \
        if schema is not None else None
    convert = pacsv.ConvertOptions(
        column_types=col_types,
        null_values=["", "NULL", "null"],
        strings_can_be_null=True,
        quoted_strings_can_be_null=False)
    table = pacsv.read_csv(path, read_options=read_opts,
                           parse_options=parse_opts,
                           convert_options=convert)
    if schema is not None:
        table = table.rename_columns([f.name for f in schema])
    return table


def _evolve(table, schema: Schema):
    """Schema evolution: reorder to `schema`, insert all-null columns for
    missing names, cast mismatched arrow types (reference:
    evolveSchemaIfNeededAndClose, GpuParquetScan.scala:502-534)."""
    import pyarrow as pa
    import pyarrow.compute as pc
    arrays = []
    for f in schema:
        at = to_arrow(f.dtype)
        if f.name in table.column_names:
            col = table.column(f.name)
            if col.type != at:
                col = pc.cast(col, at)
            arrays.append(col)
        else:
            arrays.append(pa.nulls(table.num_rows, type=at))
    return pa.table(arrays, names=schema.names)


# --------------------------------------------------------------------------
# chunked host readers (shared by the Cpu and Tpu execs; the Tpu exec adds
# the H2D edge)
# --------------------------------------------------------------------------

def _rg_can_match(rg_meta, name_to_idx: dict, predicates) -> bool:
    """Row-group min/max statistics vs pushed predicates: False = provably
    no row matches, skip the group (reference: pushed-down filters rebuilt
    against the footer, GpuParquetScan.scala:106-147).  Conservative on any
    missing/incomparable statistic."""
    for (name, op, value) in predicates:
        idx = name_to_idx.get(name)
        if idx is None:
            continue
        stats = rg_meta.column(idx).statistics
        if stats is None or not stats.has_min_max:
            continue
        lo, hi = stats.min, stats.max
        try:
            if op == "EqualTo" and (value < lo or value > hi):
                return False
            if op == "LessThan" and not (lo < value):
                return False
            if op == "LessThanOrEqual" and not (lo <= value):
                return False
            if op == "GreaterThan" and not (hi > value):
                return False
            if op == "GreaterThanOrEqual" and not (hi >= value):
                return False
        except TypeError:
            continue  # incomparable literal vs file stats: keep the group  # tpulint: disable=TPU006 conservative keep IS the handling; comparability is a static property of the query, not an anomaly
    return True


def _read_chunk(pf, chunk: List[int], columns, dump_prefix: str, seq: int):
    """Decode the clipped row groups ONCE; if debug dumping is on, persist
    the same table as a standalone parquet file for offline repro
    (spark.rapids.sql.parquet.debug.dumpPrefix; reference dumps the
    reassembled host buffer the same way)."""
    table = pf.read_row_groups(chunk, columns=columns)
    if dump_prefix:
        import pyarrow.parquet as pq
        pq.write_table(table, f"{dump_prefix}-{seq}.parquet")
    return table


def _leaf_index_map(pf) -> dict:
    """TOP-LEVEL flat column name -> LEAF column index.  Row-group chunk
    metadata (and statistics) index the FLATTENED leaves, which diverge
    from arrow's top-level field indices as soon as the file has a nested
    column — mapping by leaf path keeps flat names correct and simply
    omits nested leaves (paths with a dot)."""
    out = {}
    for i in range(len(pf.schema.names)):
        path = pf.schema.column(i).path  # dotted for nested leaves
        if "." not in path:
            out[path] = i
    return out


def _parquet_chunks(pf, max_rows: int, max_bytes: int, predicates,
                    name_to_leaf: dict, metrics):
    """Group row groups into reader-limit-bounded chunks, skipping groups
    whose statistics contradict the pushed predicates (shared by the host
    and device decode paths; reference populateCurrentBlockChunk,
    GpuParquetScan.scala:571)."""
    chunk: List[int] = []
    rows = bytes_ = 0
    for rg in range(pf.metadata.num_row_groups):
        meta = pf.metadata.row_group(rg)
        if metrics is not None:
            metrics.add(MN.NUM_ROW_GROUPS, 1)
        if predicates and not _rg_can_match(meta, name_to_leaf, predicates):
            if metrics is not None:
                metrics.add(MN.NUM_ROW_GROUPS_SKIPPED, 1)
            continue
        if chunk and (rows + meta.num_rows > max_rows
                      or bytes_ + meta.total_byte_size > max_bytes):
            yield chunk
            chunk, rows, bytes_ = [], 0, 0
        chunk.append(rg)
        rows += meta.num_rows
        bytes_ += meta.total_byte_size
    if chunk:
        yield chunk


def _iter_parquet(files, max_rows: int, max_bytes: int,
                  columns: Optional[List[str]] = None,
                  predicates=None, metrics=None, dump_prefix: str = ""):
    """Yield arrow tables bounded by reader batch limits, grouping whole row
    groups per chunk like the reference's populateCurrentBlockChunk
    (GpuParquetScan.scala:571).  Row groups whose statistics contradict the
    pushed predicates are skipped before any bytes are read."""
    import pyarrow.parquet as pq
    dump_seq = 0
    for path in files:
        pf = pq.ParquetFile(path)
        if pf.metadata.num_row_groups == 0:
            continue
        file_names = set(pf.schema_arrow.names)
        cols = [c for c in columns if c in file_names] \
            if columns is not None else None
        if cols is not None and not cols:
            cols = None  # no requested column exists: schema evolution path
        for chunk in _parquet_chunks(pf, max_rows, max_bytes, predicates,
                                     _leaf_index_map(pf), metrics):
            yield path, _read_chunk(pf, chunk, cols, dump_prefix, dump_seq)
            dump_seq += 1


def _bounds_can_match(lo, hi, op, value) -> bool:
    """min/max bounds vs one pushed predicate (False = provably dead)."""
    try:
        if op == "EqualTo" and (value < lo or value > hi):
            return False
        if op == "LessThan" and not (lo < value):
            return False
        if op == "LessThanOrEqual" and not (lo <= value):
            return False
        if op == "GreaterThan" and not (hi > value):
            return False
        if op == "GreaterThanOrEqual" and not (hi >= value):
            return False
    except TypeError:
        return True  # incomparable literal vs file data: keep the stripe
    return True


def _orc_stripe_can_match(stripe, predicates) -> bool:
    """Predicate-column min/max computed from the decoded predicate
    columns (fallback when the file has no metadata section; the primary
    path is footer stripe statistics, _orc_stats_can_match)."""
    import pyarrow.compute as pc
    for (name, op, value) in predicates:
        if name not in stripe.schema.names:
            continue
        col = stripe.column(name)
        if col.null_count == len(col):
            continue
        try:
            mm = pc.min_max(col)
            lo, hi = mm["min"].as_py(), mm["max"].as_py()
        except Exception as e:  # noqa: BLE001 — keep the stripe on any error
            # conservatively keeping the stripe is correct, but silent
            # stat failures degrade pruning to a full scan — count them
            from ..metrics.registry import count_swallowed
            count_swallowed("numScanPruneStatErrors", "spark_rapids_tpu.io",
                            "stripe min/max for predicate column %r failed "
                            "(%r); keeping the stripe", name, e)
            continue
        if lo is None or hi is None:
            continue
        if not _bounds_can_match(lo, hi, op, value):
            return False
    return True


def _orc_stats_can_match(stats_row, columns_map, predicates) -> bool:
    """Stripe-footer statistics vs pushed predicates — the reference's
    SearchArgument evaluation (OrcFilters.scala:1-194) without decoding a
    single value.  Undecidable predicates keep the stripe (safe)."""
    for (name, op, value) in predicates:
        entry = columns_map.get(name)
        if entry is None:
            continue
        cid = entry[0]
        st = stats_row[cid] if cid < len(stats_row) else None
        if st is None:
            continue
        if not _bounds_can_match(st[0], st[1], op, value):
            return False
    return True


def _iter_orc(files, max_rows: int, max_bytes: int,
              columns: Optional[List[str]] = None, predicates=None,
              metrics=None):
    """Stripe-granular ORC chunks (reference: GpuOrcScan.scala:247-711)."""
    from pyarrow import orc
    for path in files:
        of = orc.ORCFile(path)
        n = of.nstripes
        if n == 0:
            continue
        file_names = set(of.schema.names)
        cols = [c for c in columns if c in file_names] \
            if columns is not None else None
        if cols is not None and not cols:
            cols = None
        pred_cols = None
        if predicates:
            pred_cols = [nm for (nm, _, _) in predicates
                         if nm in file_names]
            pred_cols = sorted(set(pred_cols)) or None
        stats = cols_map = None
        if pred_cols:
            stats, cols_map = _orc_stats_for(path)
        chunk = []
        rows = bytes_ = 0
        for s in range(n):
            if pred_cols:
                if metrics is not None:
                    metrics.add(MN.NUM_STRIPES, 1)
                if stats is not None and s < len(stats):
                    alive = _orc_stats_can_match(stats[s], cols_map,
                                                 predicates)
                else:  # no metadata section: decode predicate cols only
                    alive = _orc_stripe_can_match(
                        of.read_stripe(s, columns=pred_cols), predicates)
                if not alive:
                    if metrics is not None:
                        metrics.add(MN.NUM_STRIPES_SKIPPED, 1)
                    continue
            stripe = of.read_stripe(s, columns=cols)
            if chunk and (rows + stripe.num_rows > max_rows
                          or bytes_ + stripe.nbytes > max_bytes):
                yield path, _concat_record_batches(chunk)
                chunk, rows, bytes_ = [], 0, 0
            chunk.append(stripe)
            rows += stripe.num_rows
            bytes_ += stripe.nbytes
        if chunk:
            yield path, _concat_record_batches(chunk)


def _orc_stats_for(path: str):
    """(stripe_stats, column_map) via the hand-rolled footer reader, or
    (None, None) when the file is outside its scope (e.g. snappy) or has
    no metadata section — the caller then probes predicate columns."""
    try:
        from .orc_device import OrcFileInfo
        fi = OrcFileInfo(path)
        return fi.stripe_stats(), fi.columns
    except Exception:
        return None, None


def _concat_record_batches(batches):
    import pyarrow as pa
    return pa.Table.from_batches(batches)


def _iter_csv(files, file_schema: Schema, options: dict, max_rows: int):
    for path in files:
        table = _read_csv_arrow(path, file_schema, options)
        off = 0
        while off < table.num_rows or (table.num_rows == 0 and off == 0):
            yield path, table.slice(off, max_rows)
            off += max_rows
            if table.num_rows == 0:
                break


def _host_chunks(fmt: str, files, schema: Schema, options: dict,
                 conf, metrics=None) -> Iterator:
    """Bounded arrow chunks, evolved to `schema` with any Hive partition
    columns (options['__partitions__']) attached as constants.

    `schema` may be column-pruned by the pushdown pass (plan/pushdown.py):
    only its names are requested from the readers, and pushed predicates
    (options['__predicates__']) skip parquet row groups by statistics."""
    import pyarrow as pa
    max_rows = min(conf.get(C.MAX_READER_BATCH_SIZE_ROWS), 1 << 20)
    max_bytes = conf.get(C.MAX_READER_BATCH_SIZE_BYTES)
    partitions = options.get("__partitions__") or {}
    part_names = {n for vals in partitions.values() for n in vals}
    file_cols = [f.name for f in schema if f.name not in part_names]
    if fmt == "parquet":
        it = _iter_parquet(files, max_rows, max_bytes, columns=file_cols,
                           predicates=options.get("__predicates__"),
                           metrics=metrics,
                           dump_prefix=conf.get(C.PARQUET_DEBUG_DUMP_PREFIX))
    elif fmt == "orc":
        it = _iter_orc(files, max_rows, max_bytes, columns=file_cols,
                       predicates=options.get("__predicates__"),
                       metrics=metrics)
    elif fmt == "csv":
        file_schema = Schema([f for f in schema
                              if f.name not in part_names])
        it = _iter_csv(files, file_schema, options, max_rows)
    else:
        raise NotImplementedError(f"scan format {fmt}")
    from ..ops.expressions import clear_input_file, publish_input_file
    try:
        for path, table in it:
            vals = partitions.get(path) \
                or partitions.get(os.path.abspath(path))
            if vals:
                for name, value in vals.items():
                    if name not in schema.names:
                        continue  # pruned partition column
                    f = schema.field(name)
                    table = table.append_column(
                        name, pa.array([value] * table.num_rows,
                                       type=to_arrow(f.dtype)))
            # provenance for input_file_name()/block expressions
            # (reference: InputFileBlockHolder.set in the readers)
            publish_input_file(path)
            yield _evolve(table, schema)
    finally:
        # past the scan (exchange, join probe, collect) the provenance is
        # undefined and Spark reports ("", -1, -1)
        clear_input_file()


# --------------------------------------------------------------------------
# execs
# --------------------------------------------------------------------------

def _device_orc_batches(path: str, schema: Schema, options: dict, conf,
                        metrics) -> Iterator[ColumnarBatch]:
    """Stripe-granular ORC decode with floats/doubles, RLEv2 ints/dates,
    strings, booleans, and timestamps on device and column-granular pyarrow fallback
    for the rest (io/orc_device.py).  The whole control plane parses
    BEFORE the first yield, so unsupported files fall back file-granularly;
    stripe predicates skip provably-dead stripes like the host reader."""
    from pyarrow import orc as paorc

    from ..columnar.batch import bucket_rows
    from ..ops.expressions import clear_input_file, publish_input_file
    from .orc_device import (OrcDeviceUnsupported, OrcFileInfo,
                             decode_column)

    info = OrcFileInfo(path)  # raises OrcDeviceUnsupported pre-yield
    predicates = options.get("__predicates__")
    of = paorc.ORCFile(path)
    file_names = set(of.schema.names)
    pred_cols = sorted({nm for (nm, _, _) in predicates or []
                        if nm in file_names}) or None
    stats = None
    if pred_cols:
        try:
            stats = info.stripe_stats()
        except Exception:
            stats = None  # stats are an optimization, never a failure
    try:
        publish_input_file(path)
        import jax.numpy as jnp
        for si in range(len(info.stripes)):
            if pred_cols:
                if metrics is not None:
                    metrics.add(MN.NUM_STRIPES, 1)
                if stats is not None and si < len(stats):
                    alive = _orc_stats_can_match(stats[si], info.columns,
                                                 predicates)
                else:  # no metadata section: decode predicate cols only
                    alive = _orc_stripe_can_match(
                        of.read_stripe(si, columns=pred_cols), predicates)
                if not alive:
                    if metrics is not None:
                        metrics.add(MN.NUM_STRIPES_SKIPPED, 1)
                    continue
            rows = info.stripes[si]["numberOfRows"]
            cap = bucket_rows(max(rows, 1))
            out_cols: dict = {}
            host_names: List[str] = []
            for f in schema:
                if f.name not in info.columns:
                    host_names.append(f.name)  # evolution: nulls via host
                    continue
                try:
                    with named_range("scan_decode", metrics, MN.SCAN_TIME,
                                     rows=rows):
                        out_cols[f.name] = decode_column(
                            info, si, f.name, f.dtype, cap)
                    if metrics is not None:
                        metrics.add(MN.NUM_DEVICE_DECODED_COLUMNS, 1)
                except OrcDeviceUnsupported:
                    host_names.append(f.name)  # expected scope fallback
                except Exception:
                    # the hand-rolled protobuf/RLEv2 parsers must never be
                    # able to fail a query the pyarrow path could read; a
                    # surprise error falls back too but is COUNTED so a
                    # regression disabling the device path stays visible
                    if metrics is not None:
                        metrics.add(MN.NUM_DEVICE_DECODE_ERRORS, 1)
                    host_names.append(f.name)
            if host_names:
                table = of.read_stripe(
                    si, columns=[n for n in host_names if n in file_names])
                host_batch = ColumnarBatch.from_arrow(
                    _evolve(table, Schema([schema.field(n)
                                           for n in host_names])),
                    capacity=cap)
                for n, c in zip(host_names, host_batch.columns):
                    out_cols[n] = c
            sel = jnp.arange(cap, dtype=jnp.int32) < rows
            if metrics is not None:
                metrics.add(MN.NUM_OUTPUT_ROWS, rows)
                metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
            yield ColumnarBatch([out_cols[f.name] for f in schema], sel,
                                schema)
    finally:
        info.close()
        clear_input_file()


def _device_parquet_batches(files, schema: Schema, options: dict, conf,
                            metrics) -> Iterator[ColumnarBatch]:
    """Parquet chunks decoded on DEVICE column-by-column
    (io/parquet_device.py); any column outside the device decoder's scope
    (strings, exotic encodings) is read for the same row groups through
    pyarrow and merged, so the fallback is column-granular.  Chunking,
    predicate skipping and partition columns mirror _iter_parquet."""
    import jax.numpy as jnp
    import pyarrow.parquet as pq
    from ..columnar import Column
    from ..columnar.batch import bucket_rows
    from .parquet_device import (DeviceDecodeUnsupported, _copy_range,
                                 decode_column_chunk)

    max_rows = min(conf.get(C.MAX_READER_BATCH_SIZE_ROWS), 1 << 20)
    max_bytes = conf.get(C.MAX_READER_BATCH_SIZE_BYTES)
    predicates = options.get("__predicates__")
    partitions = options.get("__partitions__") or {}
    part_names = {n for vals in partitions.values() for n in vals}

    chunks = _device_parquet_files(
        list(files), schema, options, conf, metrics, max_rows, max_bytes,
        predicates, partitions, part_names)
    try:
        while True:
            # one span (and the scan timer) per row-group chunk: footer and
            # page parsing, decompression, the H2D enqueues and the decode
            # dispatches; on the prefetch thread when prefetch is on
            with named_range("scan_decode", metrics, MN.SCAN_TIME):
                chunk = next(chunks, None)
            if chunk is None:
                return
            yield chunk
    finally:
        chunks.close()


def _device_parquet_files(files, schema, options, conf, metrics, max_rows,
                          max_bytes, predicates, partitions, part_names):
    """Yields (batch, num_rows, path).  The input-file provenance global
    is NOT touched here: this generator may run on the prefetch thread,
    and publish_input_file is process-global state the CONSUMER must
    sequence with its own batch handling (scan.py _batches)."""
    import jax.numpy as jnp
    import pyarrow.parquet as pq
    from ..columnar import Column
    from ..columnar.batch import bucket_rows
    from .parquet_device import (DeviceDecodeUnsupported, _copy_range,
                                 decode_column_chunk)
    for path in files:
        pf = pq.ParquetFile(path)
        if pf.metadata.num_row_groups == 0:
            continue
        name_to_leaf = _leaf_index_map(pf)
        pvals = partitions.get(path) or partitions.get(os.path.abspath(path))

        for chunk in _parquet_chunks(pf, max_rows, max_bytes, predicates,
                                     name_to_leaf, metrics):
            num_rows = sum(pf.metadata.row_group(rg).num_rows
                           for rg in chunk)
            cap = bucket_rows(max(num_rows, 1))
            out_cols: dict = {}
            host_names: List[str] = []

            def _decode_field(f):
                # the thread that launches carries the span: on the column
                # pool nothing else is open, and a launch's owner is read
                # from its CALLING thread (`pq_copy_*`, `pq_sdict`, the
                # numeric dictionary gathers' eager `jnp.take`)
                with named_range("scan_column", rows=num_rows,
                                 column=f.name):
                    return _decode_column(f)

            def _decode_column(f):
                """-> (name, Column | None, 'unsupported'|'error'|None,
                page copies); runs on the column pool — each column's
                host control plane (header walk, decompress, RLE) is
                independent, and so is its count."""
                ci = name_to_leaf[f.name]
                max_def = pf.schema.column(ci).max_definition_level
                counts = {"page_copies": 0}
                try:
                    rg_cols = []
                    for rg in chunk:
                        rgm = pf.metadata.row_group(rg)
                        rg_cols.append((decode_column_chunk(
                            path, rgm.column(ci),
                            rgm.column(ci).physical_type,
                            f.dtype, rgm.num_rows, max_def,
                            bucket_rows(max(rgm.num_rows, 1)), counts),
                            rgm.num_rows))
                    if len(rg_cols) == 1 \
                            and int(rg_cols[0][0].data.shape[0]) == cap:
                        # single-row-group chunk at matching capacity
                        # (the common layout: writer row groups ~= reader
                        # chunk budget): the decoded column IS the batch
                        # column — skip the zero-init + range copies
                        return (f.name, rg_cols[0][0], None,
                                counts["page_copies"])
                    if f.dtype.is_string:
                        width = max(c.max_len for c, _ in rg_cols)
                        rg_cols = [(c.pad_strings_to(width), nr)
                                   for c, nr in rg_cols]
                        data = jnp.zeros((cap, width), dtype=jnp.uint8)
                        lengths = jnp.zeros(cap, dtype=jnp.int32)
                    else:
                        data = jnp.zeros(cap,
                                         dtype=rg_cols[0][0].data.dtype)
                        lengths = None
                    valid = jnp.zeros(cap, dtype=jnp.bool_)
                    off = 0
                    for col, nr in rg_cols:
                        data = _copy_range(data, col.data, off, nr)
                        valid = _copy_range(valid, col.valid, off, nr)
                        if lengths is not None:
                            lengths = _copy_range(lengths, col.lengths,
                                                  off, nr)
                        off += nr
                    return (f.name, Column(data, valid, f.dtype, lengths),
                            None, counts["page_copies"])
                except DeviceDecodeUnsupported:
                    return f.name, None, "unsupported", 0
                except Exception:
                    # the hand-rolled page/run parsers must never be able
                    # to fail a query the pyarrow path could read: ANY
                    # other error also falls back, column-granular
                    return f.name, None, "error", 0

            fields = [f for f in schema
                      if f.name not in part_names and f.name in name_to_leaf]
            if len(fields) > 1:
                # column-parallel decode: the per-column host work
                # (thrift walk, decompression dispatch, RLE) overlaps
                # across the pool the way the reference's multithreaded
                # reader overlaps per-column device decode
                from .parquet_device import _column_pool
                results = list(_column_pool().map(_decode_field, fields))
            else:
                results = [_decode_field(f) for f in fields]
            for name, colv, err, copies in results:
                if colv is not None:
                    out_cols[name] = colv
                    if metrics is not None:
                        metrics.add(MN.NUM_DEVICE_DECODED_COLUMNS, 1)
                        metrics.add(MN.SCAN_PAGE_COPIES, copies)
                else:
                    if err == "error" and metrics is not None:
                        metrics.add(MN.NUM_DEVICE_DECODE_ERRORS, 1)
                    host_names.append(name)
            if host_names:
                table = pf.read_row_groups(chunk, columns=host_names)
                host_batch = ColumnarBatch.from_arrow(
                    _evolve(table, Schema([schema.field(n)
                                           for n in host_names])),
                    capacity=cap)
                for n, c in zip(host_names, host_batch.columns):
                    out_cols[n] = c
            # partition constants + schema evolution nulls
            for f in schema:
                if f.name in out_cols:
                    continue
                value = (pvals or {}).get(f.name) if f.name in part_names \
                    else None
                if f.dtype.is_string:
                    out_cols[f.name] = Column.from_strings(
                        [value] * num_rows, capacity=cap)
                else:
                    import numpy as _np
                    vals = _np.zeros(num_rows, dtype=f.dtype.np_dtype) \
                        if value is None else _np.full(
                            num_rows, value, dtype=f.dtype.np_dtype)
                    vd = _np.full(num_rows, value is not None, dtype=bool)
                    out_cols[f.name] = Column.from_numpy(
                        vals, vd, f.dtype, capacity=cap)
            sel = jnp.arange(cap, dtype=jnp.int32) < num_rows
            out_batch = ColumnarBatch([out_cols[f.name] for f in schema],
                                      sel, schema)
            out_batch.known_rows = num_rows  # from file metadata
            yield (out_batch, num_rows, path)


class TpuFileScanExec(TpuExec):
    """Device file scan (GpuFileSourceScanExec / GpuBatchScanExec
    equivalent): host footer-clipped columnar decode, one H2D per chunk."""

    def __init__(self, fmt: str, files: List[str], schema: Schema,
                 options: dict):
        super().__init__()
        self.fmt = fmt
        self.files = files
        self._schema = schema
        self.options = options

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"TpuFileScanExec[{self.fmt}, files={len(self.files)}]"

    def _host_batches(self, paths, ctx) -> Iterator[ColumnarBatch]:
        """Host decode + H2D for `paths` (the fallback tail every device
        branch shares)."""
        for table in _host_chunks(self.fmt, paths, self._schema,
                                  self.options, ctx.conf, self.metrics):
            with self.metrics.timer(MN.SCAN_TIME):
                batch = ColumnarBatch.from_arrow(table)
            self.metrics.add(MN.NUM_OUTPUT_ROWS, table.num_rows)
            self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
            yield batch

    def _batches(self, ctx) -> Iterator[ColumnarBatch]:
        if self.fmt == "orc" and ctx.conf.get(C.ORC_DEVICE_DECODE) \
                and not self.options.get("__partitions__"):
            from .orc_device import OrcDeviceUnsupported
            for path in self.files:
                try:
                    yield from _device_orc_batches(
                        path, self._schema, self.options, ctx.conf,
                        self.metrics)
                except OrcDeviceUnsupported:
                    yield from self._host_batches([path], ctx)
            return
        if self.fmt == "csv" and ctx.conf.get(C.CSV_DEVICE_DECODE) \
                and not self.options.get("__partitions__"):
            from .csv_device import CsvDeviceUnsupported, device_csv_batches
            for path in self.files:
                try:
                    # tokenization errors surface before the first yield of
                    # a file, so the fallback is file-granular
                    for batch, nrows in device_csv_batches(
                            [path], self._schema, self.options, ctx.conf,
                            self.metrics):
                        self.metrics.add(MN.NUM_OUTPUT_ROWS, nrows)
                        self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
                        self.metrics.add(MN.NUM_DEVICE_DECODED_COLUMNS,
                                         len(self._schema))
                        yield batch
                except CsvDeviceUnsupported:
                    yield from self._host_batches([path], ctx)
            return
        if self.fmt == "parquet" \
                and ctx.conf.get(C.PARQUET_DEVICE_DECODE) \
                and not ctx.conf.get(C.PARQUET_DEBUG_DUMP_PREFIX):
            it = _device_parquet_batches(
                self.files, self._schema, self.options, ctx.conf,
                self.metrics)
            depth = int(ctx.conf.get(C.SCAN_PREFETCH_DEPTH))
            if depth > 0:
                # decode chunk N+1's host control plane while the device
                # consumes chunk N (the reference's MULTITHREADED reader;
                # the H2D transfer over the host link pipelines against
                # the next chunk's decode)
                from ..utils.prefetch import PrefetchIterator
                it = PrefetchIterator(it, depth)
            from ..ops.expressions import (clear_input_file,
                                           publish_input_file)
            try:
                for batch, nrows, path in it:
                    # provenance publishes on the CONSUMER thread, in
                    # batch order (the producer runs ahead of us);
                    # nrows comes from file metadata — never a sync
                    publish_input_file(path)
                    self.metrics.add(MN.NUM_OUTPUT_ROWS, nrows)
                    self.metrics.add(MN.NUM_OUTPUT_BATCHES, 1)
                    yield batch
            finally:
                clear_input_file()
                if hasattr(it, "close"):
                    # an early-stopping consumer (LIMIT) must unpark the
                    # prefetch thread and close the source generator
                    it.close()
            return
        yield from self._host_batches(self.files, ctx)

    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from ..exec.base import record_cost
        produced = False
        for batch in self._batches(ctx):
            produced = True
            # roofline: every scan batch crossed the host->device link
            # and landed in HBM, whichever decode branch produced it
            # (device_size_bytes is shape metadata, never a sync)
            sz = batch.device_size_bytes()
            record_cost(self.metrics, h2d=sz, hbm_written=sz)
            yield batch
        if not produced:
            yield ColumnarBatch.from_pydict(
                {f.name: [] for f in self._schema}, self._schema)


class CpuFileScanExec(CpuExec):
    """Host fallback scan producing arrow tables."""

    def __init__(self, fmt: str, files: List[str], schema: Schema,
                 options: dict):
        super().__init__()
        self.fmt = fmt
        self.files = files
        self._schema = schema
        self.options = options

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"CpuFileScanExec[{self.fmt}, files={len(self.files)}]"

    def execute_cpu(self, ctx: ExecContext):
        produced = False
        for table in _host_chunks(self.fmt, self.files, self._schema,
                                  self.options, ctx.conf, self.metrics):
            produced = True
            yield table
        if not produced:
            import pyarrow as pa
            yield pa.table({f.name: pa.nulls(0, type=to_arrow(f.dtype))
                            for f in self._schema})


def make_scan_exec(plan: "L.LogicalScan", on_tpu: bool, conf):
    files = plan.source if isinstance(plan.source, list) \
        else expand_paths([plan.source])
    cls = TpuFileScanExec if on_tpu else CpuFileScanExec
    return cls(plan.fmt, files, plan.schema, plan.options)
