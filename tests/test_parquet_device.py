"""Device-side parquet decode (io/parquet_device.py, VERDICT item 4):
CPU-vs-TPU oracle across encodings, codecs, page versions, nulls, and
multi-row-group files; column-granular fallback for strings."""
import sys
from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from compare import assert_rows_equal  # noqa: E402
from spark_rapids_tpu.engine import TpuSession  # noqa: E402
from spark_rapids_tpu.plan.logical import col, functions as f  # noqa: E402

WRITE_CONFS = [
    dict(compression="NONE", use_dictionary=False),
    dict(compression="NONE", use_dictionary=True),
    dict(compression="snappy", use_dictionary=True),
    dict(compression="NONE", use_dictionary=False,
         data_page_version="2.0"),
]


def _table(n=4000, seed=0, with_null=True, with_strings=True):
    rng = np.random.RandomState(seed)
    cols = {
        "i": pa.array(rng.randint(-2**31, 2**31 - 1, n), type=pa.int32()),
        "l": pa.array(rng.randint(-2**62, 2**62, n), type=pa.int64()),
        "d": pa.array(rng.uniform(-1e6, 1e6, n), type=pa.float64()),
        "f": pa.array(rng.uniform(-10, 10, n).astype(np.float32),
                      type=pa.float32()),
        "b": pa.array(rng.rand(n) < 0.5),
        "dt": pa.array([int(x) for x in rng.randint(0, 20000, n)],
                       type=pa.int32()).cast(pa.date32()),
    }
    if with_strings:
        cols["s"] = pa.array([f"s{int(x)}" for x in rng.randint(0, 50, n)])
    t = pa.table(cols)
    if with_null:
        mask = rng.rand(n) < 0.15
        t = pa.table({
            name: pa.array(
                [None if mask[i] else v
                 for i, v in enumerate(c.to_pylist())], type=c.type)
            for name, c in zip(t.column_names, t.columns)})
    return t


def _find_scan(n):
    if type(n).__name__ == "TpuFileScanExec":
        return n
    for c in n.children:
        r = _find_scan(c)
        if r:
            return r


def _roundtrip(tmp_path, write_conf, table, read_conf=None, query=None):
    p = str(tmp_path / "t.parquet")
    pq.write_table(table, p, **write_conf)

    def run(extra):
        s = TpuSession({**(read_conf or {}), **extra})
        df = s.read.parquet(p)
        if query is not None:
            df = query(df)
        return df.collect()
    tpu = run({})
    cpu = run({"spark.rapids.sql.enabled": "false"})
    assert_rows_equal(cpu, tpu, ignore_order=False, approx_float=True)
    return tpu


@pytest.mark.parametrize("wc", WRITE_CONFS,
                         ids=["plain", "dict", "snappy", "v2"])
def test_all_types_roundtrip(tmp_path, wc):
    _roundtrip(tmp_path, wc, _table())


@pytest.mark.parametrize("wc", WRITE_CONFS[:3],
                         ids=["plain", "dict", "snappy"])
def test_multi_row_group(tmp_path, wc):
    _roundtrip(tmp_path, dict(row_group_size=700, **wc), _table(n=5000))


def test_no_nulls_required_columns(tmp_path):
    _roundtrip(tmp_path, WRITE_CONFS[0], _table(with_null=False))


def test_device_decode_actually_used(tmp_path):
    """The scan metric proves the device path ran (not silently the host
    fallback)."""
    p = str(tmp_path / "t.parquet")
    pq.write_table(_table(n=500), p, compression="NONE")
    s = TpuSession()
    df = s.read.parquet(p)
    node = s.plan(df.plan)
    from spark_rapids_tpu.exec.base import ExecContext
    batches = list(node.execute(ExecContext(s.conf, runtime=s.runtime)))
    assert batches

    scan = _find_scan(node)
    # 6 numeric/bool/date columns decoded on device; strings fell back
    assert scan.metrics.values.get("numDeviceDecodedColumns", 0) >= 6


def test_conf_disables_device_decode(tmp_path):
    p = str(tmp_path / "t.parquet")
    pq.write_table(_table(n=300), p, compression="NONE")

    def run(conf):
        return TpuSession(conf).read.parquet(p).collect()
    a = run({})
    b = run({"spark.rapids.sql.format.parquet.deviceDecode.enabled":
             "false"})
    assert_rows_equal(a, b, ignore_order=False, approx_float=True)


def test_query_on_device_decoded_scan(tmp_path):
    """Q6 shape over a parquet file: filter+agg on device-decoded columns."""
    def q(df):
        return (df.filter((col("i") > 0) & col("d").is_not_null())
                .agg(f.sum(col("d")).alias("s"),
                     f.count(col("l")).alias("c")))
    _roundtrip(tmp_path, WRITE_CONFS[1], _table(n=3000, seed=3), query=q)


def test_pushdown_skips_row_groups_on_device_path(tmp_path):
    p = str(tmp_path / "t.parquet")
    t = pa.table({"k": pa.array(list(range(10000)), type=pa.int64()),
                  "v": pa.array([float(i) for i in range(10000)])})
    pq.write_table(t, p, row_group_size=1000, compression="NONE")
    s = TpuSession()
    df = s.read.parquet(p).filter(col("k") >= 9000).select(col("v"))
    node = s.plan(df.plan)
    from spark_rapids_tpu.exec.base import ExecContext
    rows = [r for b in node.execute(ExecContext(s.conf, runtime=s.runtime))
            for r in b.to_pylist()]
    assert len(rows) >= 1000  # filter applied above the scan

    scan = _find_scan(node)
    assert scan.metrics.values.get("numRowGroupsSkipped", 0) >= 8


def test_nested_columns_do_not_misalign_leaves(tmp_path):
    """Row-group metadata indexes FLATTENED leaves; a nested column before
    a selected flat column must not shift the device decoder onto the
    wrong chunk (review regression: name_to_idx vs leaf index).  The
    session schema comes from the FIRST (flat) file; the second file
    carries an extra struct whose leaves sit between a and b."""
    d = tmp_path / "data"
    d.mkdir()
    flat = pa.table({"a": pa.array([1, 2, 3], type=pa.int64()),
                     "b": pa.array([100, 200, 300], type=pa.int64())})
    nested = pa.table({
        "a": pa.array([4, 5], type=pa.int64()),
        "s": pa.array([{"x": 10, "y": 11}, {"x": 20, "y": 21}]),
        "b": pa.array([400, 500], type=pa.int64()),
    })
    pq.write_table(flat, str(d / "part-0.parquet"), compression="NONE",
                   use_dictionary=False)
    pq.write_table(nested, str(d / "part-1.parquet"), compression="NONE",
                   use_dictionary=False)
    s = TpuSession()
    rows = sorted(s.read.parquet(str(d)).select(col("a"), col("b"))
                  .collect())
    assert rows == [(1, 100), (2, 200), (3, 300), (4, 400), (5, 500)], rows


def test_dict_string_decoded_on_device(tmp_path):
    """Dictionary-encoded strings take the device path (dict parsed on
    host, index decode + gather on device); PLAIN strings fall back."""
    p = str(tmp_path / "t.parquet")
    pq.write_table(_table(n=2000, seed=5), p, compression="NONE",
                   use_dictionary=True)
    s = TpuSession()
    node = s.plan(s.read.parquet(p).plan)
    from spark_rapids_tpu.exec.base import ExecContext
    list(node.execute(ExecContext(s.conf, runtime=s.runtime)))

    scan = _find_scan(node)
    # all 7 columns (6 numeric/bool/date + the string) decoded on device
    assert scan.metrics.values.get("numDeviceDecodedColumns", 0) >= 7


def test_string_heavy_query_roundtrip(tmp_path):
    def q(df):
        return (df.filter(col("s").is_not_null())
                .group_by("s").agg(f.count(col("i")).alias("c"))
                .order_by("s"))
    for wc in (WRITE_CONFS[1], WRITE_CONFS[2]):
        _roundtrip(tmp_path, wc, _table(n=2500, seed=6), query=q)


def test_delta_binary_packed_decode(tmp_path):
    """DELTA_BINARY_PACKED int pages decode on device (host walks
    block/miniblock headers; device unpacks little-endian deltas and
    rebuilds values with one masked cumsum)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from compare import assert_rows_equal
    from spark_rapids_tpu.engine import TpuSession
    rng = np.random.RandomState(14)
    n = 5000
    vals = [None if rng.rand() < 0.1 else int(v)
            for v in rng.randint(-10**9, 10**9, n)]
    seq = list(range(n))
    p = tmp_path / "t.parquet"
    pq.write_table(pa.table({
        "a": pa.array(vals, pa.int64()),
        "seq": pa.array(seq, pa.int32())}), str(p),
        use_dictionary=False,
        column_encoding={"a": "DELTA_BINARY_PACKED",
                         "seq": "DELTA_BINARY_PACKED"},
        compression="none")

    def q(s):
        return s.read.parquet(str(p))
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    dev = TpuSession({})
    assert_rows_equal(q(cpu).collect(), q(dev).collect(),
                      ignore_order=False)
    # the device decoder actually engaged
    node = dev.plan(q(dev).plan)
    from spark_rapids_tpu.exec.base import ExecContext
    list(node.execute(ExecContext(dev.conf, runtime=dev.runtime)))
    total = [0]

    def walk(nd):
        total[0] += nd.metrics.values.get("numDeviceDecodedColumns", 0)
        for c in nd.children:
            walk(c)
    walk(node)
    assert total[0] >= 2, "delta-packed columns fell back"


def test_byte_stream_split_decode(tmp_path):
    """BYTE_STREAM_SPLIT float/double pages decode (float32 combines +
    bitcasts on device; float64 combines host-side — the emulated-f64
    bitcast carve-out)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from compare import assert_rows_equal
    from spark_rapids_tpu.engine import TpuSession
    rng = np.random.RandomState(15)
    n = 3000
    f32 = [None if rng.rand() < 0.1 else float(v)
           for v in np.round(rng.randn(n), 4).astype(np.float32)]
    f64 = [None if rng.rand() < 0.1 else float(v)
           for v in rng.randn(n) * 1e6]
    p = tmp_path / "t.parquet"
    pq.write_table(pa.table({
        "f": pa.array(f32, pa.float32()),
        "d": pa.array(f64, pa.float64())}), str(p),
        use_dictionary=False, compression="none",
        column_encoding={"f": "BYTE_STREAM_SPLIT",
                         "d": "BYTE_STREAM_SPLIT"})

    def q(s):
        return s.read.parquet(str(p))
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    dev = TpuSession({})
    assert_rows_equal(q(cpu).collect(), q(dev).collect(),
                      ignore_order=False, approx_float=True)


def test_plain_byte_array_strings_decode_on_device(tmp_path):
    """VERDICT r3 item 6: un-dictionaried (PLAIN) BYTE_ARRAY strings must
    decode device-side — host scans the length-prefixed layout into
    offsets (native pq_byte_array_scan), the device gathers the padded
    byte matrix."""
    p = str(tmp_path / "t.parquet")
    rng = np.random.RandomState(3)
    vals = [None if rng.rand() < 0.1
            else "x" * int(rng.randint(0, 40)) + str(int(x))
            for x in rng.randint(0, 10**9, 3000)]
    t = pa.table({"s": pa.array(vals), "v": rng.uniform(0, 1, 3000)})
    pq.write_table(t, p, compression="NONE", use_dictionary=False)

    s = TpuSession()
    node = s.plan(s.read.parquet(p).plan)
    from spark_rapids_tpu.exec.base import ExecContext
    batches = list(node.execute(ExecContext(s.conf, runtime=s.runtime)))
    assert batches

    scan = _find_scan(node)
    # BOTH columns device-decoded: the string column no longer falls back
    assert scan.metrics.values.get("numDeviceDecodedColumns", 0) >= 2, \
        scan.metrics.values

    got = [r[0] for b in batches for r in b.to_pylist()]
    assert got == vals


def test_mixed_plain_and_dict_string_pages(tmp_path):
    """Writers switch to PLAIN mid-column when the dictionary overflows;
    both page kinds must compose in one chunk."""
    rng = np.random.RandomState(4)
    # low-cardinality head (dictionary) then high-cardinality tail (PLAIN
    # after dict overflow, forced by a tiny dictionary_pagesize_limit)
    vals = ([f"k{int(x)}" for x in rng.randint(0, 8, 1500)]
            + [f"u{int(x)}" for x in rng.randint(0, 10**9, 1500)])
    t = pa.table({"s": pa.array(vals)})
    p = str(tmp_path / "t.parquet")
    pq.write_table(t, p, compression="NONE", use_dictionary=True,
                   dictionary_pagesize_limit=2048)

    s = TpuSession()
    got = [r[0] for r in s.read.parquet(p).collect()]
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    want = [r[0] for r in cpu.read.parquet(p).collect()]
    assert got == want == vals


def test_delta_length_byte_array_strings(tmp_path):
    """DELTA_LENGTH_BYTE_ARRAY strings decode on device: lengths through
    the DELTA_BINARY_PACKED kernel, bytes through the offset gather."""
    p = str(tmp_path / "t.parquet")
    rng = np.random.RandomState(5)
    vals = [None if rng.rand() < 0.1
            else "x" * int(rng.randint(0, 30)) + str(int(v))
            for v in rng.randint(0, 10**9, 4000)]
    t = pa.table({"s": pa.array(vals), "v": rng.uniform(0, 1, 4000)})
    pq.write_table(t, p, compression="NONE", use_dictionary=False,
                   row_group_size=900,
                   column_encoding={"s": "DELTA_LENGTH_BYTE_ARRAY",
                                    "v": "PLAIN"})
    s = TpuSession()
    node = s.plan(s.read.parquet(p).plan)
    from spark_rapids_tpu.exec.base import ExecContext
    batches = list(node.execute(ExecContext(s.conf, runtime=s.runtime)))
    got = [r[0] for b in batches for r in b.to_pylist()]
    assert got == vals

    scan = _find_scan(node)
    assert scan.metrics.values.get("numDeviceDecodedColumns", 0) >= 2, \
        scan.metrics.values  # both columns on device, zero fallbacks


def test_native_and_python_page_walks_agree(tmp_path, monkeypatch):
    """The native C++ page walk (native.pq_page_walk + pq_def_levels +
    pq_rle_decode) and the pure-python walk must produce IDENTICAL
    decoded columns — the docstring's 'mirrors the python loop' claim,
    checked byte for byte across encodings, v2 pages, compression, and
    real nulls."""
    from spark_rapids_tpu import native
    from spark_rapids_tpu.io import parquet_device as pd_mod

    confs = WRITE_CONFS + [
        dict(compression="snappy", use_dictionary=True,
             data_page_version="2.0"),
        dict(compression="snappy", use_dictionary=False,
             data_page_version="2.0"),
    ]
    for ci, wc in enumerate(confs):
        table = _table(n=3000, seed=ci, with_strings=False)
        p = str(tmp_path / f"t{ci}.parquet")
        pq.write_table(table, p, row_group_size=1200,
                       data_page_size=1 << 10, **wc)
        pf = pq.ParquetFile(p)
        from spark_rapids_tpu.columnar.batch import bucket_rows

        def decode_all():
            out = {}
            for fi, field in enumerate(pf.schema_arrow):
                rgm = pf.metadata.row_group(0)
                cm = rgm.column(fi)
                from spark_rapids_tpu.types import from_arrow
                try:
                    c = pd_mod.decode_column_chunk(
                        p, cm, cm.physical_type, from_arrow(field.type),
                        rgm.num_rows,
                        pf.schema.column(fi).max_definition_level,
                        bucket_rows(rgm.num_rows))
                except pd_mod.DeviceDecodeUnsupported:
                    continue
                out[field.name] = (np.asarray(c.data),
                                   np.asarray(c.valid))
            return out

        assert native.native_available()
        with_native = decode_all()
        assert with_native, f"conf {ci} decoded nothing on device"
        monkeypatch.setattr(native, "get_lib", lambda: None)
        try:
            pure_python = decode_all()
        finally:
            monkeypatch.undo()
        assert set(with_native) == set(pure_python), (ci, wc)
        for name in with_native:
            dn, vn = with_native[name]
            dp, vp = pure_python[name]
            np.testing.assert_array_equal(vn, vp, err_msg=f"{ci}:{name}")
            # compare VALID lanes only (dead-lane garbage may differ
            # between the assembly strategies by design)
            np.testing.assert_array_equal(dn[vn], dp[vp],
                                          err_msg=f"{ci}:{name}")


# --- string chunks of MANY pages: whole-chunk launches, no page copies ------

_STRING_LAYOUTS = {
    # name -> (write conf, distinct values the strings are drawn from)
    "dict": (dict(use_dictionary=True), 50),
    "plain": (dict(use_dictionary=False), 10**9),
    "delta_length": (dict(use_dictionary=False,
                          column_encoding={"s": "DELTA_LENGTH_BYTE_ARRAY"}),
                     10**9),
    # a dictionary that overflows: dictionary pages, then PLAIN ones
    "dict_then_plain": (dict(use_dictionary=True,
                             dictionary_pagesize_limit=2048), None),
}


def _many_page_strings(layout, nulls, n=9600, seed=11):
    """`n` strings with empty ones among them and, with `nulls`, 15% NULLs,
    a stretch of 700 NULLs and 1,600 more at the chunk's end, from a row
    where a write batch of 64 or 200 rows begins (a writer closes a page on
    its value bytes, so only there is a page all NULL whatever the
    encoding)."""
    rng = np.random.RandomState(seed)
    distinct = _STRING_LAYOUTS[layout][1]

    def long_tail(m):
        return ["u" * int(w) + str(int(x)) for w, x in
                zip(rng.randint(0, 30, m), rng.randint(0, 10**9, m))]
    if distinct is None:
        vals = ([f"k{int(x)}" for x in rng.randint(0, 8, n // 2)]
                + long_tail(n - n // 2))
    elif distinct <= 50:
        vals = [f"v{int(x)}" * (int(x) % 4)   # "" for every 4th value
                for x in rng.randint(0, distinct, n)]
    else:
        vals = long_tail(n)
    vals = ["" if rng.rand() < 0.05 else v for v in vals]
    if nulls:
        vals = [None if rng.rand() < 0.15 else v for v in vals]
        vals[1000:1700] = [None] * 700
        vals[n - 1600:] = [None] * 1600
    return vals


def _spy_assembly(monkeypatch):
    """Record (page kinds in order, non-null counts) of every chunk the
    device decode assembles."""
    from spark_rapids_tpu.io import parquet_device as pd_mod
    seen = []
    real = pd_mod._assemble_chunk

    def spy(value_pieces, *a, **kw):
        seen.append(([k for (k, _p, _n) in value_pieces],
                     [n for (_k, _p, n) in value_pieces]))
        return real(value_pieces, *a, **kw)
    monkeypatch.setattr(pd_mod, "_assemble_chunk", spy)
    return seen


@pytest.mark.parametrize("page_rows", [64, 200])
@pytest.mark.parametrize("nulls", [False, True], ids=["nonull", "nulls"])
@pytest.mark.parametrize("layout", list(_STRING_LAYOUTS))
def test_many_page_string_chunk(tmp_path, monkeypatch, layout, nulls,
                                page_rows):
    """A string chunk of at least 32 pages decodes in whole-chunk launches
    whatever its page layout: same answers as the CPU session's, and
    `scanPageCopies` does not grow with the page count (0 for a uniform
    layout, at most 2 for dictionary-then-PLAIN)."""
    vals = _many_page_strings(layout, nulls)
    p = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"s": pa.array(vals, pa.string())}), p,
                   compression="snappy", data_page_size=256,
                   write_batch_size=page_rows, **_STRING_LAYOUTS[layout][0])
    seen = _spy_assembly(monkeypatch)
    s = TpuSession()
    got = [r[0] for r in s.read.parquet(p).collect()]
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    want = [r[0] for r in cpu.read.parquet(p).collect()]
    assert got == want == vals

    [(kinds, nonnulls)] = seen
    assert len(kinds) >= 32, len(kinds)
    assert set(kinds) == {
        "dict": {"dict"}, "plain": {"plain"},
        "delta_length": {"delta_lba"},
        "dict_then_plain": {"dict", "plain"}}[layout], set(kinds)
    assert (0 in nonnulls) == nulls   # the all-NULL page
    totals = s.query_metrics_total
    assert totals["numDeviceDecodedColumns"] == 1
    assert totals.get("numDeviceDecodeErrors", 0) == 0
    assert "scanPageCopies" in totals   # present even where it is 0
    assert totals["scanPageCopies"] <= (2 if layout == "dict_then_plain"
                                        else 0)


@pytest.mark.parametrize("layout", list(_STRING_LAYOUTS))
def test_many_page_string_chunk_python_walk(tmp_path, monkeypatch, layout):
    """The same chunks through the pure-python page walk, length scan and
    run decode (no native library): same strings, no page copies."""
    from spark_rapids_tpu import native
    vals = _many_page_strings(layout, nulls=True, n=3200)
    p = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"s": pa.array(vals, pa.string())}), p,
                   compression="snappy", data_page_size=64,
                   write_batch_size=64, **_STRING_LAYOUTS[layout][0])
    monkeypatch.setattr(native, "get_lib", lambda: None)
    s = TpuSession()
    assert [r[0] for r in s.read.parquet(p).collect()] == vals
    totals = s.query_metrics_total
    assert totals["numDeviceDecodedColumns"] == 1
    assert totals["scanPageCopies"] == 0


@pytest.mark.parametrize("page_rows", [50, 100])
def test_mixed_numeric_chunk_copies_once_a_group(tmp_path, monkeypatch,
                                                 page_rows):
    """The numeric branch's count: a dictionary prefix and a PLAIN suffix
    are two page groups and two range copies whatever the page count (on
    the CPU backend numbers assemble on the host, so the device branch is
    asked for here)."""
    from spark_rapids_tpu.io import parquet_device as pd_mod
    monkeypatch.setattr(pd_mod, "_assemble_numeric_host",
                        lambda *a, **kw: None)
    rng = np.random.RandomState(1)
    vals = np.concatenate([rng.randint(0, 8, 3000),
                           rng.randint(0, 2**40, 3000)]).astype(np.int64)
    p = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"x": vals}), p, compression="NONE",
                   dictionary_pagesize_limit=1024, data_page_size=512,
                   write_batch_size=page_rows)
    seen = _spy_assembly(monkeypatch)
    s = TpuSession()
    assert [r[0] for r in s.read.parquet(p).collect()] == vals.tolist()
    [(kinds, _nonnulls)] = seen
    assert len(kinds) >= 32 and set(kinds) == {"dict", "plain"}
    assert s.query_metrics_total["scanPageCopies"] == 2


@pytest.mark.parametrize("layout", ["dict", "dict_runs", "plain",
                                    "dict_then_plain"])
def test_page_sizes_build_no_new_kernel(tmp_path, layout):
    """Two files of other page counts and page sizes whose chunks bucket
    to the same shapes: the second builds no kernel (`window_compiles`
    stays 0 when a scan meets a file written with other page sizes)."""
    from spark_rapids_tpu.utils import kernel_cache
    if layout == "dict_runs":
        # 3 distinct values: RLE and bit-packed runs alternate in a page,
        # so the index array is made on the host
        rng = np.random.RandomState(2)
        vals = [None if rng.rand() < 0.1 else "ANR"[int(x)]
                for x in rng.randint(0, 3, 9600)]
        conf = dict(use_dictionary=True)
    else:
        vals = _many_page_strings(layout, nulls=True)
        conf = _STRING_LAYOUTS[layout][0]
    table = pa.table({"s": pa.array(vals, pa.string())})

    def read(name, page_rows):
        p = str(tmp_path / name)
        pq.write_table(table, p, compression="NONE", data_page_size=64,
                       write_batch_size=page_rows, **conf)
        pages = len(_data_pages(p))
        got = [r[0] for r in TpuSession().read.parquet(p).collect()]
        assert got == vals
        return pages

    pages_a = read("a.parquet", 100)
    builds = kernel_cache.stats()["builds"]
    pages_b = read("b.parquet", 110)
    assert pages_a != pages_b and min(pages_a, pages_b) >= 32
    assert kernel_cache.stats()["builds"] == builds


def _data_pages(path):
    """The data pages of row group 0, column 0 (the native page walk)."""
    from spark_rapids_tpu import native
    from spark_rapids_tpu.io import parquet_device as pd_mod
    rgm = pq.ParquetFile(path).metadata.row_group(0)
    cm = rgm.column(0)
    start = cm.dictionary_page_offset \
        if cm.dictionary_page_offset is not None else cm.data_page_offset
    with open(path, "rb") as f:
        f.seek(start)
        raw = f.read(cm.total_compressed_size)
    pages = native.pq_page_walk(raw, rgm.num_rows)
    return [t for t in pages["ptype"]
            if int(t) in (pd_mod._DATA_PAGE, pd_mod._DATA_PAGE_V2)]
