import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import (ColumnarBatch, Column, bucket_rows,
                                       concat_batches)


def test_bucket_rows():
    assert bucket_rows(1) == 1024
    assert bucket_rows(1024) == 1024
    assert bucket_rows(1025) == 2048
    assert bucket_rows(5000) == 8192


def test_from_pydict_roundtrip():
    schema = T.schema_of(a=T.IntegerType, b=T.DoubleType, s=T.StringType)
    batch = ColumnarBatch.from_pydict(
        {"a": [1, None, 3], "b": [1.5, 2.5, None], "s": ["x", None, "hello"]},
        schema)
    assert batch.capacity == 1024
    assert batch.num_rows_host() == 3
    assert batch.to_pylist() == [(1, 1.5, "x"), (None, 2.5, None),
                                 (3, None, "hello")]


def test_filter_defers_then_compacts():
    schema = T.schema_of(a=T.LongType)
    batch = ColumnarBatch.from_pydict({"a": list(range(10))}, schema)
    import jax.numpy as jnp
    keep = batch.column("a").data % 2 == 0
    filtered = batch.filter(keep)
    assert filtered.capacity == batch.capacity  # no data movement
    assert filtered.num_rows_host() == 5
    assert [r[0] for r in filtered.to_pylist()] == [0, 2, 4, 6, 8]


def test_arrow_roundtrip_with_nulls():
    tbl = pa.table({
        "i": pa.array([1, 2, None], type=pa.int32()),
        "f": pa.array([1.0, None, 3.0], type=pa.float64()),
        "s": pa.array(["a", None, "ccc"]),
        "d": pa.array([0, 1, None], type=pa.date32()),
        "t": pa.array([1000, None, 3000], type=pa.timestamp("us", tz="UTC")),
        "bl": pa.array([True, False, None]),
    })
    batch = ColumnarBatch.from_arrow(tbl)
    out = batch.to_arrow()
    assert out.column("i").to_pylist() == [1, 2, None]
    assert out.column("f").to_pylist() == [1.0, None, 3.0]
    assert out.column("s").to_pylist() == ["a", None, "ccc"]
    assert out.column("bl").to_pylist() == [True, False, None]
    assert [d.toordinal() - 719163 if d else None
            for d in out.column("d").to_pylist()] == [0, 1, None]


def test_int64_precision_survives():
    big = 2**62 + 12345
    schema = T.schema_of(a=T.LongType)
    batch = ColumnarBatch.from_pydict({"a": [big]}, schema)
    assert batch.to_pylist()[0][0] == big


def test_concat_batches():
    schema = T.schema_of(a=T.IntegerType, s=T.StringType)
    b1 = ColumnarBatch.from_pydict({"a": [1, 2], "s": ["aa", None]}, schema)
    b2 = ColumnarBatch.from_pydict({"a": [None, 4], "s": ["b", "longer-string"]},
                                   schema)
    out = concat_batches([b1, b2])
    assert out.to_pylist() == [(1, "aa"), (2, None), (None, "b"),
                               (4, "longer-string")]


def test_concat_respects_filtered_inputs():
    schema = T.schema_of(a=T.IntegerType)
    b1 = ColumnarBatch.from_pydict({"a": list(range(6))}, schema)
    b1 = b1.filter(b1.column("a").data >= 4)
    b2 = ColumnarBatch.from_pydict({"a": [100]}, schema)
    out = concat_batches([b1, b2])
    assert [r[0] for r in out.to_pylist()] == [4, 5, 100]


def test_batch_is_pytree():
    import jax
    schema = T.schema_of(a=T.IntegerType, s=T.StringType)
    batch = ColumnarBatch.from_pydict({"a": [1, 2, 3], "s": ["x", "y", None]},
                                      schema)

    @jax.jit
    def bump(b: ColumnarBatch) -> ColumnarBatch:
        c = b.column("a")
        c2 = Column(c.data + 1, c.valid, c.dtype)
        return ColumnarBatch([c2, b.column("s")], b.sel, b.schema)

    out = bump(batch)
    assert [r[0] for r in out.to_pylist()] == [2, 3, 4]


def test_string_column_padding():
    c = Column.from_strings(["abc", "a-much-longer-string"], capacity=4)
    assert c.max_len == 32
    c2 = c.pad_strings_to(64)
    assert c2.max_len == 64
    assert c2.to_pylist(2) == ["abc", "a-much-longer-string"]


_EDGE = "é" * 4                      # 8 bytes: the first max_len bucket, full
_STRING_CASES = {
    "nulls": pa.array(["a", None, "bb", None, "ccc"]),
    "empty_strings": pa.array(["", "x", "", None, ""]),
    "utf8_at_the_edge": pa.array([_EDGE, "a", None, "ü"]),
    "utf8_past_the_edge": pa.array([_EDGE + "a", "ß" * 7, None, ""]),
    "sliced": pa.array(["skip", None, "kept", "é", None, "tail" * 5]).slice(1, 4),
    "large_string": pa.array(["a", None, "long" * 9, ""], pa.large_string()),
    "large_string_sliced": pa.array(
        ["x", "yy", None, "zzz"], pa.large_string()).slice(2),
    "one_width": pa.array(["A", "N", "R", "N"] * 300),
    "one_width_sliced": pa.array(["AF", "NO", "RF"] * 50).slice(7, 101),
    "chunked": pa.chunked_array([["a", None], [], ["ccc", "dd" * 5, None]]),
    "all_null": pa.array([None, None, None], pa.string()),
    "zero_rows": pa.array([], pa.string()),
}


@pytest.mark.parametrize("case", sorted(_STRING_CASES))
def test_arrow_strings_from_buffers_equal_the_row_loop(case):
    """A string column uploaded from Arrow's buffers (offsets, data,
    validity bitmap) is, byte for byte, what `from_strings` builds from the
    same values as Python objects: the byte matrix, lengths, validity and
    its `max_len` bucket."""
    values = _STRING_CASES[case]
    batch = ColumnarBatch.from_arrow(pa.table({"s": values}))
    got, = batch.columns
    want = Column.from_strings(values.to_pylist(), capacity=batch.capacity)
    assert got.max_len == want.max_len
    for name in ("data", "lengths", "valid"):
        a, b = (np.asarray(getattr(c, name)) for c in (got, want))
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a, b), name
    assert batch.to_pylist() == [(v,) for v in values.to_pylist()]
