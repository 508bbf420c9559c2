"""The grouped aggregate's bucket update: dense passes over each batch's
own hash ids, against the sort path and the CPU executors."""
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.plan.logical import col, functions as f, lit

from compare import assert_rows_equal
from test_aggregate import FLOAT_AGG, _find_agg


# ---- the bucket update: dense passes over the batch's own hash ids ----------

def _narrow_ids(h1):
    """A 10-bit stand-in for TpuHashAggregateExec._bucket_ids: two keys of
    one id are then a matter of a few thousand tries, not of 2**31."""
    import jax.numpy as jnp
    return (h1 & jnp.uint64(1023)).astype(jnp.int32)


def _ids_of(values, dtype, fold=TpuHashAggregateExec._bucket_ids):
    """Hash id of each key value, as _bucket_update_kernel folds it."""
    import numpy as np
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.ops.hashing import hash_columns_double
    b = ColumnarBatch.from_pydict(
        {"k": list(values)}, T.Schema([T.StructField("k", dtype)]))
    h1, _ = hash_columns_double([b.column(0)], b.sel)
    return np.asarray(fold(h1))[:len(values)].tolist()


def _int_keys_of_distinct_ids(n):
    ids = _ids_of(range(n), T.LongType)
    assert len(set(ids)) == n, "two of the first integers share an id"
    return list(range(n))


def _two_int_keys_of_one_id():
    """A genuine collision of the 31-bit fold: among 2**18 consecutive
    integers some 16 pairs are expected."""
    first = {}
    for v, i in enumerate(_ids_of(range(2 ** 18), T.LongType)):
        if i in first:
            return first[i], v
        first[i] = v
    raise AssertionError("no collision")


_NAN = float("nan")
_G = TpuHashAggregateExec._DENSE_GROUPS
_B = TpuHashAggregateExec._BUCKETS
_BATCH = 64          # rows a reader batch in these cases


def _numeric_aggs(df, tag=""):
    """Count, Sum, Average, Min, Max over a long and a double column."""
    return df.agg(f.count(lit(1)).alias("c" + tag),
                  f.count(col("d")).alias("cd"),
                  f.sum(col("v")).alias("sv"),
                  f.sum(col("d")).alias("sd"),
                  f.avg(col("d")).alias("ad"),
                  f.min(col("v")).alias("mnv"),
                  f.max(col("v")).alias("mxv"),
                  f.min(col("d")).alias("mnd"),
                  f.max(col("d")).alias("mxd"))


def _rows_for(keys_per_batch, key_dtype=T.LongType, batch=_BATCH):
    """One batch of `batch` rows per key list, keys cycling; v and d carry
    nulls, NaN, +-0.0 and both signs."""
    import random
    rng = random.Random(7)
    k, v, d = [], [], []
    for keys in keys_per_batch:
        for i in range(batch):
            k.append(keys[i % len(keys)])
            v.append(None if i % 11 == 3 else rng.randint(-10**6, 10**6))
            d.append(rng.choice([None, _NAN, 0.0, -0.0])
                     if i % 7 == 2 else rng.uniform(-1e3, 1e3))
    schema = T.Schema([T.StructField("k", key_dtype),
                       T.StructField("v", T.LongType),
                       T.StructField("d", T.DoubleType)])
    return {"k": k, "v": v, "d": d}, schema


def _case_one_group():
    data, schema = _rows_for([[5]] * 3)
    return dict(data=data, schema=schema, dense=[1, 1, 1],
                q=lambda df: _numeric_aggs(df.group_by("k")))


def _case_exactly_g_groups():
    keys = _int_keys_of_distinct_ids(_G)
    data, schema = _rows_for([keys, keys])
    return dict(data=data, schema=schema, dense=[1, 1],
                q=lambda df: _numeric_aggs(df.group_by("k")))


def _case_g_plus_one_groups():
    """Batch 1 holds G + 1 groups and takes a second pass, batch 2 holds
    G."""
    keys = _int_keys_of_distinct_ids(_G + 1)
    data, schema = _rows_for([keys, keys[:-1]])
    return dict(data=data, schema=schema, dense=[0, 1],
                q=lambda df: _numeric_aggs(df.group_by("k")))


def _case_two_full_passes():
    """Batch 1 holds 2 G groups, a row each: two full passes and no third;
    batch 2 one pass and a part of the next."""
    keys = _int_keys_of_distinct_ids(2 * _G)
    assert len(keys) == _BATCH
    data, schema = _rows_for([keys, keys[:_G + 5]])
    return dict(data=data, schema=schema, dense=[0, 0],
                q=lambda df: _numeric_aggs(df.group_by("k")))


def _case_two_keys_in_one_bucket():
    """Dirty: two integers whose 31-bit ids agree, so only the exact key
    compare tells them apart.  The bucket program's answer is dropped, the
    sort program answers and the kernel key is latched (own aliases: a key
    of its own)."""
    a, b = _two_int_keys_of_one_id()
    data, schema = _rows_for([[a, b], [a]])
    return dict(data=data, schema=schema, dense=[-1, 1], dirty=True,
                q=lambda df: _numeric_aggs(df.group_by("k"), tag="_dirty"))


def _case_null_nan_negzero_keys():
    keys = [None, _NAN, 0.0, -0.0, 1.5, -1.5]
    data, schema = _rows_for([keys, keys], key_dtype=T.DoubleType)
    # 0.0 and -0.0 are one group: 5 groups
    return dict(data=data, schema=schema, dense=[1, 1],
                q=lambda df: _numeric_aggs(df.group_by("k")))


def _case_string_keys_unequal_length():
    """Keys of one width bucket whose padded bytes agree and only the
    length differs ("a" / "a\\0" / "a\\0\\0"), the empty string and null;
    a second key column makes it a two-key probe."""
    keys = ["a", "a\x00", "a\x00\x00", "", None, "ab", "abcdefg", "b"]
    data, schema = _rows_for([keys, keys[:3]], key_dtype=T.StringType)
    data["k2"] = [i % 2 for i in range(len(data["k"]))]
    schema = T.Schema(list(schema) + [T.StructField("k2", T.IntegerType)])
    return dict(data=data, schema=schema, dense=[1, 1],
                q=lambda df: _numeric_aggs(df.group_by("k", "k2")))


def _case_string_keys_same_bytes_one_bucket():
    """Two keys of one id whose padded bytes agree and whose lengths
    differ: only the length compare tells them apart, and it must (dirty).
    Such a pair is one in 2**31 under the kernel's fold, so the case runs
    under _narrow_ids."""
    import itertools
    import string
    stems = ["".join(p) for p in itertools.product(
        string.ascii_letters + string.digits, repeat=2)]
    longer = [x + "\x00" for x in stems]
    hits = [(x, y) for x, y, ix, iy in zip(
        stems, longer, _ids_of(stems, T.StringType, _narrow_ids),
        _ids_of(longer, T.StringType, _narrow_ids)) if ix == iy]
    assert hits, "no stem shares an id with its zero-padded twin"
    data, schema = _rows_for([list(hits[0]), ["zz"]],
                             key_dtype=T.StringType)
    return dict(data=data, schema=schema, dense=[-1, 1], dirty=True,
                fold=_narrow_ids,
                q=lambda df: _numeric_aggs(df.group_by("k"), tag="_len"))


def _case_all_dead_batch():
    """The filter leaves batch 2 without a live row."""
    data, schema = _rows_for([[1, 2, 3], [4], [1, 2]])
    data["keep"] = [not (_BATCH <= i < 2 * _BATCH)
                    for i in range(3 * _BATCH)]
    schema = T.Schema(list(schema) + [T.StructField("keep", T.BooleanType)])
    return dict(data=data, schema=schema, dense=[1, 1, 1],
                q=lambda df: _numeric_aggs(
                    df.filter(col("keep")).group_by("k")))


def _case_all_nan_and_no_valid_groups():
    """Group 1 all NaN, group 2 no valid value, group 3 NaN among numbers
    and both zeros, group 4 plain."""
    k, v, d = [], [], []
    for i in range(2 * _BATCH):
        g = i % 4 + 1
        k.append(g)
        v.append(None if g == 2 else i - 50)
        d.append({1: _NAN, 2: None,
                  3: [_NAN, 0.0, -0.0, 2.5, -7.0][i // 4 % 5],
                  4: float(i) - 31.5}[g])
    schema = T.Schema([T.StructField("k", T.LongType),
                       T.StructField("v", T.LongType),
                       T.StructField("d", T.DoubleType)])
    return dict(data={"k": k, "v": v, "d": d}, schema=schema,
                dense=[1, 1],
                q=lambda df: _numeric_aggs(df.group_by("k")))


def _case_rollup_171_groups():
    """The rollup report's shape: two string keys that the subtotals
    leave NULL and the grouping id, 171 groups a batch: six passes."""
    cats = ["category%02d" % i for i in range(10)]
    classes = ["class%02d" % i for i in range(16)]
    groups = ([(c, s, 0) for c in cats for s in classes]
              + [(c, None, 1) for c in cats] + [(None, None, 3)])
    assert len(groups) == 171
    data, schema = _rows_for([list(range(171))] * 2, batch=256)
    rows = [groups[i] for i in data.pop("k")]
    data["cat"], data["cls"], data["gid"] = map(list, zip(*rows))
    schema = T.Schema([T.StructField("cat", T.StringType),
                       T.StructField("cls", T.StringType),
                       T.StructField("gid", T.IntegerType)]
                      + list(schema)[1:])
    return dict(data=data, schema=schema, batch=256, dense=[0, 0],
                state_rows=[171, 171],
                q=lambda df: _numeric_aggs(df.group_by("cat", "cls", "gid")))


def _case_exactly_b_groups():
    """As many groups as the state holds: clean at the last pass."""
    keys = _int_keys_of_distinct_ids(_B)
    data, schema = _rows_for([keys, keys[:_G]], batch=_B)
    return dict(data=data, schema=schema, batch=_B, dense=[0, 1],
                state_rows=[_B, _G],
                q=lambda df: _numeric_aggs(df.group_by("k")))


def _case_b_plus_one_groups():
    """One group more than the state holds: dirty after the last pass, the
    sort program answers, the key is latched."""
    keys = _int_keys_of_distinct_ids(_B + 1)
    data, schema = _rows_for([keys, keys[:3]], batch=2 * _B)
    return dict(data=data, schema=schema, batch=2 * _B, dense=[-1, 1],
                state_rows=[_B, 3], dirty=True,
                q=lambda df: _numeric_aggs(df.group_by("k"), tag="_over"))


def _case_high_cardinality_bails_after_one_pass():
    """60,000 groups a batch: the first pass reads that from the ids it
    found and the loop ends there, with one pass's groups in the state."""
    keys = list(range(60000))
    data, schema = _rows_for([keys], batch=65536)
    return dict(data=data, schema=schema, batch=65536, dense=[-1],
                state_rows=[_G], dirty=True,
                q=lambda df: _numeric_aggs(df.group_by("k"), tag="_many"))


_BUCKET_CASES = {
    "one_group": _case_one_group,
    "exactly_G_groups": _case_exactly_g_groups,
    "G_plus_one_groups": _case_g_plus_one_groups,
    "two_full_passes": _case_two_full_passes,
    "two_keys_in_one_bucket": _case_two_keys_in_one_bucket,
    "null_nan_negzero_keys": _case_null_nan_negzero_keys,
    "string_keys_unequal_length": _case_string_keys_unequal_length,
    "string_keys_same_bytes_one_bucket":
        _case_string_keys_same_bytes_one_bucket,
    "all_dead_batch": _case_all_dead_batch,
    "all_nan_and_no_valid_groups": _case_all_nan_and_no_valid_groups,
    "rollup_171_groups": _case_rollup_171_groups,
    "exactly_B_groups": _case_exactly_b_groups,
    "B_plus_one_groups": _case_b_plus_one_groups,
    "high_cardinality_bails_after_one_pass":
        _case_high_cardinality_bails_after_one_pass,
}


@pytest.mark.parametrize("path", ["whole_stage", "streaming", "kernel"])
@pytest.mark.parametrize("case", list(_BUCKET_CASES))
def test_bucket_update_dense_passes(case, path, monkeypatch):
    """_bucket_update_kernel in one pass and in more against the sort
    path's _update_kernel on the same batches (`kernel`) and against the
    CPU executors (ops/cpu_eval.py) through the whole-stage program and
    the streaming loop; `took` / aggDenseBatches count the batches of one
    pass, aggBucketBatches those of any number."""
    import jax
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec import aggregate as A
    from spark_rapids_tpu.exec.base import ExecContext
    c = _BUCKET_CASES[case]()
    if "fold" in c:
        monkeypatch.setattr(TpuHashAggregateExec, "_bucket_ids",
                            staticmethod(c["fold"]))
    conf = {**FLOAT_AGG, "spark.rapids.sql.reader.batchSizeRows":
            str(c.get("batch", _BATCH))}
    if path == "streaming":
        conf["spark.rapids.sql.tpu.wholeStage.enabled"] = "false"
    s = TpuSession(conf)
    query = c["q"](s.from_pydict(c["data"], c["schema"]))
    dirty_before = set(A._BUCKET_DIRTY_KEYS)
    try:
        if path == "kernel":
            agg = _find_agg(s.plan(query.plan))
            batches = list(agg.children[0].execute(
                ExecContext(s.conf, runtime=s.runtime)))
            assert len(batches) == len(c["dense"])
            bucket = jax.jit(agg._bucket_update_kernel)
            result = jax.jit(lambda st: agg._finalize_kernel(
                agg._merge_kernel(st)))
            state_rows = c.get("state_rows", [None] * len(batches))
            for b, want, rows in zip(batches, c["dense"], state_rows):
                took, bstate = bucket(b)
                assert int(took) == want
                assert bstate.capacity == agg._BUCKETS
                if rows is not None:
                    # the groups found are the state's first rows, G a
                    # pass: their number is the passes that ran
                    sel = bstate.sel.tolist()
                    assert sel == [True] * rows + [False] * (_B - rows)
                if want >= 0:
                    assert_rows_equal(
                        result(agg._update_kernel(b)).to_pylist(),
                        result(bstate).to_pylist())
            return
        tpu = query.collect()
        cpu = c["q"](TpuSession(
            {**conf, "spark.rapids.sql.enabled": "false"}).from_pydict(
                c["data"], c["schema"])).collect()
        assert_rows_equal(cpu, tpu)
        counted = s.query_metrics_total.get("aggDenseBatches", 0)
        taken = s.query_metrics_total.get("aggBucketBatches", 0)
        by_sort = s.query_metrics_total.get("aggSortPathBatches", 0)
        latched = A._BUCKET_DIRTY_KEYS - dirty_before
        if c.get("dirty"):
            # the whole-stage program drops every batch's bucket state;
            # the loop stops probing at the first dirty batch
            assert counted == 0 and taken == 0 and len(latched) == 1
            assert by_sort == len(c["dense"])
        else:
            assert counted == sum(c["dense"]) and not latched
            assert taken == len(c["dense"]) and by_sort == 0
    finally:
        A._BUCKET_DIRTY_KEYS.intersection_update(dirty_before)


def test_bucket_update_counts_a_row_of_the_largest_id(monkeypatch):
    """The fold reaches the id the dead rows carry; a live row that folds
    to it is clamped one below and still counted."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec.base import ExecContext
    largest = 2 ** 31 - 1
    assert int(TpuHashAggregateExec._bucket_ids(
        jnp.uint64(2 ** 64 - 1))) == largest
    monkeypatch.setattr(
        TpuHashAggregateExec, "_bucket_ids",
        staticmethod(lambda h1: jnp.full(h1.shape, largest, jnp.int32)))
    data, schema = _rows_for([[5]])
    data["keep"] = [i % 3 != 0 for i in range(_BATCH)]
    schema = T.Schema(list(schema) + [T.StructField("keep", T.BooleanType)])
    s = TpuSession(FLOAT_AGG)
    query = _numeric_aggs(s.from_pydict(data, schema).filter(col("keep"))
                          .group_by("k"), tag="_top")
    agg = _find_agg(s.plan(query.plan))
    [b] = agg.children[0].execute(ExecContext(s.conf, runtime=s.runtime))
    took, bstate = jax.jit(agg._bucket_update_kernel)(b)
    assert int(took) == 1
    result = jax.jit(lambda st: agg._finalize_kernel(agg._merge_kernel(st)))
    [row] = result(bstate).to_pylist()
    assert row[:2] == (5, sum(data["keep"]))       # k, count(*)
    assert_rows_equal(result(agg._update_kernel(b)).to_pylist(), [row])


def _per_row_indexed(jaxpr, cap):
    """Ops of this jaxpr that scatter, gather or dynamic-slice with an
    index operand of at least `cap` elements; a count, not a timing.
    Nested jits and the bodies of loops and conds are walked into."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name.startswith(("scatter", "gather", "dynamic_slice",
                            "dynamic_update_slice")) \
                and any(v.aval.size >= cap for v in eqn.invars[1:]
                        if v.aval.dtype.kind in "iu"):
            found.append(name)
        for p in eqn.params.values():
            for inner in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += _per_row_indexed(inner, cap)
    return found


def test_bucket_update_indexes_nothing_per_row():
    """Q1's aggregate at cap 4,096: the bucket update holds no scatter,
    gather or dynamic_slice over cap indices, in its loop or outside it;
    the sort path's update of the same batch holds them (so the walker
    sees what it is meant to see)."""
    import jax
    from benchmarks.tpch import bulk
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec.base import ExecContext
    cap = 4096
    s = TpuSession({**FLOAT_AGG,
                    "spark.rapids.sql.reader.batchSizeRows": str(cap)})
    q1 = bulk.q1(s.from_arrow(bulk.make_lineitem(cap, seed=3)))
    agg = _find_agg(s.plan(q1.plan))
    [batch] = agg.children[0].execute(ExecContext(s.conf,
                                                  runtime=s.runtime))
    assert batch.capacity == cap
    bucket = jax.make_jaxpr(agg._bucket_update_kernel)(batch).jaxpr
    assert any(e.primitive.name == "while" for e in bucket.eqns)
    assert not _per_row_indexed(bucket, cap)
    sort = jax.make_jaxpr(agg._update_kernel)(batch).jaxpr
    per_row = _per_row_indexed(sort, cap)
    assert "gather" in per_row and \
        any(n.startswith("scatter") for n in per_row), per_row
