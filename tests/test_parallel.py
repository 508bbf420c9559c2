"""Multi-chip SPMD tests on the 8-virtual-CPU-device mesh.

Covers every function in spark_rapids_tpu/parallel/: mesh construction,
both exchange strategies (compact all-to-all + sel-mask all_gather),
bucketing, and the distributed aggregate / join / sort steps against
single-process numpy oracles.  (The reference has no in-tree transport
tests — SURVEY.md §4 flags that as a gap not to copy.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import Column, ColumnarBatch
from spark_rapids_tpu.ops import expressions as E
from spark_rapids_tpu.ops.aggregates import AggregateExpression
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.join import TpuHashJoinExec
from spark_rapids_tpu.exec.base import ExecNode
from spark_rapids_tpu.parallel import distributed as D
from spark_rapids_tpu.parallel.mesh import (DATA_AXIS, make_mesh,
                                            row_sharding, shard_batch)

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N_DEV, "conftest must provision 8 devices"
    return make_mesh(N_DEV)


def _int_batch(values, cap, valid=None, name="x", dtype=T.LongType):
    col = Column.from_numpy(np.asarray(values, dtype=np.int64), valid,
                            dtype, capacity=cap)
    schema = T.Schema([T.StructField(name, dtype)])
    sel = jnp.arange(cap, dtype=jnp.int32) < len(values)
    return ColumnarBatch([col], sel, schema)


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------

def test_make_mesh_and_sharding(mesh):
    assert mesh.shape[DATA_AXIS] == N_DEV
    b = _int_batch(np.arange(60), cap=64)
    sb = shard_batch(b, mesh)
    assert sb.columns[0].data.sharding.is_equivalent_to(
        row_sharding(mesh), ndim=1)
    np.testing.assert_array_equal(np.asarray(sb.columns[0].data),
                                  np.asarray(b.columns[0].data))


def test_shard_batch_rejects_indivisible(mesh):
    b = _int_batch(np.arange(10), cap=12)
    with pytest.raises(ValueError):
        shard_batch(b, mesh)


# ---------------------------------------------------------------------------
# exchanges
# ---------------------------------------------------------------------------

def _run_exchange_compact(batch, mesh, quota):
    """bucket = value % N_DEV, exchanged under shard_map."""
    def step(local):
        bucket = (local.columns[0].data % N_DEV).astype(jnp.int32)
        return D.exchange_compact(local, bucket, quota)
    fn = D.shard_map(step, mesh=mesh, in_specs=(P(DATA_AXIS),),
                     out_specs=(P(DATA_AXIS), P()))
    with mesh:
        return jax.jit(fn)(batch)


def test_exchange_compact_routes_rows(mesh):
    cap = 128
    vals = np.arange(100, dtype=np.int64)
    b = shard_batch(_int_batch(vals, cap), mesh)
    quota = 8  # local cap = 16, up to 16 rows could share a destination
    out, overflow = _run_exchange_compact(b, mesh, quota)
    assert int(overflow) == 0
    # received capacity is O(cap): n*quota per device, NOT n*cap
    per_dev = N_DEV * quota
    assert out.capacity == N_DEV * per_dev
    sel = np.asarray(out.sel)
    data = np.asarray(out.columns[0].data)
    got_all = []
    for d in range(N_DEV):
        shard = slice(d * per_dev, (d + 1) * per_dev)
        got = data[shard][sel[shard]]
        assert np.all(got % N_DEV == d), (d, got)
        got_all.extend(got.tolist())
    assert sorted(got_all) == vals.tolist()


def test_exchange_compact_detects_overflow(mesh):
    cap = 128
    vals = np.full(100, 8, dtype=np.int64)  # all rows -> device 0
    b = shard_batch(_int_batch(vals, cap), mesh)
    out, overflow = _run_exchange_compact(b, mesh, quota=2)
    assert int(overflow) > 0  # lossy: caller must retry with bigger quota


def test_exchange_compact_lossless_at_full_quota(mesh):
    cap = 128
    vals = np.full(100, 8, dtype=np.int64)  # all rows -> device 0
    b = shard_batch(_int_batch(vals, cap), mesh)
    out, overflow = _run_exchange_compact(b, mesh, quota=cap // N_DEV)
    assert int(overflow) == 0
    sel = np.asarray(out.sel)
    data = np.asarray(out.columns[0].data)
    assert sorted(data[sel].tolist()) == vals.tolist()


def test_exchange_by_bucket_equivalent(mesh):
    cap = 128
    rng = np.random.RandomState(3)
    vals = rng.randint(0, 1000, 90).astype(np.int64)
    b = shard_batch(_int_batch(vals, cap), mesh)

    def step(local):
        bucket = (local.columns[0].data % N_DEV).astype(jnp.int32)
        return D.exchange_by_bucket(local, bucket)
    fn = D.shard_map(step, mesh=mesh, in_specs=(P(DATA_AXIS),),
                     out_specs=P(DATA_AXIS))
    with mesh:
        out = jax.jit(fn)(b)
    # sel-mask path: capacity blows up to n*cap per device
    assert out.capacity == N_DEV * N_DEV * (cap // N_DEV)
    sel = np.asarray(out.sel)
    data = np.asarray(out.columns[0].data)
    per_dev = out.capacity // N_DEV
    got_all = []
    for d in range(N_DEV):
        shard = slice(d * per_dev, (d + 1) * per_dev)
        got = data[shard][sel[shard]]
        assert np.all(got % N_DEV == d)
        got_all.extend(got.tolist())
    assert sorted(got_all) == sorted(vals.tolist())


def test_key_buckets_stable_and_bounded():
    vals = np.arange(50, dtype=np.int64)
    col = Column.from_numpy(vals, None, T.LongType, capacity=64)
    live = jnp.arange(64, dtype=jnp.int32) < 50
    b1 = D.key_buckets([col], live, 8)
    b2 = D.key_buckets([col], live, 8)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    assert np.asarray(b1).min() >= 0 and np.asarray(b1).max() < 8
    # no key columns -> everything to device 0
    b0 = D.key_buckets([], live, 8)
    assert np.asarray(b0).max() == 0


def test_default_quota_properties():
    q = D.default_quota(1024, 8)
    assert q & (q - 1) == 0 and q >= 1024 // 8
    assert D.default_quota(16, 8) <= 16
    assert D.default_quota(1024, 1) == 1024


# ---------------------------------------------------------------------------
# distributed aggregate vs oracle
# ---------------------------------------------------------------------------

def _agg_exec():
    k = E.BoundReference(0, T.LongType, "k")
    v = E.BoundReference(1, T.DoubleType, "v")
    aggs = [AggregateExpression("Sum", v, output_name="sum_v"),
            AggregateExpression("Count", v, output_name="cnt"),
            AggregateExpression("Min", v, output_name="min_v"),
            AggregateExpression("Max", v, output_name="max_v")]
    return TpuHashAggregateExec([k], ["k"], aggs, ExecNode())


def _kv_batch(keys, vals, cap, kvalid=None, vvalid=None):
    schema = T.Schema([T.StructField("k", T.LongType),
                       T.StructField("v", T.DoubleType)])
    cols = [Column.from_numpy(np.asarray(keys, np.int64), kvalid, T.LongType,
                              capacity=cap),
            Column.from_numpy(np.asarray(vals, np.float64), vvalid,
                              T.DoubleType, capacity=cap)]
    sel = jnp.arange(cap, dtype=jnp.int32) < len(keys)
    return ColumnarBatch(cols, sel, schema)


def _agg_oracle(keys, vals, kvalid, vvalid):
    """groupby k: sum(v), count(v), min(v), max(v) with None-key group."""
    groups = {}
    for i in range(len(keys)):
        k = int(keys[i]) if kvalid is None or kvalid[i] else None
        g = groups.setdefault(k, [])
        if vvalid is None or vvalid[i]:
            g.append(float(vals[i]))
    out = {}
    for k, vs in groups.items():
        out[k] = (sum(vs) if vs else None, len(vs),
                  min(vs) if vs else None, max(vs) if vs else None)
    return out


@pytest.mark.parametrize("seed,nulls", [(0, False), (1, True), (2, True)])
def test_distributed_aggregate_matches_oracle(mesh, seed, nulls):
    rng = np.random.RandomState(seed)
    n, cap = 700, 1024
    keys = rng.randint(0, 40, n).astype(np.int64)
    vals = rng.uniform(-100, 100, n)
    kvalid = rng.uniform(size=n) > 0.1 if nulls else None
    vvalid = rng.uniform(size=n) > 0.1 if nulls else None
    batch = shard_batch(_kv_batch(keys, vals, cap, kvalid, vvalid), mesh)
    out = D.run_distributed_aggregate(_agg_exec(), mesh, batch)
    rows = out.to_pylist()
    got = {r[0]: tuple(r[1:]) for r in rows}
    want = _agg_oracle(keys, vals, kvalid, vvalid)
    assert set(got) == set(want)
    for k in want:
        ws, wc, wmn, wmx = want[k]
        gs, gc, gmn, gmx = got[k]
        assert gc == wc, k
        if ws is None:
            assert gs is None and gmn is None and gmx is None
        else:
            assert gs == pytest.approx(ws, rel=1e-9), k
            assert gmn == pytest.approx(wmn), k
            assert gmx == pytest.approx(wmx), k


def test_distributed_aggregate_allgather_fallback(mesh):
    rng = np.random.RandomState(7)
    n, cap = 300, 512
    keys = rng.randint(0, 20, n).astype(np.int64)
    vals = rng.uniform(-10, 10, n)
    batch = shard_batch(_kv_batch(keys, vals, cap), mesh)
    out = D.run_distributed_aggregate(_agg_exec(), mesh, batch,
                                      use_allgather=True)
    got = {r[0]: tuple(r[1:]) for r in out.to_pylist()}
    want = _agg_oracle(keys, vals, None, None)
    assert set(got) == set(want)
    for k in want:
        assert got[k][0] == pytest.approx(want[k][0], rel=1e-9)


def test_distributed_aggregate_step_overflow_flag(mesh):
    """quota=1 with >1 group per destination must flag overflow."""
    rng = np.random.RandomState(11)
    n, cap = 500, 512
    keys = rng.randint(0, 200, n).astype(np.int64)  # many groups
    vals = rng.uniform(size=n)
    batch = shard_batch(_kv_batch(keys, vals, cap), mesh)
    step = jax.jit(D.distributed_aggregate_step(_agg_exec(), mesh, quota=1))
    with mesh:
        _, overflow = step(batch)
    assert int(overflow) > 0


# ---------------------------------------------------------------------------
# distributed join vs oracle
# ---------------------------------------------------------------------------

def _join_exec(join_type):
    lk = E.BoundReference(0, T.LongType, "k")
    rk = E.BoundReference(0, T.LongType, "rk")
    lfields = [T.StructField("k", T.LongType), T.StructField("lv", T.LongType)]
    rfields = [T.StructField("rk", T.LongType), T.StructField("rv", T.LongType)]
    if join_type in ("left_semi", "left_anti"):
        out_schema = T.Schema(lfields)
    else:
        out_schema = T.Schema(lfields + rfields)
    return TpuHashJoinExec(ExecNode(), ExecNode(), join_type, [lk], [rk],
                           None, out_schema)


def _two_col_batch(a, b, names, cap):
    schema = T.Schema([T.StructField(names[0], T.LongType),
                       T.StructField(names[1], T.LongType)])
    cols = [Column.from_numpy(np.asarray(a, np.int64), None, T.LongType,
                              capacity=cap),
            Column.from_numpy(np.asarray(b, np.int64), None, T.LongType,
                              capacity=cap)]
    sel = jnp.arange(cap, dtype=jnp.int32) < len(a)
    return ColumnarBatch(cols, sel, schema)


def _join_oracle(lk, lv, rk, rv, join_type):
    from collections import defaultdict
    right = defaultdict(list)
    for k, v in zip(rk, rv):
        right[int(k)].append(int(v))
    rows = []
    for k, v in zip(lk, lv):
        matches = right.get(int(k), [])
        if join_type == "inner":
            rows += [(int(k), int(v), int(k), m) for m in matches]
        elif join_type == "left":
            rows += ([(int(k), int(v), int(k), m) for m in matches]
                     or [(int(k), int(v), None, None)])
        elif join_type == "left_semi":
            if matches:
                rows.append((int(k), int(v)))
        elif join_type == "left_anti":
            if not matches:
                rows.append((int(k), int(v)))
    return sorted(rows, key=lambda r: tuple((x is None, x) for x in r))


@pytest.mark.parametrize("join_type", ["inner", "left", "left_semi",
                                       "left_anti"])
def test_distributed_join_matches_oracle(mesh, join_type):
    rng = np.random.RandomState(5)
    nl, nr, cap = 400, 300, 512
    lk = rng.randint(0, 60, nl)
    lv = rng.randint(0, 1000, nl)
    rk = rng.randint(0, 80, nr)
    rv = rng.randint(0, 1000, nr)
    left = shard_batch(_two_col_batch(lk, lv, ("k", "lv"), cap), mesh)
    right = shard_batch(_two_col_batch(rk, rv, ("rk", "rv"), cap), mesh)
    join = _join_exec(join_type)
    out = D.run_distributed_join(join, mesh, left, right)
    got = sorted(out.to_pylist(),
                 key=lambda r: tuple((x is None, x) for x in r))
    want = _join_oracle(lk, lv, rk, rv, join_type)
    assert got == want


def test_distributed_join_retry_on_skew(mesh):
    """One hot key: max_dup must grow via the retry loop, result stays exact."""
    nl, nr, cap = 64, 256, 256
    lk = np.zeros(nl, dtype=np.int64)          # every left row hits the hot key
    lv = np.arange(nl, dtype=np.int64)
    rk = np.zeros(nr, dtype=np.int64)          # 256 duplicates on build side
    rv = np.arange(nr, dtype=np.int64)
    left = shard_batch(_two_col_batch(lk, lv, ("k", "lv"), cap), mesh)
    right = shard_batch(_two_col_batch(rk, rv, ("rk", "rv"), cap), mesh)
    join = _join_exec("inner")
    out = D.run_distributed_join(join, mesh, left, right, max_dup=2)
    assert len(out.to_pylist()) == nl * nr


# ---------------------------------------------------------------------------
# distributed sort vs oracle
# ---------------------------------------------------------------------------

def _sort_batch(a, b, cap, avalid=None):
    schema = T.Schema([T.StructField("a", T.LongType),
                       T.StructField("b", T.LongType)])
    cols = [Column.from_numpy(np.asarray(a, np.int64), avalid, T.LongType,
                              capacity=cap),
            Column.from_numpy(np.asarray(b, np.int64), None, T.LongType,
                              capacity=cap)]
    sel = jnp.arange(cap, dtype=jnp.int32) < len(a)
    return ColumnarBatch(cols, sel, schema)


def _global_rows(out, n_dev):
    """Live rows in shard order == claimed global order."""
    sel = np.asarray(out.sel)
    per_dev = out.capacity // n_dev
    rows = []
    cols = [np.asarray(c.data) for c in out.columns]
    valids = [np.asarray(c.valid) for c in out.columns]
    for d in range(n_dev):
        for i in range(d * per_dev, (d + 1) * per_dev):
            if sel[i]:
                rows.append(tuple(
                    int(c[i]) if v[i] else None
                    for c, v in zip(cols, valids)))
    return rows


@pytest.mark.parametrize("seed", [0, 1])
def test_distributed_sort_two_keys_with_cross_device_ties(mesh, seed):
    rng = np.random.RandomState(seed)
    n, cap = 600, 1024
    a = rng.randint(0, 5, n)   # few distinct: ties MUST colocate
    b = rng.randint(0, 10000, n)
    batch = shard_batch(_sort_batch(a, b, cap), mesh)
    exprs = [E.BoundReference(0, T.LongType, "a"),
             E.BoundReference(1, T.LongType, "b")]
    out = D.run_distributed_sort(exprs, [True, True], [True, True], mesh,
                                 batch)
    got = _global_rows(out, N_DEV)
    want = sorted(zip(a.tolist(), b.tolist()))
    assert got == [tuple(w) for w in want]


def test_distributed_sort_desc_with_nulls(mesh):
    rng = np.random.RandomState(9)
    n, cap = 500, 512
    a = rng.randint(0, 50, n)
    b = rng.randint(0, 100, n)
    avalid = rng.uniform(size=n) > 0.15
    batch = shard_batch(_sort_batch(a, b, cap, avalid=avalid), mesh)
    exprs = [E.BoundReference(0, T.LongType, "a"),
             E.BoundReference(1, T.LongType, "b")]
    # a DESC nulls last, b ASC
    out = D.run_distributed_sort(exprs, [False, True], [False, True], mesh,
                                 batch)
    got = _global_rows(out, N_DEV)
    rows = [(int(x) if ok else None, int(y))
            for x, y, ok in zip(a, b, avalid)]
    want = sorted(rows, key=lambda r: (r[0] is None,
                                       -r[0] if r[0] is not None else 0,
                                       r[1]))
    assert got == want


def test_distributed_sort_float_inf_nan_nulls(mesh):
    """Sentinel regression: ±inf data values must order correctly against
    the NaN (greatest) and null coarse-key sentinels across devices."""
    rng = np.random.RandomState(13)
    n, cap = 256, 256
    vals = rng.uniform(-100, 100, n)
    vals[:40] = np.inf
    vals[40:80] = -np.inf
    vals[80:120] = np.nan
    avalid = np.ones(n, dtype=bool)
    avalid[120:150] = False
    schema = T.Schema([T.StructField("a", T.DoubleType)])
    col = Column.from_numpy(vals, avalid, T.DoubleType, capacity=cap)
    sel = jnp.arange(cap, dtype=jnp.int32) < n
    batch = shard_batch(ColumnarBatch([col], sel, schema), mesh)
    exprs = [E.BoundReference(0, T.DoubleType, "a")]
    out = D.run_distributed_sort(exprs, [True], [True], mesh, batch)
    got = [r[0] for r in _float_rows(out, N_DEV)]
    # ascending, nulls first, NaN greatest (above +inf)
    def rank(v):
        if v is None:
            return (0, 0.0)
        if isinstance(v, float) and np.isnan(v):
            return (2, 0.0)
        return (1, v)
    want = sorted((None if not ok else float(v)
                   for v, ok in zip(vals, avalid)), key=rank)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        if w is None or (isinstance(w, float) and np.isnan(w)):
            assert (g is None) if w is None else np.isnan(g)
        else:
            assert g == w


def _float_rows(out, n_dev):
    sel = np.asarray(out.sel)
    per_dev = out.capacity // n_dev
    data = np.asarray(out.columns[0].data)
    valid = np.asarray(out.columns[0].valid)
    rows = []
    for d in range(n_dev):
        for i in range(d * per_dev, (d + 1) * per_dev):
            if sel[i]:
                rows.append((float(data[i]) if valid[i] else None,))
    return rows


def test_distributed_sort_skew_retry(mesh):
    """All rows share the first key -> one device owns everything; the quota
    retry must escalate to full capacity and still return every row."""
    n, cap = 200, 256
    a = np.full(n, 7, dtype=np.int64)
    b = np.arange(n)[::-1].astype(np.int64)
    batch = shard_batch(_sort_batch(a, b, cap), mesh)
    exprs = [E.BoundReference(0, T.LongType, "a"),
             E.BoundReference(1, T.LongType, "b")]
    out = D.run_distributed_sort(exprs, [True, True], [True, True], mesh,
                                 batch)
    got = _global_rows(out, N_DEV)
    assert got == sorted(zip(a.tolist(), b.tolist()))


class TestMultiHostInit:
    """Multi-host bring-up plumbing (parallel/mesh.py init_distributed):
    conf/env -> jax.distributed.initialize args; single-host no-op."""

    def _record(self, monkeypatch):
        calls = []
        import jax

        def fake_initialize(**kw):
            calls.append(kw)
        monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
        from spark_rapids_tpu.parallel import mesh
        monkeypatch.setattr(mesh.init_distributed, "_done", None,
                            raising=False)
        return calls

    def test_no_coordinator_is_single_host_noop(self, monkeypatch):
        calls = self._record(monkeypatch)
        monkeypatch.delenv("JAX_COORDINATOR", raising=False)
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.parallel.mesh import init_distributed
        assert init_distributed(TpuConf()) is False
        assert calls == []

    def test_conf_coordinator_joins(self, monkeypatch):
        calls = self._record(monkeypatch)
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.parallel.mesh import init_distributed
        conf = TpuConf({
            "spark.rapids.sql.tpu.mesh.coordinator": "host0:1234",
            "spark.rapids.sql.tpu.mesh.numProcesses": "4",
            "spark.rapids.sql.tpu.mesh.processId": "2"})
        assert init_distributed(conf) is True
        assert calls == [{"coordinator_address": "host0:1234",
                          "num_processes": 4, "process_id": 2}]
        # idempotent: second call does not re-initialize
        assert init_distributed(conf) is True
        assert len(calls) == 1

    def test_env_fallback(self, monkeypatch):
        calls = self._record(monkeypatch)
        monkeypatch.setenv("JAX_COORDINATOR", "envhost:9")
        monkeypatch.setenv("JAX_NUM_PROCESSES", "2")
        monkeypatch.setenv("JAX_PROCESS_ID", "1")
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.parallel.mesh import init_distributed
        assert init_distributed(TpuConf()) is True
        assert calls == [{"coordinator_address": "envhost:9",
                          "num_processes": 2, "process_id": 1}]
