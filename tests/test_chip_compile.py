"""Main-path programs compile for a described (not attached) TPU v5e.

The chip's compiler is installed here and compiles for a topology that is
only described, so these tests guard every PR at no chip time: what XLA:TPU
refuses (f64->int bitcasts, 64-bit all-reduces other than Sum, programs too
big for 16 GiB) fails HERE, not minutes into a chip call.  A compile that
passes is not a chip run; `chip_smoke.py` is.

How the programs are obtained: chip_smoke.py's own queries run once through
`TpuSession` on the CPU backend at the smoke's size (TPC-H SF1 widths), with
`jax.default_backend` steered to "tpu" so the engine takes its TPU branches
and `jax.jit` wrapped so every program is recorded with its argument
shapes; each test then lowers one recorded program for the described chip.

Rules this file keeps (on-chip-measurement guide, section 2): the topology
is described inside a module-scoped fixture that skips when it cannot be,
never at import; everything compiles in the test's own process; the
persistent compilation cache is off around the compiles; no other test file
describes a topology.
"""
from __future__ import annotations

import functools
import os
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest

SMOKE_ROWS = 6_000_000          # chip_smoke.LINEITEM_ROWS
HBM_BYTES = 16 << 30            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is locked
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def tpu_branches(monkeypatch):
    """Nine engine sites ask `jax.default_backend()` and would see `cpu`
    while lowering here: steer them from the test, not through an option
    of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


class _Recorded(NamedTuple):
    """One jitted program as the engine called it."""
    name: str
    jitted: object      # the jax.jit object
    args: tuple         # argument pytrees, arrays as ShapeDtypeStructs
    kwargs: dict

    def rows(self) -> int:
        dims = [x.shape[0] for x in jax.tree_util.tree_leaves(
            (self.args, self.kwargs)) if getattr(x, "shape", ())]
        return max(dims or [0])


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") and hasattr(a, "dtype") else a, tree)


def _recording_jit(records):
    real_jit = jax.jit

    class Recorder:
        def __init__(self, fun, **kw):
            inner = fun.func if isinstance(fun, functools.partial) else fun
            self._name = getattr(inner, "__qualname__", repr(inner))
            self._jit = real_jit(fun, **kw)

        def _record(self, a, k):
            records.append(_Recorded(self._name, self._jit,
                                     _shapes(a), _shapes(k)))

        def __call__(self, *a, **k):
            self._record(a, k)
            return self._jit(*a, **k)

        def trace(self, *a, **k):      # kernel_cache.stage_executable's AOT
            self._record(a, k)
            return self._jit.trace(*a, **k)

        def __getattr__(self, n):
            return getattr(self._jit, n)

    return lambda fun, **kw: Recorder(fun, **kw)


def _grouped_minmax(lineitem):
    """Few groups, min and max over doubles and a date: the bucket
    update's dense min/max reducers over f32 pairs, which Q1 lacks."""
    from spark_rapids_tpu.plan.logical import col, functions as F
    return lineitem.group_by(col("l_returnflag")).agg(
        F.min(col("l_extendedprice")).alias("min_price"),
        F.max(col("l_discount")).alias("max_disc"),
        F.min(col("l_shipdate")).alias("first_ship"))


@pytest.fixture(scope="module")
def smoke_programs(topo):
    """{query: [programs]} recorded from chip_smoke.py's q6, q1 and join
    query (q1 over five batches too, q6 and q1 through the streaming loop,
    and one grouped min/max) at SF1 widths,
    run on the CPU with the engine on its TPU branches."""
    import chip_smoke
    from benchmarks.tpch import bulk
    from spark_rapids_tpu.columnar.contiguous import pack_batch
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.utils import kernel_cache
    from spark_rapids_tpu.utils.scan_cache import MEMORY_SCAN_CACHE

    def forget_compiled():
        # kernels are cached by structural key, which does not know the
        # backend gate: CPU-branch kernels must not leak into the
        # recording, nor TPU-branch ones out of it
        kernel_cache.clear()
        jax.clear_caches()
        MEMORY_SCAN_CACHE.clear()

    records: dict = {}
    mp = pytest.MonkeyPatch()
    forget_compiled()
    # the session is made BEFORE the steering: its compile-cache gate asks
    # the backend too, and this process must not start persisting XLA:CPU
    # executables
    session = TpuSession(chip_smoke.base_conf())
    try:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        lineitem = bulk.make_lineitem(SMOKE_ROWS, seed=22,
                                      n_orders=SMOKE_ROWS // 4)
        orders = bulk.make_orders(SMOKE_ROWS // 4, seed=22)
        li, od = session.from_arrow(lineitem), session.from_arrow(orders)
        li2 = session.from_arrow(lineitem.slice(0, 2 << 20))  # two batches
        li5 = session.from_arrow(lineitem.slice(0, 5 << 20))
        for name, query in (("q6", lambda: bulk.q6(li)),
                            ("q1", lambda: bulk.q1(li)),
                            ("q1x5", lambda: bulk.q1(li5)),
                            ("minmax", lambda: _grouped_minmax(li2)),
                            ("q3_join", lambda: bulk.q3_shape(li, od))):
            records[name] = []
            mp.setattr(jax, "jit", _recording_jit(records[name]))
            assert query().collect()
        # q6 again past the whole-stage budget (half of batchSizeBytes:
        # 64 MiB against 38 MB a batch), as the SF10 table is at the
        # default: the keyless streaming loop's step program
        streaming = TpuSession({**chip_smoke.base_conf(),
                                "spark.rapids.sql.batchSizeBytes": "128m"})
        records["q6_stream"] = []
        mp.setattr(jax, "jit", _recording_jit(records["q6_stream"]))
        assert bulk.q6(streaming.from_arrow(lineitem)).collect()
        # q1 the same way: the GROUPED loop's bucket update a 1M-row batch
        # (the SF10 Q1 cell's program), its folds' merge of bucket states
        records["q1_stream"] = []
        mp.setattr(jax, "jit", _recording_jit(records["q1_stream"]))
        assert bulk.q1(streaming.from_arrow(lineitem)).collect()
        # the contiguous pack (shuffle/spill/broadcast unit) of one
        # reader batch of orders: ints, plus a double column for the
        # f32-pair branch
        from spark_rapids_tpu.columnar import ColumnarBatch
        records["pack"] = []
        mp.setattr(jax, "jit", _recording_jit(records["pack"]))
        batch = ColumnarBatch.from_arrow(
            lineitem.select(["l_orderkey", "l_shipdate",
                             "l_extendedprice"]).slice(0, 1 << 20))
        pack_batch(batch)
    finally:
        mp.undo()
        forget_compiled()
    return records


def _compile_for_chip(prog: _Recorded, sharding):
    place = functools.partial(
        jax.tree_util.tree_map,
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        if isinstance(a, jax.ShapeDtypeStruct) else a)
    compiled = prog.jitted.lower(*place(prog.args),
                                 **place(prog.kwargs)).compile()
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES
    return compiled


def _largest(programs, name_part):
    """The recorded program of that kind with the most rows: the shape
    the smoke's full-capacity batches take."""
    hits = [p for p in programs if name_part in p.name]
    assert hits, (name_part, sorted({p.name for p in programs}))
    return max(hits, key=_Recorded.rows)


@pytest.mark.parametrize("query,kernel,min_rows", [
    # scan -> filter -> aggregate over all six 1M-row batches, one program
    ("q6", "agg.whole_stage", 1 << 20),
    # the same query as the streaming loop answers it: filter, masked
    # reductions and the merge into the running 1-row state, one batch
    ("q6_stream", "agg.stream_step", 1 << 20),
    # the grouped loop over the same batches: the bucket update alone, a
    # batch a launch, its six groups in one dense pass
    ("q1_stream", "agg.hashaggregate_bucket", 1 << 20),
    # the grouped aggregate over the same batches: per batch the bucket
    # update's `while` of dense passes, f64 sums and int64 counts in its
    # carry (two string keys; as a `cond` over a dense and a scatter form
    # XLA:TPU's memory-space assignment segfaulted at 5 and 6 batches)
    ("q1", "agg.whole_stage_bucket", 1 << 20),
    ("q1x5", "agg.whole_stage_bucket", 1 << 20),
    # the same over two batches with min and max of doubles and a date
    ("minmax", "agg.whole_stage_bucket", 1 << 20),
    # the join's fused window+count kernel over a full probe batch
    ("q3_join", "join.hashjoin_probe", 1 << 20),
    # its gather: the pairs placed from the output's side (a scatter,
    # two scans, popcounts), the payload columns taken at output capacity
    ("q3_join", "join.hashjoin_gather", 1 << 20),
    # the 64-bit sort: packed u64 keys, revenue (a double: the f32-pair
    # keys, where the f64 comparator took 9 minutes) DESC then o_orderdate
    ("q3_join", "sort.sort", 1 << 20),
    # the sort-path aggregate over the join's outputs: a batch's update
    # and the fold (the states placed by their live prefixes, then the
    # merge, one program; its arguments are the 524,288-row states, its
    # merge runs at the bucket of their live rows), their segment bounds
    # read off the sorted group ids
    ("q3_join", "agg.hashaggregate_update", 1 << 19),
    ("q3_join", "agg.fold", 1 << 19),
    # the contiguous pack of a full reader batch (f64 leaves as f32 pairs)
    ("pack", "mem.contig_pack", 1 << 20),
])
def test_smoke_program_compiles_for_v5e(smoke_programs, one_chip,
                                        no_persistent_cache, tpu_branches,
                                        query, kernel, min_rows):
    prog = _largest(smoke_programs[query], kernel)
    assert prog.rows() >= min_rows, \
        f"{prog.name} recorded at {prog.rows()} rows: not the smoke's size"
    compiled = _compile_for_chip(prog, one_chip)
    if kernel == "join.hashjoin_probe":
        # the count walk is the one loop left: the candidate windows come
        # from a merge (sorts and scans), where two binary searches were
        # a `while` of 21 dependent 1M-row gathers each (PR 30)
        assert len(re.findall(r"\bwhile\(", compiled.as_text())) <= 1
    if kernel in ("join.hashjoin_gather", "agg.hashaggregate_update",
                  "agg.fold"):
        # no walk in the gather: nothing loops over the stream batch; no
        # binary search over every group id in the aggregate
        assert not re.findall(r"\bwhile\(", compiled.as_text())


def test_f64_bitcast_is_what_the_tpu_branches_avoid(one_chip,
                                                    no_persistent_cache):
    """The reason for the nine `jax.default_backend()` gates, pinned: the
    day XLA:TPU takes an f64->int bitcast this fails and they can go."""
    x = jax.ShapeDtypeStruct((1024,), jnp.float64, sharding=one_chip)
    with pytest.raises(Exception, match="X64"):
        jax.jit(lambda v: jax.lax.bitcast_convert_type(v, jnp.int64)) \
            .lower(x).compile()
