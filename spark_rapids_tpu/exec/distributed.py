"""Planner-integrated SPMD execs: aggregate / join / sort over a device mesh.

These are the physical operators the `distribute` planner pass
(plan/transitions.py) swaps in when `spark.rapids.sql.tpu.mesh.devices` > 1:
a planned DataFrame query then executes its shuffle-shaped subtrees as ONE
compiled SPMD program over a `jax.sharding.Mesh`, with repartitioning as XLA
all-to-all collectives over ICI.

Reference analogue: the shuffle manager being THE execution path for every
exchange (rapids/RapidsShuffleInternalManager.scala:73-170,
rapids/GpuShuffleExchangeExec.scala:60-155).  The TPU-native design needs no
separate exchange operator: partial-agg -> all-to-all -> merge (etc.) fuse
into one XLA program per subtree, so the "exchange" is a collective the
compiler schedules, not a materialization boundary.

Input staging is STREAMED (VERDICT r3 item 4): the child iterator is staged
in chunks of spark.rapids.sql.tpu.mesh.inputChunkRows rows; aggregates keep
a mesh-resident compacted partial state merged chunk-by-chunk, and joins
keep the exchanged build side resident while probe chunks stream through —
peak memory is one chunk plus the resident state, never the whole input.
Sort still stages its full input (sampled range bounds need a complete
pass).  Results are yielded as globally-sharded batches; downstream
single-chip operators (and D2H) consume the global view.
"""
from __future__ import annotations

from typing import Optional

import jax

from ..columnar import ColumnarBatch, concat_batches
from ..columnar.batch import bucket_rows
from ..parallel.mesh import DATA_AXIS, make_mesh, shard_batch
from ..parallel.distributed import (run_distributed_aggregate,
                                    run_distributed_aggregate_streaming,
                                    run_distributed_join,
                                    run_distributed_join_streaming,
                                    run_distributed_sort)
from ..utils.tracing import named_range
from .aggregate import TpuHashAggregateExec
from .base import ExecContext, record_cost, record_output_batch
from .join import TpuHashJoinExec, _empty_batch
from .sort import TpuSortExec
from ..metrics import names as MN


def resolve_mesh(conf) -> Optional["jax.sharding.Mesh"]:
    """Mesh from session conf, or None when disabled.

    `spark.rapids.sql.tpu.mesh.devices` = 0 disables; N > 1 requires N
    devices (power of two, so sharded capacities divide evenly) and
    raises when fewer exist: a mesh that was asked for never quietly
    becomes a single-chip run."""
    from .. import config as C
    from ..parallel.mesh import init_distributed
    n = conf.get(C.MESH_DEVICES)
    if n is None or int(n) <= 1:
        return None
    n = int(n)
    if n & (n - 1):
        raise ValueError(f"{C.MESH_DEVICES.key} must be a power of two, "
                         f"got {n}")
    # multi-host: join the coordination service BEFORE enumerating devices
    # so jax.devices() is the global pod list (no-op without a coordinator)
    init_distributed(conf)
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"{C.MESH_DEVICES.key}={n} but jax reports {have} "
            f"{jax.devices()[0].platform} device(s)")
    return make_mesh(n)


def _ici_declarer(metrics):
    """The SPMD drivers' `on_exchange`: every row exchange they dispatch
    (overflow retries included: those bytes moved too) declares its
    interconnect bytes on the operator's metrics, from host-known metadata
    (parallel/distributed.exchange_ici_bytes), as exec/exchange.py does for
    the generic exchange."""
    return lambda nbytes: record_cost(metrics, ici=nbytes)


def _stage_chunk(batches, mesh, min_cap: int):
    """Concat a LIST of batches to one shardable batch and mesh it."""
    n = mesh.shape[DATA_AXIS]
    if len(batches) == 1 and batches[0].capacity % n == 0 \
            and batches[0].capacity >= min_cap:
        big = batches[0]
    else:
        total = sum(b.num_rows_host() for b in batches)
        cap = max(bucket_rows(max(total, 1)), min_cap, n)
        big = concat_batches(batches, capacity=cap)
    return shard_batch(big, mesh)


def _drain_to_sharded(child, ctx: ExecContext, mesh, min_cap: int):
    """Drain a child exec into ONE row-sharded batch (or None if empty)."""
    batches = [b for b in child.execute(ctx) if b is not None]
    if not batches:
        return None
    return _stage_chunk(batches, mesh, min_cap)


def _sharded_chunks(child, ctx: ExecContext, mesh, min_cap: int,
                    chunk_rows: int):
    """Stream a child exec as row-sharded CHUNKS of at most ~chunk_rows
    rows each (VERDICT r3 item 4: the input is never concatenated whole on
    the host; peak staging is one chunk)."""
    pending, rows = [], 0
    for b in child.execute(ctx):
        if b is None:
            continue
        pending.append(b)
        rows += b.num_rows_host()
        if rows >= chunk_rows:
            yield _stage_chunk(pending, mesh, min_cap)
            pending, rows = [], 0
    if pending:
        yield _stage_chunk(pending, mesh, min_cap)


class TpuDistributedAggregateExec(TpuHashAggregateExec):
    """SPMD hash aggregate: local partial-agg -> compact all-to-all by key
    hash -> merge -> finalize, one compiled program (parallel/distributed.py
    distributed_aggregate_step)."""

    def __init__(self, grouping, group_names, aggregates, child, mesh,
                 use_allgather: bool = False):
        super().__init__(grouping, group_names, aggregates, child)
        self.mesh = mesh
        self.use_allgather = use_allgather

    def describe(self):
        return (f"TpuDistributedAggregateExec[n="
                f"{self.mesh.shape[DATA_AXIS]}]")

    def execute(self, ctx: ExecContext):
        from .. import config as C
        n = self.mesh.shape[DATA_AXIS]
        chunk_rows = max(int(ctx.conf.get(C.MESH_INPUT_CHUNK_ROWS)), n)
        chunks = _sharded_chunks(self.children[0], ctx, self.mesh, n,
                                 chunk_rows)
        with named_range("dist_agg", self.metrics,
                         MN.DISTRIBUTED_AGG_TIME):
            out = run_distributed_aggregate_streaming(
                self, self.mesh, chunks, use_allgather=self.use_allgather,
                cache_key=self.kernel_key(),
                on_exchange=_ici_declarer(self.metrics))
        if out is None:
            # delegate empty-input semantics (global 1-row / grouped none)
            yield from super().execute(ctx)
            return
        record_output_batch(self.metrics, out, ctx.runtime)
        yield out


class TpuDistributedJoinExec(TpuHashJoinExec):
    """SPMD hash join: both sides hash-partitioned by join key over the mesh
    in one all-to-all, local sort+merge join per device."""

    def __init__(self, left, right, join_type, left_keys, right_keys,
                 condition, out_schema, using_drop, mesh,
                 use_allgather: bool = False):
        super().__init__(left, right, join_type, left_keys, right_keys,
                         condition, out_schema, using_drop)
        self.mesh = mesh
        self.use_allgather = use_allgather

    def describe(self):
        return (f"TpuDistributedJoinExec[{self.join_type}, n="
                f"{self.mesh.shape[DATA_AXIS]}]")

    def execute(self, ctx: ExecContext):
        from .. import config as C
        n = self.mesh.shape[DATA_AXIS]
        chunk_rows = max(int(ctx.conf.get(C.MESH_INPUT_CHUNK_ROWS)), n)
        right = _drain_to_sharded(self.children[1], ctx, self.mesh, n)
        if right is None:
            # empty build side: the single-chip kernels handle null/empty
            # semantics (left rows with no matches etc.) without a mesh
            yield from super().execute(ctx)
            return
        produced = False
        # stream the probe side: every supported join type
        # (inner/left/left_semi/left_anti) is per-left-row independent,
        # so per-chunk results compose by concatenation
        chunks = run_distributed_join_streaming(
            self, self.mesh,
            _sharded_chunks(self.children[0], ctx, self.mesh, n, chunk_rows),
            right, use_allgather=self.use_allgather,
            cache_key=self.kernel_key(),
            on_exchange=_ici_declarer(self.metrics))
        while True:
            # one span (and the timer) a chunk, closed before the yield: a
            # span held open across a yield clocks the consumer too, and
            # straddles this operator's own pull spans (`exec/base.py`)
            with named_range("dist_join", self.metrics,
                             MN.DISTRIBUTED_JOIN_TIME):
                out = next(chunks, None)
            if out is None:
                break
            produced = True
            record_output_batch(self.metrics, out, ctx.runtime)
            yield out
        if not produced:
            yield _empty_batch(self.schema)


class TpuDistributedSortExec(TpuSortExec):
    """SPMD global sort: sampled range bounds -> range-partition all-to-all
    -> local lexsort; shard order IS global order."""

    child_coalesce_goal = None  # drains + concats itself

    def __init__(self, sort_exprs, ascending, nulls_first, child, mesh,
                 use_allgather: bool = False):
        super().__init__(sort_exprs, ascending, nulls_first, child)
        self.mesh = mesh
        self.use_allgather = use_allgather

    def describe(self):
        return (f"TpuDistributedSortExec[n={self.mesh.shape[DATA_AXIS]}]")

    def execute(self, ctx: ExecContext):
        n = self.mesh.shape[DATA_AXIS]
        batch = _drain_to_sharded(self.children[0], ctx, self.mesh, n)
        if batch is None:
            return
        with named_range("dist_sort", self.metrics,
                         MN.DISTRIBUTED_SORT_TIME):
            out = run_distributed_sort(
                self.sort_exprs, self.ascending, self.nulls_first,
                self.mesh, batch, use_allgather=self.use_allgather,
                cache_key=self.kernel_key(),
                on_exchange=_ici_declarer(self.metrics))
        record_output_batch(self.metrics, out, ctx.runtime)
        yield out
