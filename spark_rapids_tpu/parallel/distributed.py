"""Distributed query steps: SPMD operators over a device mesh.

The TPU-native replacement for the reference's accelerated shuffle path
(reference: rapids/shuffle/RapidsShuffleClient.scala, RapidsShuffleServer.scala,
shuffle-plugin/.../ucx/): where the reference moves device buffers peer-to-peer
over UCX/RDMA with a flatbuffers control plane and bounce-buffer pools, here a
repartition-by-key is ONE XLA collective (`all_to_all` over ICI) inside a
`shard_map`-traced program — no control plane, no staging copies, and the
compiler overlaps it with compute.

Two exchange strategies, both static-shape:

  * `exchange_compact` (default): each device compacts its live rows into a
    fixed per-destination quota block [n, q] and ONE tiled `all_to_all`
    moves exactly the owned rows — per-device traffic and received capacity
    are O(cap), independent of mesh size.  Quota overflow is *detected*
    (returned as a scalar) and the host driver retries with a doubled
    quota — the bounded-capacity + overflow-retry pattern this framework
    uses everywhere XLA's static shapes meet data-dependent sizes.
  * `exchange_by_bucket` (fallback knob): sel-mask all_gather — every device
    receives all n*cap rows with n different selection masks.  Zero overflow
    risk, linear-in-n cost; kept for tiny meshes and as the safety net.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..columnar import Column, ColumnarBatch
from ..metrics import names as MN
from ..ops.hashing import hash_columns_double
from ..utils import pow2_bucket
from .mesh import DATA_AXIS


def _all_to_all(x, axis: str):
    """Tiled all-to-all on the leading (row) axis: the array is split into
    `n` equal row blocks, block d goes to device d, received blocks are
    re-concatenated in peer order."""
    return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=True)


def default_quota(local_cap: int, n: int, factor: int = 2,
                  minimum: int = 8) -> int:
    """Per-destination row quota for exchange_compact: a power-of-two bucket
    of factor*cap/n, clamped to cap.  `factor` absorbs hash imbalance so the
    overflow-retry path stays cold."""
    want = max(minimum, factor * local_cap // max(n, 1))
    return min(pow2_bucket(want, minimum), local_cap)


def exchange_compact(batch: ColumnarBatch, bucket, quota: int,
                     axis: str = DATA_AXIS):
    """Inside shard_map: route each live row to device `bucket[row]` with a
    fixed quota of `quota` rows per destination.

    Returns (out_batch, overflow):
      * out_batch has capacity n*quota — quota rows received from each peer,
        live rows flagged by its selection mask;
      * overflow = total rows (across all devices) that exceeded their
        destination quota and were DROPPED.  overflow == 0 means lossless;
        a driver must treat overflow > 0 as a retry signal, not a result.

    Reference contract analogue: RapidsShuffleTransport.scala:38-500 moves
    partitions through bounded bounce-buffer pools with throttled receives;
    here the bound is the static quota block and the "throttle" is the
    compiled all_to_all schedule.
    """
    n = jax.lax.psum(1, axis)  # concrete: mesh size
    cap = batch.capacity
    live = batch.sel
    dest = jnp.where(live, bucket.astype(jnp.int32), n)
    # group rows by destination (stable: preserves row order within a
    # dest); this sort runs inside EVERY quota-block exchange dispatch
    from ..utils.packed_sort import stable_argsort
    order = stable_argsort(
        [(dest.astype(jnp.uint64), max(1, int(n).bit_length() + 1))], cap)
    dsorted = jnp.take(dest, order)
    start_of = jnp.searchsorted(dsorted, jnp.arange(n, dtype=jnp.int32)
                                ).astype(jnp.int32)
    pos = jnp.arange(cap, dtype=jnp.int32)
    rank = pos - jnp.take(start_of, jnp.clip(dsorted, 0, n - 1))
    fits = (dsorted < n) & (rank < quota)
    slot = jnp.where(fits, dsorted * quota + rank, n * quota)
    send_idx = jnp.full((n * quota,), cap, jnp.int32).at[slot].set(
        order, mode="drop")
    send_ok = jnp.zeros((n * quota,), jnp.bool_).at[slot].set(
        True, mode="drop")
    overflow = jnp.sum(((dsorted < n) & (rank >= quota)).astype(jnp.int32))

    def exchange_col(c: Column) -> Column:
        t = c.take(send_idx)
        if c.dtype.is_string:
            return Column(_all_to_all(t.data, axis),
                          _all_to_all(t.valid, axis), c.dtype,
                          _all_to_all(t.lengths, axis))
        return Column(_all_to_all(t.data, axis), _all_to_all(t.valid, axis),
                      c.dtype)

    cols = [exchange_col(c) for c in batch.columns]
    recv_sel = _all_to_all(send_ok, axis)
    out = ColumnarBatch(cols, recv_sel, batch.schema)
    return out, jax.lax.psum(overflow, axis)


def exchange_by_bucket(batch: ColumnarBatch, bucket, axis: str = DATA_AXIS
                       ) -> ColumnarBatch:
    """Sel-mask fallback: route each live row to device `bucket[row] % n`.

    Returns a batch of capacity n*cap whose selection mask keeps exactly the
    rows this device owns.  Since every destination receives the SAME column
    data (only the selection mask differs per destination), the data movement
    is an all_gather; only the mask needs a true all_to_all.  O(n*cap)
    received capacity — fine for small meshes, disqualifying at pod scale.
    """
    n = jax.lax.psum(1, axis)
    cap = batch.capacity
    dest = jnp.arange(n, dtype=jnp.int32)[:, None]            # [n, 1]
    sel_nd = batch.sel[None, :] & (bucket[None, :] == dest)    # [n, cap]
    recv_sel = _all_to_all(sel_nd.reshape(n * cap), axis)

    def gather(x):
        return jax.lax.all_gather(x, axis, tiled=True)

    def exchange_col(c: Column) -> Column:
        if c.dtype.is_string:
            return Column(gather(c.data), gather(c.valid), c.dtype,
                          gather(c.lengths))
        return Column(gather(c.data), gather(c.valid), c.dtype)

    cols = [exchange_col(c) for c in batch.columns]
    return ColumnarBatch(cols, recv_sel, batch.schema)


def exchange_ici_bytes(batch: ColumnarBatch, n: int,
                       rows_per_peer: int) -> int:
    """Bytes ONE row exchange of `batch`'s columns puts on the interconnect,
    from metadata alone (shapes x dtype widths, never a device read): every
    device sends `rows_per_peer` rows to each of its n-1 peers —
    exchange_compact's quota block, i.e. the all-to-all's n*quota-row
    operand x (n-1)/n; for exchange_by_bucket's all-gather the whole local
    shard, i.e. the gathered size less the device's own part.  A row's
    width is the batch's static footprint per row: data, validity, string
    lengths, and the selection mask the exchange moves with them."""
    row_bytes = batch.device_size_bytes() // max(batch.capacity, 1)
    return n * (n - 1) * rows_per_peer * row_bytes


def key_buckets(key_cols: Sequence[Column], live, n: int):
    """Owner device of each row: h1(keys) % n (dead rows -> garbage, masked
    by sel downstream)."""
    if not key_cols:
        return jnp.zeros(live.shape, dtype=jnp.int32)
    h1, _ = hash_columns_double(key_cols, live)
    return (h1 % jnp.uint64(n)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# generic exchange (shuffle/mesh_exchange.py drives this)
# ---------------------------------------------------------------------------

def append_pid_column(batch: ColumnarBatch, pids) -> ColumnarBatch:
    """Carry per-row partition ids through an exchange as a trailing
    int32 column (the receiving side needs them to serve per-partition
    reads; the exchange collectives move COLUMNS, so the ids ride as
    one)."""
    from ..types import IntegerType, Schema, StructField
    pid_col = Column(pids.astype(jnp.int32),
                     jnp.ones(batch.capacity, dtype=jnp.bool_),
                     IntegerType)
    schema = Schema(list(batch.schema) +
                    [StructField("__ici_pid__", IntegerType)])
    return ColumnarBatch(list(batch.columns) + [pid_col], batch.sel,
                         schema)


def exchange_partition_step(mesh: Mesh, num_partitions: int, pid_fn,
                            quota: int, pre=None, param_slots=None,
                            axis: str = DATA_AXIS,
                            use_allgather: bool = False):
    """The GENERIC-exchange collective (TpuShuffleExchangeExec's mesh
    lowering, shuffle/mesh_exchange.py): per device, [optional fused
    row-local chain `pre`] -> `pid_fn(local, global_start)` per-row
    partition ids over `num_partitions` -> global per-partition live-row
    counts (the AQE map statistics, computed DEVICE-side) -> ids carried
    as a trailing column through ONE tiled all-to-all routed by owner
    device `(pid * n) // num_partitions`.  Chain, partition-id compute
    and collective land in one compiled program; the data never leaves
    device memory.

    Returns fn: (row-sharded batch, start[, param values]) ->
    (exchanged batch + trailing ``__ici_pid__`` column, overflow scalar,
    per-partition global live counts).  `start` is the map task's
    round-robin offset (traced, so every map task shares one program);
    `param_slots` threads plan-cache parameter values as a trailing
    traced argument (exec/basic.bound_param_builder rationale).
    overflow > 0 means the compact quota dropped rows — the driver must
    retry with a doubled quota, exactly like every other quota-block
    exchange in this module."""
    n = mesh.shape[axis]

    def step(local: ColumnarBatch, start):
        if pre is not None:
            local = pre(local)
        base = jax.lax.axis_index(axis).astype(jnp.int32) \
            * jnp.int32(local.capacity)
        pids = pid_fn(local, start + base).astype(jnp.int32)
        counts = jnp.bincount(
            jnp.where(local.sel, pids, jnp.int32(num_partitions)),
            length=num_partitions + 1)[:num_partitions]
        counts = jax.lax.psum(counts, axis)
        owner = (pids * jnp.int32(n)) // jnp.int32(num_partitions)
        carried = append_pid_column(local, pids)
        if use_allgather:
            ex = exchange_by_bucket(carried, owner, axis)
            return ex, jnp.int32(0), counts
        ex, overflow = exchange_compact(carried, owner, quota, axis)
        return ex, overflow, counts

    if param_slots is None:
        return shard_map(step, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=(P(axis), P(), P()))
    from ..ops import expressions as PE

    def step_p(local: ColumnarBatch, start, pvals):
        with PE.bound_params(dict(zip(param_slots, pvals))):
            return step(local, start)

    return shard_map(step_p, mesh=mesh, in_specs=(P(axis), P(), P()),
                     out_specs=(P(axis), P(), P()))


# ---------------------------------------------------------------------------
# aggregate
# ---------------------------------------------------------------------------

def distributed_aggregate_step(agg, mesh: Mesh, axis: str = DATA_AXIS,
                               pre=None, quota=None,
                               use_allgather: bool = False):
    """Build the full SPMD aggregation step over a mesh.

    Per device: [optional fused filter/project `pre`] -> update-aggregate
    local rows -> all_to_all partial states by key hash -> merge-aggregate
    owned groups -> finalize.  This is the TPU equivalent of the reference's
    partial-agg -> shuffle -> final-agg stage pair (reference:
    rapids/aggregate.scala Partial/Final modes + GpuShuffleExchangeExec), as
    one compiled XLA program.

    Returns a function: globally row-sharded batch -> (row-sharded result
    batch whose live rows are each device's owned groups, overflow scalar).
    overflow > 0 means the exchange quota was exceeded: the result is
    incomplete and the caller must retry with a larger quota (see
    run_distributed_aggregate).  The sel-mask path never overflows.
    """
    n = mesh.shape[axis]
    nkeys = len(agg.grouping)

    def step(local: ColumnarBatch):
        if pre is not None:
            local = pre(local)
        state = agg._update_kernel(local)
        bucket = key_buckets(list(state.columns[:nkeys]), state.sel, n)
        if use_allgather:
            gathered = exchange_by_bucket(state, bucket, axis)
            overflow = jnp.int32(0)
        else:
            q = quota if quota is not None \
                else default_quota(state.capacity, n)
            gathered, overflow = exchange_compact(state, bucket, q, axis)
        merged = agg._merge_kernel(gathered)
        return agg._finalize_kernel(merged), overflow

    return shard_map(step, mesh=mesh, in_specs=(P(axis),),
                     out_specs=(P(axis), P()))


def _jit_step(builder, role, cache_key, *shape):
    """jit a distributed step as the program `dist.<role>`, through the
    process-wide kernel cache when the planner-integrated execs pass their
    structural key (repeated queries then reuse the compiled SPMD program
    instead of retracing); `shape` is what the step was specialised to."""
    from ..utils.kernel_cache import cached_kernel, named_jit
    if cache_key is None:
        return named_jit(builder, role)
    return cached_kernel((role,) + cache_key + shape, builder)


def run_distributed_aggregate(agg, mesh: Mesh, batch: ColumnarBatch,
                              pre=None, axis: str = DATA_AXIS,
                              use_allgather: bool = False,
                              cache_key=None) -> ColumnarBatch:
    """Host driver: run the SPMD aggregate with overflow-retry.

    Doubles the exchange quota (recompiling) until the exchange is lossless;
    terminates because quota caps at the local capacity, where every row
    fits by construction."""
    n = mesh.shape[axis]
    local_cap = batch.capacity // n
    quota = None if use_allgather else default_quota(local_cap, n)
    while True:
        step = _jit_step(
            lambda: distributed_aggregate_step(
                agg, mesh, axis=axis, pre=pre, quota=quota,
                use_allgather=use_allgather),
            "agg", cache_key, n, local_cap, quota, use_allgather)
        with mesh:
            out, overflow = step(batch)
        if use_allgather or int(overflow) == 0:
            return out
        if quota >= local_cap:  # pragma: no cover - cannot overflow at cap
            raise AssertionError("overflow with quota == local capacity")
        quota = min(local_cap, quota * 2)


# ---------------------------------------------------------------------------
# streaming aggregate (VERDICT r3 item 4: no whole-input host concat)
# ---------------------------------------------------------------------------

def _concat_local(a: ColumnarBatch, b: ColumnarBatch,
                  schema) -> ColumnarBatch:
    """Trace-safe per-device concat of two state batches (live rows stay
    wherever their sel marks them; the merge kernel keys off sel, not
    position).  Unlike columnar.concat_batches this never syncs row counts
    to the host, so it can run inside a shard_map program."""
    cols = []
    for ca, cb, f in zip(a.columns, b.columns, schema):
        if f.dtype.is_string:
            ml = max(ca.max_len, cb.max_len)
            pa_, pb = ca.pad_strings_to(ml), cb.pad_strings_to(ml)
            cols.append(Column(
                jnp.concatenate([pa_.data, pb.data], axis=0),
                jnp.concatenate([pa_.valid, pb.valid]), f.dtype,
                jnp.concatenate([pa_.lengths, pb.lengths])))
        else:
            cols.append(Column(
                jnp.concatenate([ca.data, cb.data]),
                jnp.concatenate([ca.valid, cb.valid]), f.dtype))
    sel = jnp.concatenate([a.sel, b.sel])
    return ColumnarBatch(cols, sel, schema)


def distributed_aggregate_partial_step(agg, mesh: Mesh,
                                       axis: str = DATA_AXIS, pre=None,
                                       quota=None,
                                       use_allgather: bool = False):
    """The streaming chunk step: update -> all_to_all by key hash -> merge,
    WITHOUT finalize.  Because the exchange routes every state row by key
    hash, a given group's partials land on the same device in every chunk —
    so cross-chunk merging is purely device-local (no further collective).

    Returns fn: sharded chunk -> (sharded state, overflow, max_groups)
    where max_groups is the largest per-device live-group count (for the
    host's state-compaction decision)."""
    n = mesh.shape[axis]
    nkeys = len(agg.grouping)

    def step(local: ColumnarBatch):
        if pre is not None:
            local = pre(local)
        state = agg._update_kernel(local)
        bucket = key_buckets(list(state.columns[:nkeys]), state.sel, n)
        if use_allgather:
            gathered = exchange_by_bucket(state, bucket, axis)
            overflow = jnp.int32(0)
        else:
            q = quota if quota is not None \
                else default_quota(state.capacity, n)
            gathered, overflow = exchange_compact(state, bucket, q, axis)
        merged = agg._merge_kernel(gathered)
        # int32 accumulator: under x64 a plain sum widens to int64, and
        # XLA:TPU lowers 64-bit all-reduces for Sum only (a pmax over
        # s64 is refused: "Supported lowering only of Sum all reduce")
        ng = jax.lax.pmax(jnp.sum(merged.sel, dtype=jnp.int32), axis)
        return merged, overflow, ng

    return shard_map(step, mesh=mesh, in_specs=(P(axis),),
                     out_specs=(P(axis), P(), P()))


def distributed_aggregate_combine_step(agg, mesh: Mesh,
                                       axis: str = DATA_AXIS):
    """Cross-chunk state merge, device-local: concat the running state with
    a chunk's partial state and re-merge.  Returns fn:
    (state, partial) -> (merged state at concat capacity, max_groups)."""
    def step(a: ColumnarBatch, b: ColumnarBatch):
        merged = agg._merge_kernel(_concat_local(a, b, agg._state_schema))
        # int32 accumulator: under x64 a plain sum widens to int64, and
        # XLA:TPU lowers 64-bit all-reduces for Sum only (a pmax over
        # s64 is refused: "Supported lowering only of Sum all reduce")
        ng = jax.lax.pmax(jnp.sum(merged.sel, dtype=jnp.int32), axis)
        return merged, ng

    return shard_map(step, mesh=mesh, in_specs=(P(axis), P(axis)),
                     out_specs=(P(axis), P()))


def distributed_shrink_step(mesh: Mesh, new_local_cap: int,
                            axis: str = DATA_AXIS):
    """Compact a state batch down to `new_local_cap` rows per device (live
    groups are front-compacted by the merge kernel, so a prefix slice is
    lossless once new_local_cap >= every device's live count)."""
    def step(state: ColumnarBatch):
        idx = jnp.arange(new_local_cap, dtype=jnp.int32)
        cols = [c.take(idx) for c in state.columns]
        return ColumnarBatch(cols, jnp.take(state.sel, idx), state.schema)

    return shard_map(step, mesh=mesh, in_specs=(P(axis),),
                     out_specs=P(axis))


def distributed_finalize_step(agg, mesh: Mesh, axis: str = DATA_AXIS):
    def step(state: ColumnarBatch):
        return agg._finalize_kernel(state)
    return shard_map(step, mesh=mesh, in_specs=(P(axis),),
                     out_specs=P(axis))


def run_distributed_aggregate_streaming(agg, mesh: Mesh, chunks,
                                        pre=None, axis: str = DATA_AXIS,
                                        use_allgather: bool = False,
                                        cache_key=None, on_exchange=None):
    """Host driver: stream sharded input chunks through the mesh.

    Per chunk: partial step (update/exchange/merge) with quota
    overflow-retry; then a device-local combine with the running state;
    then, when the running state's capacity is far above its live-group
    count, a prefix-slice compaction (one host sync per chunk reads the
    max group count).  Peak device memory is one chunk + the compacted
    state — never the whole input (reference: partial/final agg pair
    streams batches through the shuffle the same way).  Returns the
    finalized sharded result, or None for empty input."""
    from ..columnar.batch import bucket_rows
    n = mesh.shape[axis]
    state = None
    state_ng = 0
    for chunk in chunks:
        local_cap = chunk.capacity // n
        quota = None if use_allgather else default_quota(local_cap, n)
        while True:
            pstep = _jit_step(
                lambda: distributed_aggregate_partial_step(
                    agg, mesh, axis=axis, pre=pre, quota=quota,
                    use_allgather=use_allgather),
                "agg_partial", cache_key, n, local_cap, quota,
                use_allgather)
            with mesh:
                partial, overflow, ng = pstep(chunk)
            if on_exchange is not None:
                # the exchanged partial state has the result's columns
                on_exchange(exchange_ici_bytes(
                    partial, n, local_cap if use_allgather else quota))
            if use_allgather or int(overflow) == 0:
                break
            quota = min(local_cap, quota * 2)
        if state is None:
            state, state_ng = partial, int(ng)
        else:
            a_cap = state.capacity // n
            b_cap = partial.capacity // n
            cstep = _jit_step(
                lambda: distributed_aggregate_combine_step(agg, mesh, axis),
                "agg_combine", cache_key, n, a_cap, b_cap)
            with mesh:
                state, ng = cstep(state, partial)
            state_ng = int(ng)
        # compact: keep the state near its live size so capacity doesn't
        # grow with chunk COUNT when the group count is small
        state_local = state.capacity // n
        target = bucket_rows(max(state_ng, 1))
        if target < state_local:
            sstep = _jit_step(
                lambda: distributed_shrink_step(mesh, target, axis),
                "agg_shrink", cache_key, n, state_local, target)
            with mesh:
                state = sstep(state)
    if state is None:
        return None
    fstep = _jit_step(lambda: distributed_finalize_step(agg, mesh, axis),
                      "agg_final", cache_key, n, state.capacity // n)
    with mesh:
        return fstep(state)


# ---------------------------------------------------------------------------
# join
# ---------------------------------------------------------------------------

def distributed_join_step(join, mesh: Mesh, max_dup: int, out_cap: int,
                          quota_left: int, quota_right: int,
                          axis: str = DATA_AXIS,
                          use_allgather: bool = False):
    """SPMD hash join: hash-partition both sides by join key, local
    sort+merge join per device (the reference pairs
    GpuShuffleExchangeExec with GpuShuffledHashJoinExec the same way;
    GpuShuffledHashJoinExec.scala:83-87).

    Static knobs (bounded-capacity + overflow-retry, see module docstring):
      * quota_left/right — exchange quotas per side;
      * max_dup  — widest candidate hash window the probe loop scans;
      * out_cap  — output slot count per device (inner/left only).

    Returns fn: (left_sharded, right_sharded) ->
        (out_batch, left_overflow, right_overflow, dup_overflow,
         cap_overflow)
    where the four scalars flag which knob was too small (0 = fine).
    """
    n = mesh.shape[axis]

    def step(lleft: ColumnarBatch, lright: ColumnarBatch):
        lkey_cols = [e.eval(lleft) for e in join.left_keys]
        rkey_cols = [e.eval(lright) for e in join.right_keys]
        lbucket = key_buckets(lkey_cols, lleft.sel, n)
        rbucket = key_buckets(rkey_cols, lright.sel, n)
        if use_allgather:
            lex = exchange_by_bucket(lleft, lbucket, axis)
            rex = exchange_by_bucket(lright, rbucket, axis)
            lovf = rovf = jnp.int32(0)
        else:
            lex, lovf = exchange_compact(lleft, lbucket, quota_left, axis)
            rex, rovf = exchange_compact(lright, rbucket, quota_right, axis)

        build, bkeys, h1s = join._build_kernel(rex)
        lo, hi, max_dup_t = join._window_kernel(lex, h1s)
        dup_overflow = jnp.maximum(max_dup_t.astype(jnp.int32) - max_dup, 0)
        counts, starts, total, hits = join._count_kernel(
            max_dup, lex, build, bkeys, lo, hi, vary_axes=(axis,))
        if join.join_type in ("left_semi", "left_anti"):
            out = join._semi_kernel(lex, counts)
            out = ColumnarBatch(out.columns, out.sel, join._schema)
            cap_overflow = jnp.int32(0)
        else:
            out = join._gather_kernel(out_cap, lex, build, lo, counts,
                                      starts, total, hits,
                                      vary_axes=(axis,))
            cap_overflow = jnp.maximum(total.astype(jnp.int32) - out_cap, 0)
        return (out, jax.lax.psum(lovf, axis), jax.lax.psum(rovf, axis),
                jax.lax.psum(dup_overflow, axis),
                jax.lax.psum(cap_overflow, axis))

    return shard_map(step, mesh=mesh, in_specs=(P(axis), P(axis)),
                     out_specs=(P(axis), P(), P(), P(), P()))


def _count_finished_chunk(join):
    """The host counters of one stream chunk through the SPMD probe."""
    join.metrics.add(MN.JOIN_MERGED_WINDOW_BATCHES, 1)
    if join.condition is not None:
        join.metrics.add(MN.JOIN_RESIDUAL_BATCHES, 1)
    if join.join_type in ("left_semi", "left_anti"):
        join.metrics.add(MN.JOIN_SEMI_BATCHES, 1)
        if join.join_type == "left_anti":
            join.metrics.add(MN.JOIN_ANTI_BATCHES, 1)
    else:
        join.metrics.add(MN.JOIN_OUTPUT_SPACE_BATCHES, 1)


def run_distributed_join(join, mesh: Mesh, left: ColumnarBatch,
                         right: ColumnarBatch, axis: str = DATA_AXIS,
                         max_dup: int = 8, out_cap=None,
                         use_allgather: bool = False,
                         cache_key=None) -> ColumnarBatch:
    """Host driver for the SPMD join with overflow-retry on all three knobs."""
    n = mesh.shape[axis]
    lcap, rcap = left.capacity // n, right.capacity // n
    quota_l = default_quota(lcap, n)
    quota_r = default_quota(rcap, n)
    # received capacities are n*quota; out_cap defaults assume modest fanout
    if out_cap is None:
        out_cap = max(n * quota_l, 1024)
    while True:
        step = _jit_step(
            lambda: distributed_join_step(
                join, mesh, max_dup, out_cap, quota_l, quota_r, axis=axis,
                use_allgather=use_allgather),
            "join", cache_key, n, lcap, rcap, max_dup, out_cap, quota_l,
            quota_r, use_allgather)
        with mesh:
            out, l_ovf, r_ovf, dup_ovf, cap_ovf = step(left, right)
        join.metrics.add(MN.JOIN_WALK_STEPS, max_dup)
        retry = False
        if not use_allgather and int(l_ovf) > 0:
            if quota_l >= lcap:  # pragma: no cover - cap always fits
                raise AssertionError("left exchange overflow at full quota")
            quota_l = min(lcap, quota_l * 2)
            retry = True
        if not use_allgather and int(r_ovf) > 0:
            if quota_r >= rcap:  # pragma: no cover - cap always fits
                raise AssertionError("right exchange overflow at full quota")
            quota_r = min(rcap, quota_r * 2)
            retry = True
        if int(dup_ovf) > 0:
            # power-of-two bucket: bounded kernel-cache keys
            max_dup = pow2_bucket(max_dup + int(dup_ovf))
            retry = True
        if int(cap_ovf) > 0:
            out_cap = out_cap * 2
            retry = True
        if not retry:
            _count_finished_chunk(join)
            return out


def distributed_join_build_exchange_step(join, mesh: Mesh, quota_right: int,
                                         axis: str = DATA_AXIS,
                                         use_allgather: bool = False):
    """Exchange the BUILD side by join-key hash once; the exchanged batch
    stays mesh-resident for every probe chunk (the reference keeps the
    built hash table across stream batches the same way,
    GpuShuffledHashJoinExec.scala:83-87)."""
    n = mesh.shape[axis]

    def step(lright: ColumnarBatch):
        rkey_cols = [e.eval(lright) for e in join.right_keys]
        rbucket = key_buckets(rkey_cols, lright.sel, n)
        if use_allgather:
            rex = exchange_by_bucket(lright, rbucket, axis)
            rovf = jnp.int32(0)
        else:
            rex, rovf = exchange_compact(lright, rbucket, quota_right, axis)
        return rex, jax.lax.psum(rovf, axis)

    return shard_map(step, mesh=mesh, in_specs=(P(axis),),
                     out_specs=(P(axis), P()))


def distributed_join_probe_step(join, mesh: Mesh, max_dup: int,
                                out_cap: int, quota_left: int,
                                axis: str = DATA_AXIS,
                                use_allgather: bool = False):
    """Per-chunk probe: exchange one STREAM-side chunk by key hash and join
    it against the resident exchanged build side.  Correct per chunk for
    inner/left/left_semi/left_anti because each left row's result depends
    only on the build side."""
    n = mesh.shape[axis]

    def step(lleft: ColumnarBatch, rex: ColumnarBatch):
        lkey_cols = [e.eval(lleft) for e in join.left_keys]
        lbucket = key_buckets(lkey_cols, lleft.sel, n)
        if use_allgather:
            lex = exchange_by_bucket(lleft, lbucket, axis)
            lovf = jnp.int32(0)
        else:
            lex, lovf = exchange_compact(lleft, lbucket, quota_left, axis)
        build, bkeys, h1s = join._build_kernel(rex)
        lo, hi, max_dup_t = join._window_kernel(lex, h1s)
        dup_overflow = jnp.maximum(max_dup_t.astype(jnp.int32) - max_dup, 0)
        counts, starts, total, hits = join._count_kernel(
            max_dup, lex, build, bkeys, lo, hi, vary_axes=(axis,))
        if join.join_type in ("left_semi", "left_anti"):
            out = join._semi_kernel(lex, counts)
            out = ColumnarBatch(out.columns, out.sel, join._schema)
            cap_overflow = jnp.int32(0)
        else:
            out = join._gather_kernel(out_cap, lex, build, lo, counts,
                                      starts, total, hits,
                                      vary_axes=(axis,))
            cap_overflow = jnp.maximum(total.astype(jnp.int32) - out_cap, 0)
        return (out, jax.lax.psum(lovf, axis),
                jax.lax.psum(dup_overflow, axis),
                jax.lax.psum(cap_overflow, axis))

    return shard_map(step, mesh=mesh, in_specs=(P(axis), P(axis)),
                     out_specs=(P(axis), P(), P(), P()))


def run_distributed_join_streaming(join, mesh: Mesh, left_chunks,
                                   right: ColumnarBatch,
                                   axis: str = DATA_AXIS, max_dup: int = 8,
                                   out_cap=None,
                                   use_allgather: bool = False,
                                   cache_key=None, on_exchange=None):
    """Host driver: exchange the build side once (quota overflow-retry),
    then stream probe chunks through the mesh, yielding one sharded output
    batch per chunk.  Retry knobs (left quota / dup window / out capacity)
    warm up across chunks, so steady state is one dispatch per chunk."""
    n = mesh.shape[axis]
    rcap = right.capacity // n
    quota_r = default_quota(rcap, n)
    while True:
        bstep = _jit_step(
            lambda: distributed_join_build_exchange_step(
                join, mesh, quota_r, axis=axis,
                use_allgather=use_allgather),
            "join_build", cache_key, n, rcap, quota_r, use_allgather)
        with mesh:
            rex, rovf = bstep(right)
        if on_exchange is not None:
            on_exchange(exchange_ici_bytes(
                right, n, rcap if use_allgather else quota_r))
        if use_allgather or int(rovf) == 0:
            break
        if quota_r >= rcap:  # pragma: no cover - cap always fits
            raise AssertionError("right exchange overflow at full quota")
        quota_r = min(rcap, quota_r * 2)

    quota_l = None
    for chunk in left_chunks:
        lcap = chunk.capacity // n
        if quota_l is None or quota_l > lcap:
            quota_l = default_quota(lcap, n)
        if out_cap is None:
            out_cap = max(n * quota_l, 1024)
        while True:
            pstep = _jit_step(
                lambda: distributed_join_probe_step(
                    join, mesh, max_dup, out_cap, quota_l, axis=axis,
                    use_allgather=use_allgather),
                "join_probe", cache_key, n, lcap, rcap, max_dup, out_cap,
                quota_l, quota_r, use_allgather)
            with mesh:
                out, l_ovf, dup_ovf, cap_ovf = pstep(chunk, rex)
            join.metrics.add(MN.JOIN_WALK_STEPS, max_dup)
            if on_exchange is not None:
                on_exchange(exchange_ici_bytes(
                    chunk, n, lcap if use_allgather else quota_l))
            retry = False
            if not use_allgather and int(l_ovf) > 0:
                if quota_l >= lcap:  # pragma: no cover - cap always fits
                    raise AssertionError(
                        "left exchange overflow at full quota")
                quota_l = min(lcap, quota_l * 2)
                retry = True
            if int(dup_ovf) > 0:
                max_dup = pow2_bucket(max_dup + int(dup_ovf))
                retry = True
            if int(cap_ovf) > 0:
                out_cap = out_cap * 2
                retry = True
            if not retry:
                break
        _count_finished_chunk(join)
        yield out


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------

def _range_scalar_key(col: Column, ascending: bool, nulls_first: bool):
    """A monotone float64 COARSENING of one sort column's order, used only
    for range bucketing: rows that compare equal under the coarse key are
    guaranteed to land on the same device, so local full-precision sorting
    plus device order yields a correct global order.

    (f64 precision loss over int64/strings only *merges* adjacent key values
    — a coarsening — never reorders them.  Sentinels are ±inf, which MERGES
    NaN with +inf data values and nulls with ±inf extremes rather than
    inventing an order between them — merged rows colocate and the local
    full-precision sort places them.)"""
    if col.dtype.is_string:
        cap, L = col.data.shape
        w = col.data[:, :8].astype(jnp.uint64) if L >= 8 else jnp.pad(
            col.data, ((0, 0), (0, 8 - L))).astype(jnp.uint64)
        shifts = jnp.arange(56, -8, -8, dtype=jnp.uint64)
        key = jnp.sum(w << shifts, axis=1, dtype=jnp.uint64).astype(
            jnp.float64)
    elif col.dtype.is_floating:
        d = col.data.astype(jnp.float64)
        # NaN is greatest under Spark sort semantics: merge it with +inf
        key = jnp.where(jnp.isnan(d), jnp.float64(np.inf), d)
    else:
        key = col.data.astype(jnp.float64)
    if not ascending:
        key = -key
    null_key = jnp.float64(-np.inf if nulls_first else np.inf)
    return jnp.where(col.valid, key, null_key)


def distributed_sort_step(sort_exprs, ascending, nulls_first, mesh: Mesh,
                          quota: int, n_samples: int = 64,
                          axis: str = DATA_AXIS,
                          use_allgather: bool = False):
    """SPMD global sort: sample range bounds -> range-partition exchange ->
    local lexsort.  The reference realizes global sort as
    GpuRangePartitioner (host-side reservoir sampling) + per-partition
    GpuSortExec (GpuRangePartitioner.scala:42-216, GpuSortExec.scala); here
    the sampling, exchange and sort are one compiled SPMD program.

    Returns fn: sharded batch -> (sharded sorted batch, overflow).  Device
    d's live rows are all <= device d+1's under the sort order, and locally
    sorted — so shard order IS global order.
    """
    from ..ops.sort_keys import sort_order
    n = mesh.shape[axis]
    first = sort_exprs[0]

    def step(local: ColumnarBatch):
        cap = local.capacity
        c0 = first.eval(local)
        coarse = _range_scalar_key(c0, ascending[0], nulls_first[0])
        live = local.sel
        m = jnp.sum(live.astype(jnp.int32))
        # sample n_samples evenly spaced live coarse keys (sorted, dead last)
        ckey = jnp.where(live, coarse, jnp.float64(np.inf))
        csorted = jnp.sort(ckey)
        sample_pos = (jnp.arange(n_samples, dtype=jnp.int32)
                      * jnp.maximum(m, 1)) // n_samples
        samples = jnp.take(csorted, jnp.clip(sample_pos, 0, cap - 1))
        samples = jnp.where(m > 0, samples, jnp.float64(np.inf))
        all_samples = jnp.sort(
            jax.lax.all_gather(samples, axis, tiled=True))     # [n*n_samples]
        bounds = jnp.take(all_samples,
                          jnp.arange(1, n, dtype=jnp.int32) * n_samples)
        bucket = jnp.searchsorted(bounds, coarse, side="left").astype(
            jnp.int32)
        if use_allgather:
            ex = exchange_by_bucket(local, bucket, axis)
            overflow = jnp.int32(0)
        else:
            ex, overflow = exchange_compact(local, bucket, quota, axis)
        order = sort_order(ex, sort_exprs, ascending, nulls_first)
        out = ex.take(order)
        k = jnp.arange(out.capacity, dtype=jnp.int32)
        out = out.with_sel(k < jnp.sum(ex.sel.astype(jnp.int32)))
        return out, jax.lax.psum(overflow, axis)

    return shard_map(step, mesh=mesh, in_specs=(P(axis),),
                     out_specs=(P(axis), P()))


def run_distributed_sort(sort_exprs, ascending, nulls_first, mesh: Mesh,
                         batch: ColumnarBatch, axis: str = DATA_AXIS,
                         use_allgather: bool = False,
                         cache_key=None, on_exchange=None) -> ColumnarBatch:
    """Host driver for the SPMD sort with quota overflow-retry."""
    n = mesh.shape[axis]
    local_cap = batch.capacity // n
    # range partitions are less uniform than hash: start with a wider quota
    quota = default_quota(local_cap, n, factor=4)
    while True:
        step = _jit_step(
            lambda: distributed_sort_step(
                sort_exprs, ascending, nulls_first, mesh, quota, axis=axis,
                use_allgather=use_allgather),
            "sort", cache_key, n, local_cap, quota, use_allgather)
        with mesh:
            out, overflow = step(batch)
        if on_exchange is not None:
            # the row exchange only: the sampled range bounds' all-gather
            # is n*n_samples scalars
            on_exchange(exchange_ici_bytes(
                batch, n, local_cap if use_allgather else quota))
        if use_allgather or int(overflow) == 0:
            return out
        if quota >= local_cap:  # pragma: no cover - cannot overflow at cap
            raise AssertionError("overflow with quota == local capacity")
        quota = min(local_cap, quota * 2)
