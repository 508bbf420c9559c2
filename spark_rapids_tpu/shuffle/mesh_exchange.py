"""Mesh-native generic exchange: the ICI lowering of TpuShuffleExchangeExec.

ROADMAP item 1.  The socket tier pays D2H -> wire (~1 GB/s on loopback)
-> H2D for every generic exchange even when every
participating partition lives on devices of ONE jax Mesh — exactly the
data-movement tax the reference's UCX shuffle plugin exists to eliminate.
This module keeps the data in device memory instead: when the exchange's
producer and consumer are co-resident on a mesh (single process,
hash/round_robin/single partitioning, `spark.rapids.sql.tpu.shuffle.ici
.enabled`), the map phase runs as ONE compiled `shard_map` program per
map batch — fused row-local chain + partition-id compute + a quota-block
`all_to_all` (parallel/distributed.exchange_partition_step, built on the
same `exchange_compact`/`exchange_by_bucket` primitives every SPMD
operator rides) — and the reduce phase serves per-partition sub-batches
by splitting the mesh-resident exchanged chunks on device.

Contract parity with the socket tier (tests/test_mesh_exchange.py pins
all of it down):

  * **results** are bit-for-bit identical: within one map task the
    compact exchange preserves original row order per partition (stable
    sort by destination, shards are contiguous row ranges), so partition
    p reads as the same rows in the same order either tier serves them;
  * **AQE map statistics** are bit-identical: per-destination live-row
    counts come back FROM the collective program (a psum'd bincount), and
    bytes use the one shared `manager.map_output_nbytes` formula over the
    synthetic footprint of the sub-batch `split_by_partition` would
    build — so every adaptive rule sees the same numbers on either tier;
  * **memory pressure** re-enters the standard ladder: each collective
    dispatch reserves pool space (site ``exchange.collective``) inside a
    retryable block (RetryOOM -> spill/retry/split); exhaustion
    DE-LOWERS the whole exchange to the socket tier (counted in the
    transport's ``socket_fallbacks``), replaying the already-drained
    child batches — never wrong, at worst slower;
  * the kill switch (`shuffle.ici.enabled=false`) leaves the socket path
    byte-identical to the pre-mesh behavior, integrity/compression
    ladder untouched.
"""
from __future__ import annotations

import itertools
import threading
from typing import Dict, List, Optional

import numpy as np

from ..columnar import ColumnarBatch, bucket_rows, concat_batches
from ..metrics import names as MN
from ..metrics.journal import journal_span
from ..parallel.distributed import (DATA_AXIS, default_quota,
                                    exchange_partition_step)
from ..parallel.mesh import shard_batch
from .manager import map_output_nbytes
from .partition import split_by_partition

_SID_LOCK = threading.Lock()
_SID = [0]


def _next_sid() -> int:
    with _SID_LOCK:
        _SID[0] += 1
        return _SID[0]


def _row_width(batch: ColumnarBatch) -> int:
    """Static bytes per capacity row — chosen so that a sub-batch of
    capacity `cap` taken from `batch` has device_size_bytes() == cap * w
    EXACTLY (sel byte + per fixed column data+valid + per string column
    max_len+valid+lengths).  The mesh tier's map statistics are computed
    from device-side counts against this synthetic footprint through the
    shared map_output_nbytes formula, so they equal the socket tier's."""
    w = 1  # selection mask
    for c in batch.columns:
        if c.dtype.is_string:
            w += c.max_len + 1 + 4
        else:
            w += c.data.dtype.itemsize + 1
    return w


class MeshShuffleHandle:
    """A materialized MESH-tier shuffle stage: exchanged chunks (one per
    map task) sit sharded in device memory with their partition ids
    carried as a trailing column, and observed map-output statistics are
    available for adaptive re-planning.  Mirrors `_ShuffleHandle`'s
    route/stats/fetch/release surface (exec/exchange.py) so the read
    side, the AQE rules and the coalesced shuffle reader drive both
    tiers through one interface."""

    is_mesh = True

    def __init__(self, num_partitions: int, schema, n_devices: int = 0):
        from ..adaptive.stats import MapOutputTracker
        self.sid = _next_sid()
        self.num_partitions = num_partitions
        self.schema = schema
        self.n_devices = n_devices
        self.tracker = MapOutputTracker()
        self._chunks: List[ColumnarBatch] = []  # exchanged, +__ici_pid__
        self._chunk_counts: List[np.ndarray] = []
        self._parts: Dict[int, dict] = {}       # chunk -> {p: sub_batch}
        self._released = False

    # -- write side ----------------------------------------------------------

    def add_chunk(self, ex: ColumnarBatch, counts: np.ndarray) -> int:
        """Register one map task's exchanged output and record its map
        statistics from the DEVICE-computed per-partition live counts."""
        map_id = len(self._chunks)
        self._chunks.append(ex)
        self._chunk_counts.append(counts)
        w = _row_width(self._strip(ex))
        for p in range(self.num_partitions):
            cnt = int(counts[p])
            if cnt == 0:
                continue
            pcap = bucket_rows(cnt, 1024)
            self.tracker.record(self.sid, map_id, p,
                                map_output_nbytes(pcap * w, pcap, cnt),
                                cnt)
        return map_id

    # -- the _ShuffleHandle surface ------------------------------------------

    def map_epoch(self) -> int:
        return self.tracker.epoch

    def stats(self):
        return self.tracker.stats(self.sid, self.num_partitions)

    def fetch(self, p: int, map_range=None) -> List[ColumnarBatch]:
        """Partition p's sub-batches (one per contributing map task, in
        map order), split ON DEVICE from the mesh-resident exchanged
        chunks.  `map_range=(lo, hi)` restricts to map tasks in range —
        the AQE skew-slice read, map ids being chunk indexes here."""
        lo, hi = (0, len(self._chunks)) if map_range is None else map_range
        out: List[ColumnarBatch] = []
        for m in range(int(lo), min(int(hi), len(self._chunks))):
            sub = self._split(m).get(p)
            if sub is not None:
                out.append(sub)
        return out

    def _split(self, m: int) -> dict:
        """Per-partition sub-batches of chunk m, split once and cached:
        one stable device sort by partition id + one host count sync,
        amortized over every partition this chunk serves (the device
        twin of the socket tier's write-side split).

        Float columns cross the split BITCAST to same-width unsigned
        ints: the gathers here run EAGERLY over the mesh-sharded
        exchanged batch, and XLA:CPU's cross-shard data movement routes
        float elements through fast-math arithmetic that flushes
        denormals and quiets signaling-NaN payloads (measured — the
        compiled all_to_all itself is bit-exact).  Integer lanes are
        exact on every backend, and the bitcasts are free, so the mesh
        tier stays bit-for-bit with the socket tier's host-memcpy path
        for every float value including the pathological ones."""
        cached = self._parts.get(m)
        if cached is None:
            ex = self._chunks[m]
            if self.num_partitions == self.n_devices:
                # the common mesh-native shape (one reduce partition per
                # device): the owner mapping is the identity, so
                # partition p IS device p's shard of the exchanged batch
                # — zero-copy per-device views, no sort, no gather
                cached = self._split_by_shard(m, ex)
            else:
                pids = ex.columns[-1].data
                armored = _bitcast_floats_to_uint(self._strip(ex))
                cached = {
                    p: _bitcast_floats_back(sub, self.schema)
                    for p, sub in split_by_partition(
                        armored, pids, self.num_partitions)}
            self._parts[m] = cached
        return cached

    def _split_by_shard(self, m: int, ex: ColumnarBatch) -> dict:
        """num_partitions == n_devices fast path: per-device addressable
        shards of every leaf ARE the per-partition sub-batches (live
        rows flagged by the shard's selection mask, in map-original
        order — same order the socket tier serves)."""
        from ..columnar import Column

        def shards_of(arr):
            byrow = sorted(ex_shards(arr), key=lambda s: s[0])
            return [a for _start, a in byrow]

        def ex_shards(arr):
            for sh in arr.addressable_shards:
                idx = sh.index[0] if sh.index else slice(0, 0)
                yield (idx.start or 0), sh.data

        base = self._strip(ex)
        col_shards = []
        for c in base.columns:
            data = shards_of(c.data)
            valid = shards_of(c.valid)
            lengths = (shards_of(c.lengths)
                       if c.lengths is not None else None)
            col_shards.append((data, valid, lengths))
        sel = shards_of(base.sel)
        counts = self._chunk_counts[m]
        out = {}
        for p in range(self.num_partitions):
            cnt = int(counts[p])
            if cnt == 0:
                continue
            cols = [Column(d[p], v[p], c.dtype,
                           ln[p] if ln is not None else None)
                    for (d, v, ln), c in zip(col_shards, base.columns)]
            sub = ColumnarBatch(cols, sel[p], self.schema)
            sub.known_rows = cnt
            out[p] = sub
        return out

    def _strip(self, ex: ColumnarBatch) -> ColumnarBatch:
        """Drop the trailing __ici_pid__ routing column."""
        return ColumnarBatch(list(ex.columns[:-1]), ex.sel, self.schema)

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        self.tracker.remove_shuffle(self.sid)
        self._chunks = []
        self._parts = {}


def _bitcast_floats_to_uint(batch: ColumnarBatch) -> ColumnarBatch:
    """Float column data viewed as same-width unsigned ints (dtype
    METADATA untouched — only the device array changes); see _split."""
    import jax
    import jax.numpy as jnp
    from ..columnar import Column
    uint_of = {4: jnp.uint32, 8: jnp.uint64}
    cols = []
    for c in batch.columns:
        if not c.dtype.is_string and c.data.dtype.kind == "f":
            u = jax.lax.bitcast_convert_type(
                c.data, uint_of[c.data.dtype.itemsize])
            cols.append(Column(u, c.valid, c.dtype))
        else:
            cols.append(c)
    return ColumnarBatch(cols, batch.sel, batch.schema)


def _bitcast_floats_back(batch: ColumnarBatch, schema) -> ColumnarBatch:
    """Undo _bitcast_floats_to_uint after the gathers: restore each
    float column's device array from its uint view (exact, elementwise
    — no cross-shard movement, so no fast-math in the path)."""
    import jax
    import jax.numpy as jnp
    from ..columnar import Column
    # width-matched restore: on an x64-less backend a "float64" column's
    # device array is really float32, so follow the ARRAY's width
    float_of = {4: jnp.float32, 8: jnp.float64}
    cols = []
    for c in batch.columns:
        if not c.dtype.is_string and c.data.dtype.kind == "u" \
                and c.dtype.np_dtype is not None \
                and c.dtype.np_dtype.kind == "f":
            f = jax.lax.bitcast_convert_type(
                c.data, float_of[c.data.dtype.itemsize])
            cols.append(Column(f, c.valid, c.dtype))
        else:
            cols.append(c)
    out = ColumnarBatch(cols, batch.sel, schema)
    out.known_rows = batch.known_rows
    return out


def lower_exchange(exchange, ctx, mesh):
    """Run the exchange's write phase as jitted ICI collectives over
    `mesh`.  Returns ``(handle, None)`` on success, or ``(None,
    batches)`` after a de-lower — the collective retry ladder exhausted
    on some chunk, and `batches` replays the already-drained child
    output (plus the untouched remainder of the iterator) into the
    socket tier's write phase so no child work re-executes.

    One map task per child batch, exactly like the socket tier, so map
    ids — and therefore the per-map statistics AQE's skew rule slices
    on — line up across tiers."""
    import jax.numpy as jnp

    from .. import config as C
    from ..exec.retryable import run_retryable
    from ..mem.retry import RetryExhausted, split_batch_rows
    from ..metrics.journal import journal_event
    from ..ops import expressions as PE
    from ..utils.kernel_cache import (expr_key, param_free_keys,
                                      record_dispatch, schema_key,
                                      stage_executable)

    n_dev = mesh.shape[DATA_AXIS]
    n_parts = exchange.num_partitions
    use_allgather = bool(ctx.conf.get(C.MESH_USE_ALLGATHER))
    fused_stage = exchange._fused_stage_child(ctx)
    if fused_stage is not None:
        source = fused_stage.children[0]
        can_split = fused_stage._can_split()
    else:
        source = exchange.children[0]
        can_split = True
    # plan-cache parameters may live in the fused chain AND the partition
    # key expressions; both bind as a trailing traced argument so the
    # value-free key replays one compiled collective across literal
    # variants (same contract as the socket tier's bucketing fusion)
    p_exprs = list(exchange.keys)
    if fused_stage is not None:
        p_exprs = fused_stage.expressions() + p_exprs
    params = PE.collect_parameters(p_exprs)
    with param_free_keys():
        # EVERY expression-derived component builds inside this scope —
        # a plan-cache Parameter keyed by value here would make each
        # literal variant recompile the collective (the values thread as
        # a traced argument below instead).  schema_key matters beyond
        # hygiene: input_signature alone cannot tell apart logical
        # dtypes sharing one device representation (date vs int32,
        # timestamp vs int64), and an AOT executable compiled for one
        # pytree REJECTS the other
        pre_key = (fused_stage.kernel_key() if fused_stage is not None
                   else None)
        base_key = ("ici_exchange", exchange.mode, n_parts, n_dev,
                    use_allgather, mesh, pre_key,
                    schema_key(source.schema),
                    tuple(expr_key(k) for k in exchange.keys))
    pvals = None
    slots = None
    if params:
        base_key += ("params", PE.parameter_signature(params))
        pvals = PE.parameter_values(params)
        slots = [p.slot for p in params]

    handle = MeshShuffleHandle(n_parts, exchange.schema,
                               n_devices=n_dev)
    quota_by_cap: Dict[int, int] = {}
    metrics = exchange.metrics
    batches = source.execute(ctx)
    drained: List[ColumnarBatch] = []

    def pid_builder(quota):
        def build():
            pre = fused_stage.batch_fn() if fused_stage is not None \
                else None
            return exchange_partition_step(
                mesh, n_parts, _pid_fn(exchange), quota, pre=pre,
                param_slots=slots, use_allgather=use_allgather)
        return build

    def exchange_chunk(b: ColumnarBatch, map_id: int):
        if ctx.runtime is not None:
            est = (fused_stage._reserve_estimate(b)
                   if fused_stage is not None else b.device_size_bytes())
            ctx.runtime.reserve(3 * est, site="exchange.collective")
        if b.capacity % n_dev != 0 or b.capacity < n_dev:
            # bucket capacities are powers of two >= 1024, so this only
            # fires for hand-built odd capacities; re-bucket to shard
            b = concat_batches(
                [b], capacity=max(bucket_rows(max(b.num_rows_host(), 1)),
                                  n_dev))
        local_cap = b.capacity // n_dev
        sharded = shard_batch(b, mesh)
        quota = quota_by_cap.get(local_cap)
        if quota is None:
            quota = default_quota(local_cap, n_dev)
        while True:
            args = (sharded, jnp.int32(map_id))
            if pvals is not None:
                args += (pvals,)
            fn = stage_executable(base_key + (local_cap, quota),
                                  pid_builder(quota), args,
                                  metrics=metrics, name="iciExchange")
            with metrics.timer(MN.COLLECTIVE_TIME), \
                    journal_span("collective", "iciExchange",
                                 shuffle=handle.sid, map=map_id,
                                 devices=n_dev, quota=quota):
                record_dispatch()
                with mesh:
                    ex, overflow, counts = fn(*args)
            if use_allgather or int(overflow) == 0:
                break
            if quota >= local_cap:  # pragma: no cover - cap always fits
                raise AssertionError(
                    "exchange overflow with quota == local capacity")
            quota = min(local_cap, quota * 2)
        quota_by_cap[local_cap] = quota
        handle.add_chunk(ex, np.asarray(counts))  # tpulint: disable=TPU001 the ONE host sync per map task: the device-computed per-partition counts become AQE map statistics, same boundary sync split_by_partition pays on the socket tier
        return 1

    try:
        with metrics.timer(MN.SHUFFLE_WRITE_TIME):
            for map_id, batch in enumerate(batches):
                drained.append(batch)

                def attempt(b, map_id=map_id):
                    return exchange_chunk(b, map_id)

                run_retryable(ctx, metrics, "exchangeCollective", attempt,
                              [batch],
                              split=split_batch_rows if can_split
                              else None)
    except RetryExhausted:
        # de-lower: the socket tier replays the drained batches (and
        # whatever the source iterator still holds); the partial mesh
        # handle is dropped, nothing was registered outside it
        handle.release()
        journal_event("fallback", exchange.name,
                      reason="collective_retry_exhausted",
                      shuffle=handle.sid)
        _count_tier(ctx, "socket_fallbacks")
        return None, itertools.chain(drained, batches)
    if fused_stage is not None:
        # counted on SUCCESS only: a de-lower replays through
        # _write_phase, which counts the same fused stage itself
        from ..metrics import names as MNN
        fused_stage.metrics.add(MNN.NUM_FUSED_STAGES, 1)
    _count_tier(ctx, "ici_exchanges")
    return handle, None


def _pid_fn(exchange):
    """Traced per-row partition ids of one DEVICE shard: `offset` is the
    shard's global row position plus the map task's round-robin start, so
    position-based modes match the socket tier's whole-batch ids."""
    from .partition import hash_partition_ids, single_partition_ids
    mode = exchange.mode
    n = exchange.num_partitions
    keys = exchange.keys

    def pid_fn(local, offset):
        import jax.numpy as jnp
        if n == 1 or mode == "single":
            return single_partition_ids(local.capacity)
        if mode == "hash":
            return hash_partition_ids([e.eval(local) for e in keys], n)
        iota = jnp.arange(local.capacity, dtype=jnp.int32)  # round robin
        return (iota + offset) % jnp.int32(n)

    return pid_fn


def _count_tier(ctx, key: str) -> None:
    """Tier-selection counters live on the session's shuffle transport
    (`transport_counters`/`session_observability` satellite): the mesh
    tier moves no bytes through it, but the SELECTION is transport-level
    observability — which tier served each exchange, and why."""
    if ctx.runtime is None:
        return
    from .manager import get_shuffle_env
    env = get_shuffle_env(ctx.runtime, ctx.conf)
    count = getattr(env.transport, "count", None)
    if count is not None:
        count(key)


def ici_mesh_for(exchange, ctx) -> Optional[object]:
    """The mesh this exchange's collective lowering would run over, or
    None when the socket tier must serve it.  The planner's distribute
    pass stamps `ici_mesh` on every generic exchange it leaves in a mesh
    plan (plan/transitions.mark_ici_exchanges — re-run by AQE `_replan`
    so rule-created exchanges get the same, idempotent decision); an
    unstamped exchange re-resolves from conf so adaptive rewrites can
    never silently drop the lowering.

    Socket-tier forcers: the kill switch, a multi-executor cluster (the
    partitions are NOT co-resident — the socket path is the cross-host
    tier, integrity/compression ladder untouched), range partitioning
    (bounds sampling needs the materialized child output), and a missing
    / too-small device mesh."""
    from .. import config as C
    if exchange.mode == "range":
        return None
    if ctx.cluster is not None:
        return None
    if not ctx.conf.get(C.ICI_SHUFFLE_ENABLED):
        return None
    mesh = getattr(exchange, "ici_mesh", None)
    if mesh is None and ctx.runtime is not None:
        # the session transport resolved the mesh once at configure()
        # (shuffle/ici.py) — prefer that settled capability
        env = getattr(ctx.runtime, "_shuffle_env", None)
        if env is not None:
            mesh = getattr(env.transport, "mesh", None)
    if mesh is None:
        from ..exec.distributed import resolve_mesh
        mesh = resolve_mesh(ctx.conf)
    return mesh
