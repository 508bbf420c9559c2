"""Shuffle exchange exec: partition -> split -> shuffle manager round trip.

TPU-native analogue of GpuShuffleExchangeExec
(rapids/GpuShuffleExchangeExec.scala:60-155 + Plugin.scala:54-130): partition
indexes are computed ON DEVICE (murmur3 hash / range bounds / round robin /
single), the batch is contiguous-split on device (one sort + one counts
sync), and each partition slice is cached in the device-resident shuffle
store (spillable) until the read side drains it.

The CPU fallback half lives in exec/cpu_relational.CpuRepartitionExec.

PR-3 (adaptive execution) split the exchange into an explicit
MATERIALIZE step (the map stage: write phase + observed MapOutputStatistics
capture) and a spec-driven READ step, so the reduce side can be re-planned
from runtime sizes between the two (adaptive/executor.py; reference:
Spark 3 AQE over GpuShuffleExchangeExec).
"""
from __future__ import annotations

import time
from typing import Dict, Iterator, List, Optional, Sequence

from ..columnar import ColumnarBatch, concat_batches
from ..ops import expressions as E
from ..shuffle.manager import get_shuffle_env
from ..shuffle.partition import (hash_partition_ids, range_partition_ids,
                                 round_robin_partition_ids,
                                 sample_range_bounds, single_partition_ids,
                                 split_by_partition)
from .base import (ExecContext, ExecNode, TpuExec, record_movement,
                   record_output_batch)
from ..metrics import names as MN


class _ShuffleHandle:
    """A materialized shuffle stage: the write side ran, blocks sit in the
    executor catalog(s), and observed map-output statistics are available
    for adaptive re-planning (adaptive/).  Unifies the single-executor and
    multi-executor (plugin.TpuCluster) read paths behind one route/fetch
    surface."""

    def __init__(self, sid: int, num_partitions: int, env=None,
                 cluster=None):
        self.sid = sid
        self.num_partitions = num_partitions
        self.env = env
        self.cluster = cluster
        self._stats = None
        self._stats_epoch = None
        self._released = False

    def route(self, p: int):
        """(serving env, remote peer ids) for one reduce partition."""
        if self.cluster is not None:
            owner = self.cluster.env_for(p)
            return owner, self.cluster.peer_ids(owner.executor_id)
        return self.env, None

    def map_epoch(self) -> int:
        """Current lost-map-output epoch of whoever tracks this shuffle's
        statistics; a bump since capture means a map output died and any
        cached view is of a dead map stage."""
        if self.cluster is not None:
            return int(getattr(self.cluster, "map_epoch", 0))
        return self.env.map_stats.epoch

    def stats(self):
        """Cluster-wide MapOutputStatistics of this shuffle, computed
        once and cached: the map side is immutable after materialize, and
        every rule reading the same handle would otherwise re-run the
        per-executor aggregation sweep.  The cache is EPOCH-GUARDED: a
        map output declared lost (corruption / dead peer) bumps the
        tracker epoch, and the next read re-aggregates instead of handing
        AQE rules statistics from a dead map stage."""
        epoch = self.map_epoch()
        if self._stats is None or self._stats_epoch != epoch:
            if self.cluster is not None:
                self._stats = self.cluster.map_output_stats(
                    self.sid, self.num_partitions)
            else:
                self._stats = self.env.map_stats.stats(
                    self.sid, self.num_partitions)
            self._stats_epoch = epoch
        return self._stats

    def fetch(self, p: int, map_range=None):
        """One partition (or map-range skew slice) as a batch list, with
        received-buffer rollback on OOM so a retry does not duplicate the
        failed attempt's remote registrations in the pool."""
        env, peers = self.route(p)
        mark = env.received.snapshot(self.sid)
        try:
            return list(env.fetch_partition(self.sid, p,
                                            remote_peers=peers,
                                            map_range=map_range))
        except MemoryError:
            env.rollback_received(self.sid, mark)
            raise

    def release(self):
        if self._released:
            return
        self._released = True
        if self.cluster is not None:
            self.cluster.remove_shuffle(self.sid)
        else:
            self.env.remove_shuffle(self.sid)


class TpuShuffleExchangeExec(TpuExec):
    """mode: hash | round_robin | range | single."""

    coalesce_after = True

    def __init__(self, mode: str, keys: Sequence[E.Expression],
                 num_partitions: int, child: ExecNode,
                 ascending: Optional[List[bool]] = None,
                 nulls_first: Optional[List[bool]] = None):
        super().__init__(child)
        assert mode in ("hash", "round_robin", "range", "single"), mode
        self.mode = mode
        self.keys = list(keys)
        self.num_partitions = max(1, int(num_partitions))
        self.ascending = ascending or [True] * len(self.keys)
        self.nulls_first = nulls_first or [True] * len(self.keys)
        self._handle: Optional[_ShuffleHandle] = None
        # the device mesh the planner's distribute pass stamped for the
        # ICI lowering (plan/transitions.mark_ici_exchanges), or None —
        # shuffle/mesh_exchange.ici_mesh_for re-resolves from conf for
        # exchanges AQE rules create after planning
        self.ici_mesh = None

    @property
    def schema(self):
        return self.children[0].schema

    def describe(self):
        return (f"TpuShuffleExchangeExec[{self.mode}, "
                f"n={self.num_partitions}]")

    def _partition_ids(self, batch: ColumnarBatch, map_id: int, bounds):
        n = self.num_partitions
        if n == 1 or self.mode == "single":
            return single_partition_ids(batch.capacity)
        if self.mode == "hash":
            key_cols = [e.eval(batch) for e in self.keys]
            return hash_partition_ids(key_cols, n)
        if self.mode == "round_robin":
            return round_robin_partition_ids(batch.capacity, n, map_id)
        if self.mode == "range":
            if bounds is None:
                return single_partition_ids(batch.capacity)
            return range_partition_ids(batch, self.keys, self.ascending,
                                       self.nulls_first, bounds)
        raise AssertionError(self.mode)

    def _cpu_twin(self):
        """CPU re-execution plan for OOM fallback (exec/retryable.py):
        the host executor is single-process, so repartitioning degrades
        to a pass-through of the child's rows.

        NOT available for RANGE exchanges: the external sort consumes
        partition order AS global order, so a pass-through would yield a
        silently unsorted result — and its _PrefetchedSource child drains
        destructively, so a re-execution would also drop rows.  Returning
        None propagates RetryExhausted to the SORT's own fallback, which
        re-executes the original (re-runnable) child on CPU."""
        from .sort import _PrefetchedSource
        if self.mode == "range" \
                or isinstance(self.children[0], _PrefetchedSource):
            return None
        from .basic import DeviceToHostExec
        from .cpu_relational import CpuRepartitionExec
        return CpuRepartitionExec(self.num_partitions,
                                  DeviceToHostExec(self.children[0]))

    def execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from .retryable import execute_with_cpu_fallback
        yield from execute_with_cpu_fallback(
            self, ctx, self._execute_device(ctx), self._cpu_twin)

    def _execute_device(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        produced = False
        for _p, out in self.execute_partitions(ctx):
            if out is None:
                continue
            produced = True
            record_output_batch(self.metrics, out, ctx.runtime)
            yield out
        if not produced:
            # keep the one-batch-minimum contract for downstream operators
            from .join import _empty_batch
            yield _empty_batch(self.schema)

    def materialize(self, ctx: ExecContext) -> _ShuffleHandle:
        """Run the WRITE phase once (idempotent per plan instance): the
        map stage of this exchange.  After this returns, observed
        per-partition sizes are available via `handle.stats()` and the
        reduce side can be re-planned (adaptive/executor.py) before any
        read starts — the Spark AQE stage-materialization point.

        Multi-executor mode (plugin.TpuCluster): map task m writes to
        executor (m % N)'s catalog; reads later serve local blocks and
        pull the rest through the transport client/server (bounce
        buffers + throttle), like the reference's RapidsCachingReader
        local/remote split."""
        if self._handle is not None:
            return self._handle
        from ..metrics.journal import journal_event
        n = self.num_partitions
        replay_batches = None
        from ..shuffle import mesh_exchange as MX
        mesh = MX.ici_mesh_for(self, ctx)
        if mesh is not None:
            if ctx.runtime is None:
                from ..mem.runtime import TpuRuntime
                ctx.runtime = TpuRuntime(ctx.conf)
            h, replay_batches = MX.lower_exchange(self, ctx, mesh)
            if h is not None:
                st = h.stats()
                self.metrics.add(MN.MAP_OUTPUT_BYTES, st.total_bytes)
                self.metrics.add(MN.NUM_ICI_EXCHANGES, 1)
                # the map phase moved every partition through the mesh
                # collectives — LOGICAL bytes (codec-invariant like the
                # AQE map stats); nothing touched the host link or wire
                record_movement(self.metrics, ici=st.total_bytes)
                ctx.add_cleanup(h.release)
                journal_event("stage", "mapStage", shuffle=h.sid,
                              partitions=n, bytes=st.total_bytes,
                              rows=st.total_rows, maps=st.num_map_tasks,
                              tier="ici")
                self._handle = h
                return h
            # collective ladder exhausted: de-lowered — the socket tier
            # below replays the already-drained child batches
        if ctx.cluster is not None:
            cluster = ctx.cluster
            sid = cluster.new_shuffle_id()
            ctx.add_cleanup(lambda: cluster.remove_shuffle(sid))
            self._write_phase(ctx, n, lambda map_id, p, sub:
                              cluster.env_for(map_id).write_partition(
                                  sid, map_id, p, sub),
                              batches=replay_batches)
            h = _ShuffleHandle(sid, n, cluster=cluster)
        else:
            env = get_shuffle_env(ctx.runtime, ctx.conf) \
                if ctx.runtime else None
            if env is None:
                from ..mem.runtime import TpuRuntime
                ctx.runtime = TpuRuntime(ctx.conf)
                env = get_shuffle_env(ctx.runtime, ctx.conf)
            sid = env.new_shuffle_id()
            # a query dying mid-WRITE would orphan the partitions already
            # in the catalog (the read-phase try/finally never runs);
            # remove_shuffle is idempotent, so register it with the task
            # scope
            ctx.add_cleanup(lambda: env.remove_shuffle(sid))
            self._write_phase(ctx, n, lambda map_id, p, sub:
                              env.write_partition(sid, map_id, p, sub),
                              batches=replay_batches)
            h = _ShuffleHandle(sid, n, env=env)
        st = h.stats()
        self.metrics.add(MN.MAP_OUTPUT_BYTES, st.total_bytes)
        # the map phase materialized every partition off the device
        # (d2h) and handed it to the shuffle wire.  Wire bytes are
        # LOGICAL (uncompressed) on BOTH sides, like the codec-invariant
        # AQE map stats — under a shuffle codec the physical traffic is
        # smaller (transport counter compressed_bytes_sent has it)
        record_movement(self.metrics, d2h=st.total_bytes,
                        wire=st.total_bytes)
        journal_event("stage", "mapStage", shuffle=h.sid, partitions=n,
                      bytes=st.total_bytes, rows=st.total_rows,
                      maps=st.num_map_tasks, tier="socket")
        self._handle = h
        return h

    def execute_partitions(self, ctx: ExecContext, specs=None):
        """Yield (index, coalesced batch | None) for every partition spec
        in order.  The partition-aligned form TpuShuffledHashJoinExec zips
        to pair build/stream sides (reference: EnsureRequirements places
        matching HashPartitionings under GpuShuffledHashJoinExec).

        Default specs are one per reduce partition 0..n-1 (the static
        plan).  Adaptive execution passes re-planned specs
        (adaptive/stats.py): coalesced ranges ride the pipelined
        fetch_partitions_async path; skew slices use ranged catalog
        fetches."""
        h = self.materialize(ctx)
        from ..adaptive.stats import CoalescedPartitionSpec, identity_specs
        if specs is None:
            specs = identity_specs(h.num_partitions)
        from ..config import SHUFFLE_ASYNC_FETCH
        # the async producer emits partitions in request order; folding
        # them back into specs needs contiguous coalesced ranges covering
        # [0, n) — exactly what the coalesce rule produces (skew slices
        # re-read partitions, so they stay on the sync path)
        is_mesh = getattr(h, "is_mesh", False)
        async_ok = not is_mesh \
            and ctx.conf.get(SHUFFLE_ASYNC_FETCH) \
            and all(isinstance(s, CoalescedPartitionSpec) for s in specs) \
            and specs and specs[0].start == 0 \
            and specs[-1].end == h.num_partitions \
            and all(specs[i].start == specs[i - 1].end
                    for i in range(1, len(specs)))
        # data-movement policy (policy/engine.py): declare the reduce-
        # partition read order (plan lookahead for victim scoring +
        # proactive unspill), then advance the cursor / mark partitions
        # dead as each spec is handed to the consumer
        pol = getattr(ctx.runtime, "policy", None) if ctx.runtime \
            and not is_mesh else None
        spec_rids = [sorted({p for p, _mr in s.units()}) for s in specs]
        if pol is not None:
            seen = set()
            order = [p for rids in spec_rids for p in rids
                     if not (p in seen or seen.add(p))]
            # planned consumptions per partition (a skew-sliced or
            # re-read partition appears in several specs); with no
            # cluster this process is the shuffle's only consumer, so
            # the policy may free a partition's map buffers at its
            # FINAL planned consumption (early release)
            counts: Dict[int, int] = {}
            for rids in spec_rids:
                for p in rids:
                    counts[p] = counts.get(p, 0) + 1
            pol.begin_shuffle_read(h.sid, order, counts=counts,
                                   exclusive=h.cluster is None)
        wire_seen = [0]
        t0 = time.perf_counter()

        def with_read_movement(pairs):
            # on the socket tier every coalesced partition batch came OFF
            # the shuffle wire and back over the host->device link
            # (LOGICAL bytes, consistent under any codec); on the mesh
            # tier the read is a device-local split of the exchanged
            # chunks, whose movement was counted as ici at materialize
            for p, out in pairs:
                if out is not None and not is_mesh:
                    nbytes = out.device_size_bytes()
                    record_movement(self.metrics, wire=nbytes, h2d=nbytes)
                    wire_seen[0] += nbytes
                if pol is not None:
                    for rid in spec_rids[p]:
                        pol.partition_consumed(h.sid, rid)
                yield p, out

        try:
            with self.metrics.timer(MN.SHUFFLE_READ_TIME):
                if async_ok:
                    yield from with_read_movement(
                        self._read_specs_async(ctx, h, specs))
                else:
                    yield from with_read_movement(
                        self._read_specs_sync(ctx, h, specs))
        finally:
            if pol is not None:
                # runtime evidence for codec re-selection: the observed
                # read throughput of this exchange vs the wire peak
                pol.observe_exchange(h.sid, wire_seen[0],
                                     time.perf_counter() - t0)
            h.release()

    def _read_specs_async(self, ctx: ExecContext, h: _ShuffleHandle,
                          specs):
        """Pipelined read: the producer thread fetches partition k+1 while
        the consumer is still on k; `_drain_async` pads every partition
        (empty ones included) so contiguous spec ranges fold back by
        position."""
        from ..config import OOM_RETRY_MAX, SHUFFLE_MAX_RECV_INFLIGHT
        n = h.num_partitions
        if h.cluster is not None:
            from ..shuffle.fetch import AsyncFetchIterator
            pol = getattr(ctx.runtime, "policy", None) if ctx.runtime \
                else None
            it = AsyncFetchIterator(
                None, h.sid, range(n), None,
                int(ctx.conf.get(SHUFFLE_MAX_RECV_INFLIGHT)),
                route=h.route,
                oom_retries=int(ctx.conf.get(OOM_RETRY_MAX)),
                flow=pol.flow_controller() if pol is not None else None)
        else:
            it = h.env.fetch_partitions_async(h.sid, range(n))
        drained = _drain_async(it, n)
        from ..serve.lifecycle import ctx_checkpoint
        for i, spec in enumerate(specs):
            # lifecycle checkpoint on the DRAIN side (the fetch threads
            # have no query scope): cancel/deadline only — suspending
            # with the async pipeline mid-flight would pin its in-flight
            # admission window for the whole park
            ctx_checkpoint(ctx, allow_suspend=False)
            parts = []
            for _ in range(spec.start, spec.end):
                _p, b = next(drained)
                if b is not None:
                    parts.append(b)
            yield i, (parts[0] if len(parts) == 1
                      else concat_batches(parts) if parts else None)

    def _read_specs_sync(self, ctx: ExecContext, h: _ShuffleHandle, specs):
        """Retry-only read: catalog fetches are idempotent per unit (one
        reduce partition or one map-range slice), so a reserve() OOM
        during re-materialization just refetches that unit."""
        from .retryable import run_retryable
        from ..serve.lifecycle import ctx_checkpoint
        for i, spec in enumerate(specs):
            # read-boundary lifecycle checkpoint: each spec's fetches are
            # idempotent units, so cancelling between them loses nothing,
            # and a preempted reducer can park before the next fetch
            ctx_checkpoint(ctx, allow_suspend=True)
            parts = []
            for p, map_range in spec.units():
                def fetch_unit(pp, _mr=map_range):
                    return h.fetch(pp, map_range=_mr)
                parts.extend(run_retryable(ctx, self.metrics,
                                           "exchangeFetch", fetch_unit,
                                           [p])[0])
            yield i, _coalesce_parts(parts)

    def _fused_stage_child(self, ctx: ExecContext):
        """The whole-stage child to fuse the hash-partition bucketing
        into, or None.  Eligible when fusion is on and the partition-id
        compute is row-local (hash/round_robin/single — range needs a
        bounds-sampling pass over the materialized child output): the
        chain AND the bucketing then trace into ONE program per map
        batch, so the stage's only materialization is the partitioned
        output at the shuffle boundary."""
        from .. import config as C
        from .whole_stage import TpuWholeStageExec
        child = self.children[0]
        if not isinstance(child, TpuWholeStageExec):
            return None
        if not ctx.conf.get(C.FUSION_ENABLED):
            return None
        if self.mode == "range" and self.num_partitions > 1:
            return None
        if child._needs_row_offset() or child._needs_input_file():
            return None
        return child

    def _fused_partition_fn(self, stage, param_slots=None):
        """Builder of the fused (chain + partition-ids) program:
        batch -> (chain output batch, per-row partition ids).  `start` is
        the round-robin offset, traced so every map task shares one
        compiled program.  With `param_slots` the program takes the
        plan-cache parameter values as a trailing traced argument
        (exec/basic.bound_param_builder rationale)."""
        n = self.num_partitions
        mode = self.mode
        keys = self.keys

        def build():
            pre = stage.batch_fn()

            def fn(b, start):
                ob = pre(b)
                if n == 1 or mode == "single":
                    pids = single_partition_ids(ob.capacity)
                elif mode == "hash":
                    pids = hash_partition_ids([e.eval(ob) for e in keys], n)
                else:  # round_robin
                    pids = round_robin_partition_ids(ob.capacity, n, start)
                return ob, pids
            if param_slots is None:
                return fn
            from ..ops import expressions as PE

            def fn_p(b, start, pvals):
                with PE.bound_params(dict(zip(param_slots, pvals))):
                    return fn(b, start)
            return fn_p
        return build

    def _write_phase(self, ctx: ExecContext, n: int, write,
                     batches=None) -> None:
        """Shared write side: drain the child, compute partition ids, split,
        hand each piece to `write(map_id, p, sub)`.  Range mode samples
        bounds over a materialized list, then DROPS each batch reference as
        written so peak memory is the spillable partition store, not store
        plus pinned inputs.

        When the child is a fused whole-stage (plan/fusion.py), the
        row-local chain and the partition-id compute run as ONE compiled
        program over the stage's SOURCE batches (the bucketing step joins
        the stage instead of dispatching per operator).

        `batches` replays a pre-drained child output instead of
        re-executing the child — the mesh tier's de-lower path hands its
        already-consumed source iterator back here (same batch sequence:
        both tiers drain the fused stage's SOURCE when one is present)."""
        fused_stage = self._fused_stage_child(ctx)
        if batches is not None:
            child_batches = batches
        elif fused_stage is not None:
            child_batches = fused_stage.children[0].execute(ctx)
        else:
            child_batches = self.children[0].execute(ctx)
        bounds = None
        if self.mode == "range" and n > 1:
            # range bounds need a pass over the data (reference reservoir-
            # samples on the host: GpuRangePartitioner.scala:42-216)
            child_batches = list(child_batches)
            bounds = sample_range_bounds(child_batches, self.keys,
                                         self.ascending, self.nulls_first, n)
            seq = child_batches

            def _draining(s=seq):
                for i in range(len(s)):
                    b, s[i] = s[i], None
                    yield b
            child_batches = _draining()

        from ..mem.retry import RetryExhausted
        from .retryable import run_retryable, split_batch_rows
        num_writes = 0
        part_split = split_batch_rows
        fused_key = None
        fused_build = None
        fused_pvals = None
        if fused_stage is not None:
            import jax.numpy as jnp
            from ..metrics import names as MNN
            from ..ops import expressions as PE
            from ..utils.kernel_cache import (expr_key, param_free_keys,
                                              record_dispatch,
                                              stage_executable)
            # parameters can live in the fused chain AND in the partition
            # key expressions (a guard-lifted join-condition literal ends
            # up in the exchange's hash keys): the value-free key below
            # covers BOTH, so both must be in the traced binding — a
            # key-expression parameter left out would bake the first
            # submission's value into the replayed partition-id program
            # and misroute rows on later variants
            fused_params = PE.collect_parameters(
                fused_stage.expressions() + list(self.keys))
            if fused_params:
                with param_free_keys():
                    fused_key = ("exchange_fused", self.mode, n,
                                 fused_stage.kernel_key(),
                                 tuple(expr_key(k) for k in self.keys))
                fused_key += ("params",
                              PE.parameter_signature(fused_params))
                fused_pvals = PE.parameter_values(fused_params)
                fused_slots = [p.slot for p in fused_params]
                fused_build = self._fused_partition_fn(
                    fused_stage, param_slots=fused_slots)
            else:
                fused_key = ("exchange_fused", self.mode, n,
                             fused_stage.kernel_key(),
                             tuple(expr_key(k) for k in self.keys))
                fused_build = self._fused_partition_fn(fused_stage)
            fused_stage.metrics.add(MNN.NUM_FUSED_STAGES, 1)
            if not fused_stage._can_split():
                part_split = None
            from .. import config as CC
            from ..mem import donation as _donation
            fused_donate = bool(ctx.conf.get(CC.DONATION_ENABLED)) \
                and fused_stage.donate_inputs
        from ..serve.lifecycle import ctx_checkpoint
        with self.metrics.timer(MN.SHUFFLE_WRITE_TIME):
            for map_id, batch in enumerate(child_batches):
                # stage-boundary lifecycle checkpoint: between map
                # batches no partition is mid-write (partition_one has no
                # catalog writes inside), so a cancel/deadline raises
                # cleanly — the registered remove_shuffle cleanup and
                # owner-confined release free what was already written —
                # and a preemption request may suspend here
                ctx_checkpoint(ctx, allow_suspend=True)

                def partition_one(b, map_id=map_id):
                    """Retryable partition-id + split compute (no catalog
                    writes inside, so a retry or a row-range split of the
                    input never double-writes a partition)."""
                    if fused_stage is not None:
                        if ctx.runtime is not None:
                            ctx.runtime.reserve(
                                fused_stage._reserve_estimate(b),
                                site="exchange.partition")
                        args = (b, jnp.int32(map_id))
                        if fused_pvals is not None:
                            args += (fused_pvals,)
                        # donate the source batch (last consumer: the
                        # partitioned output is the only thing written)
                        # unless a retry checkpoint / cache pinned it
                        don = fused_donate and _donation.donatable(b)
                        fn = stage_executable(
                            fused_key, fused_build, args,
                            metrics=fused_stage.metrics,
                            name=f"exchangeStage-"
                                 f"{fused_stage.stage_id}",
                            donate_argnums=(0,) if don else ())
                        record_dispatch()
                        if don:
                            _donation.record_donated_dispatch(
                                b, fused_stage.metrics)
                        ob, pids = fn(*args)
                        record_output_batch(fused_stage.metrics, ob,
                                            ctx.runtime)
                        return list(split_by_partition(ob, pids, n))
                    if ctx.runtime is not None:
                        ctx.runtime.reserve(b.device_size_bytes(),
                                            site="exchange.partition")
                    pids = self._partition_ids(b, map_id, bounds)
                    return list(split_by_partition(b, pids, n))

                try:
                    pieces = run_retryable(ctx, self.metrics,
                                           "exchangePartition",
                                           partition_one, [batch],
                                           split=part_split)
                except RetryExhausted:
                    if fused_stage is None:
                        raise
                    if _donation.consumed(batch):
                        # the failed partition dispatch already donated
                        # the batch's buffers (TPU008): de-fusing would
                        # read freed device memory — terminal
                        raise
                    # fused-stage ladder, middle rung: de-fuse — run the
                    # chain operator-at-a-time (each op in its own retry
                    # block, per-op CPU fallback), then bucket the chain
                    # output with the eager partition-id path.  Only an
                    # exhaustion HERE escalates to the exchange's own
                    # CPU twin (exec/retryable.py).
                    from ..metrics import names as MNN
                    from ..metrics.journal import journal_event
                    fused_stage.metrics.add(MNN.NUM_FUSION_FALLBACKS, 1)
                    journal_event("fallback", fused_stage.name,
                                  reason="stage_retry_exhausted",
                                  stage=fused_stage.stage_id)
                    outs = fused_stage._run_ops_one_at_a_time(ctx, batch)
                    pieces = []
                    for ob in outs:
                        def bucket_one(b2, map_id=map_id):
                            if ctx.runtime is not None:
                                ctx.runtime.reserve(
                                    b2.device_size_bytes(),
                                    site="exchange.partition")
                            pids = self._partition_ids(b2, map_id, bounds)
                            return list(split_by_partition(b2, pids, n))
                        pieces.extend(run_retryable(
                            ctx, self.metrics, "exchangePartition",
                            bucket_one, [ob], split=split_batch_rows))
                batch = None
                for piece in pieces:
                    for p, sub in piece:
                        def write_one(sb, map_id=map_id, p=p):
                            # write() reserves pool space (add_batch);
                            # failure precedes registration, so the
                            # attempt is idempotent.  Split halves land
                            # as extra sub-batches of the same block —
                            # the read side coalesces them.
                            write(map_id, p, sb)
                            return 1
                        num_writes += sum(run_retryable(
                            ctx, self.metrics, "exchangeWrite", write_one,
                            [sub], split=split_batch_rows))
        self.metrics.add(MN.NUM_PARTITIONS_WRITTEN, num_writes)


def _drain_async(it, n: int):
    """Consume an AsyncFetchIterator's stream back into (partition,
    coalesced-batch) order, emitting every partition 0..n-1 exactly once
    (empty ones included)."""
    from ..shuffle.fetch import iter_partition_groups
    next_p = 0
    for rid, parts in iter_partition_groups(it):
        while next_p < rid:
            yield next_p, None
            next_p += 1
        yield rid, _coalesce_parts(parts)
        next_p = rid + 1
    while next_p < n:
        yield next_p, None
        next_p += 1


def _coalesce_parts(parts):
    if not parts:
        return None
    return parts[0] if len(parts) == 1 else concat_batches(parts)


def make_repartition_exec(plan, keys, child: ExecNode,
                          on_tpu: bool) -> ExecNode:
    """Planner hook (plan/physical.py) for LogicalRepartition."""
    mode = plan.mode
    if mode == "hash" and not keys:
        mode = "round_robin"
    return TpuShuffleExchangeExec(mode, keys, plan.num_partitions, child,
                                  getattr(plan, "ascending", None),
                                  getattr(plan, "nulls_first", None))
