"""Device-resident shuffle manager: writer, reader, server.

TPU-native analogue of RapidsShuffleInternalManager + RapidsCachingWriter /
RapidsCachingReader (org/.../rapids/RapidsShuffleInternalManager.scala:73-337,
RapidsCachingReader.scala:49-170) and GpuShuffleEnv (GpuShuffleEnv.scala:
57-107):

  * write side caches each partition's batch as a SPILLABLE buffer in the
    device store (shuffle data never leaves HBM unless memory pressure
    spills it) and registers it in the ShuffleBufferCatalog;
  * read side serves local blocks straight from the catalog (zero copy when
    still in HBM) and fetches remote blocks through the transport, which
    re-serves spilled buffers from whatever tier they occupy;
  * a baseline host-serialized path mirrors the reference's always-available
    non-UCX shuffle (GpuColumnarBatchSerializer.scala).
"""
from __future__ import annotations

import threading
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

import logging

from ..columnar import ColumnarBatch
from ..config import (PINNED_POOL_SIZE, SHUFFLE_CHECKSUM_VERIFY_LOCAL,
                      SHUFFLE_DEVICE_RESIDENT, SHUFFLE_MAX_RECV_INFLIGHT,
                      SHUFFLE_MAX_REFETCH, SHUFFLE_TRANSPORT_CLASS,
                      TpuConf)
from ..mem.buffer import (SpillPriorities, StorageTier, batch_to_host,
                          host_to_batch)
from ..mem.integrity import (BufferGone, CorruptBuffer, CorruptShuffleBlock,
                             FetchFailed, policy_from_conf)
from ..mem.runtime import TpuRuntime
from ..mem.stores import verify_buffer_leaves
from ..metrics import names as MN
from ..utils import faults
from .catalog import (ShuffleBlockId, ShuffleBufferCatalog,
                      ShuffleReceivedBufferCatalog)
from .transport import (LoopbackTransport, MetadataRequest, MetadataResponse,
                        BlockMeta, ShuffleTransport)

log = logging.getLogger("spark_rapids_tpu.shuffle")


class ShuffleServer:
    """Serves this executor's shuffle buffers to peers, from ANY storage
    tier (RapidsShuffleServer.scala:67-671: BufferSendState acquires
    possibly-spilled buffers and streams them through bounce buffers)."""

    def __init__(self, env: "ShuffleEnv"):
        from ..compress import CompressedServeCache
        self.env = env
        self._cache: Dict[int, Tuple[list, object]] = {}
        # framed compressed forms per (buffer, codec), compressed ONCE at
        # first serve and re-served for every chunk/shm fill/refetch; the
        # compressed-frame digests the reader pre-verifies come from here
        self._comp_cache = CompressedServeCache(env.compression,
                                                integrity=env.integrity)
        self._lock = threading.Lock()

    def handle_metadata_request(self, request: MetadataRequest
                                ) -> MetadataResponse:
        # codec negotiation opener: the reader names its preferred codec
        # and every BlockMeta answers with what THIS server will actually
        # frame the block's buffers with (the requested codec when the
        # library is available here, raw otherwise); the layout response
        # at fetch time confirms with per-leaf framed sizes + digests
        from ..compress import is_codec_available
        req_codec = getattr(request, "codec", None)
        negotiated = (req_codec if req_codec not in (None, "none")
                      and is_codec_available(req_codec) else None)
        blocks = request.blocks
        if blocks is None:  # wildcard discovery for one reduce partition
            blocks = self.env.catalog.blocks_for_reduce(
                request.shuffle_id, request.reduce_id)
            if request.map_lo is not None or request.map_hi is not None:
                # skew-slice discovery: only the requested map-id range
                lo = request.map_lo if request.map_lo is not None else 0
                hi = request.map_hi if request.map_hi is not None \
                    else float("inf")
                blocks = [b for b in blocks if lo <= b.map_id < hi]
        out: List[BlockMeta] = []
        for block in blocks:
            buffer_ids = self.env.catalog.buffers_for(block)
            metas, sizes, sums = [], [], []
            for bid in buffer_ids:
                sums.append(self.env.catalog.checksums_for(bid))
                baseline = self.env.baseline_leaves(bid)
                if baseline is not None:
                    metas.append(baseline[1])
                    sizes.append(baseline[1].size_bytes)
                    continue
                buf = self.env.runtime.catalog.acquire(bid)
                try:
                    metas.append(buf.meta)
                    sizes.append(buf.size_bytes)
                finally:
                    self.env.runtime.catalog.release(buf)
            comp_sizes = [
                (e.sizes if (e := self._comp_cache.peek(bid, negotiated))
                 is not None else None)
                for bid in buffer_ids] if negotiated else None
            out.append(BlockMeta(block, buffer_ids, metas, sizes,
                                 checksums=sums, codec=negotiated,
                                 compressed_sizes=comp_sizes))
        return MetadataResponse(out)

    def _leaves(self, buffer_id: int):
        """Host-side leaves of a buffer, whatever its tier (no promotion —
        serving a spilled buffer must not re-inflate HBM).

        Integrity duties on the serve path: a spilled buffer's host/disk
        form is verified against its spill-time digests before serving
        (so the server never knowingly streams rotted bytes — the typed
        corrupt frame tells the reader to recompute, not refetch), and the
        buffer's canonical checksums are recorded in the writer catalog at
        its FIRST host materialization."""
        with self._lock:
            hit = self._cache.get(buffer_id)
            if hit is not None:
                return hit
        buf = None
        baseline = self.env.baseline_leaves(buffer_id)
        if baseline is not None:
            leaves, meta = baseline
        else:
            buf = self.env.runtime.catalog.acquire(buffer_id)
            try:
                with buf.lock:
                    if buf.tier == StorageTier.DEVICE:
                        leaves, meta = batch_to_host(buf.device_batch)
                    elif buf.tier == StorageTier.HOST:
                        leaves, meta = buf.host_leaves, buf.meta
                    else:
                        # decompresses a codec-spilled file, verifying
                        # the compressed image first (read_spilled_leaves)
                        from ..mem.stores import read_spilled_leaves
                        leaves, meta = read_spilled_leaves(
                            self.env.runtime.catalog, buf), buf.meta
                    if buf.tier != StorageTier.DEVICE:
                        try:
                            # raises a typed CorruptBuffer ->
                            # OP_GONE(corrupt) at the socket server
                            verify_buffer_leaves(self.env.runtime.catalog,
                                                 buf, leaves, site="serve")
                        except CorruptBuffer:
                            # the OWNER just learned its own stored copy
                            # rotted: drop that map output's statistics
                            # (and bump the epoch) so AQE never re-plans
                            # on sizes this buffer can no longer back
                            blk = self.env.catalog.block_for_buffer(
                                buffer_id)
                            if blk is not None:
                                self.env.map_stats.mark_lost(
                                    blk.shuffle_id, blk.map_id)
                            raise
            finally:
                self.env.runtime.catalog.release(buf)
        policy = self.env.integrity
        if policy.enabled \
                and self.env.catalog.checksums_for(buffer_id) is None:
            if buf is not None and buf.host_checksums is not None:
                sums = buf.host_checksums  # spill already digested them
            else:
                sums = policy.checksum_leaves(leaves)
            self.env.catalog.set_checksums(buffer_id, policy.algorithm,
                                           sums)
        if leaves and faults.INJECTOR.on_corruptible("writer"):
            # injected WRITER-side rot: the flip lands in the copy this
            # server will keep serving, AFTER its digests were recorded —
            # refetches keep failing until the reader escalates to a map
            # recompute.  Copy-swap: host leaves are read-only views.
            leaves = list(leaves)
            leaves[0] = faults.flip_bit(leaves[0])
        flow = self._flow()
        if flow is not None:
            # map-side serve window (policy/flow.py): bounded stall when
            # in-flight served bytes exceed the reduce-rate-driven
            # window — soft backpressure on the stager, never a deadlock
            flow.serve_acquire(buffer_id,
                               sum(int(a.nbytes) for a in leaves))
        evicted = None
        with self._lock:
            if len(self._cache) >= 4:  # bounded serving cache
                evicted = next(iter(self._cache))
                self._cache.pop(evicted)
            self._cache[buffer_id] = (leaves, meta)
        if evicted is not None and flow is not None:
            flow.serve_release(evicted)
        return leaves, meta

    def _flow(self):
        pol = getattr(self.env.runtime, "policy", None)
        return pol.flow_controller() if pol is not None else None

    def buffer_layout(self, buffer_id: int):
        leaves, meta = self._leaves(buffer_id)
        layout = [(a.shape, a.dtype.str, a.nbytes) for a in leaves]
        return layout, meta

    def buffer_checksums(self, buffer_id: int):
        """(algorithm, per-leaf digests) for a served buffer; populated by
        the _leaves call every layout request makes first."""
        return self.env.catalog.checksums_for(buffer_id)

    def compressed_layout(self, buffer_id: int,
                          codec_name: str) -> Optional[dict]:
        """Frame a buffer's leaves with the READER-requested codec and
        answer the negotiated wire contract: {codec, sizes, checksums,
        algorithm} — digests over the COMPRESSED frames, established
        right here at the compression boundary.  None when this process
        cannot encode the codec (the reader falls back to raw, counted):
        the typed negotiation miss, never an error."""
        leaves, _meta = self._leaves(buffer_id)
        entry = self._comp_cache.get(buffer_id, codec_name, leaves)
        return entry.descriptor() if entry is not None else None

    def copy_compressed_chunk(self, buffer_id: int, leaf_idx: int,
                              offset: int, length: int, dest: np.ndarray,
                              codec_name: str) -> None:
        """Stage one bounce-buffer chunk of a leaf's FRAMED form (the
        compressed analogue of copy_leaf_chunk)."""
        leaves, _ = self._leaves(buffer_id)
        entry = self._comp_cache.get(buffer_id, codec_name, leaves)
        if entry is None:
            # negotiation raced a codec going away (cannot happen in
            # practice: availability is static per process) — typed, so
            # the reader's ladder sees a clean buffer-gone
            raise KeyError(f"buffer {buffer_id} has no {codec_name} "
                           "compressed form")
        dest[:length] = entry.leaves[leaf_idx][offset:offset + length]

    def diagnose_buffer(self, buffer_id: int):
        """Writer-side half of the corruption-site diagnosis
        (SPARK-36206): re-hash the LIVE copy a refetch would serve and
        compare with the recorded digests.  writer_ok=False means the
        writer's own data rotted — the reader must recompute the map
        fragment, not refetch."""
        # a reader only asks for a diagnosis after ITS verify failed: if
        # the rot lives in our cached compressed frames (digested at
        # build time, so every re-serve fails identically), dropping the
        # entries here lets the refetch recompress from the raw leaves
        # and recover in ONE round instead of burning every refetch
        # attempt into a map-fragment recompute
        self._comp_cache.drop(buffer_id)
        policy = self.env.integrity
        rec = self.env.catalog.checksums_for(buffer_id)
        if not policy.enabled or rec is None:
            return None
        algo, recorded = rec
        if algo != policy.algorithm:
            return None
        try:
            leaves, _meta = self._leaves(buffer_id)
        except CorruptBuffer:
            # the serve-time verify itself tripped while re-reading the
            # buffer: the writer's stored copy is rotted, full stop
            return {"algorithm": algo,
                    "recorded": [int(s) for s in recorded],
                    "recomputed": None, "writer_ok": False}
        recomputed = policy.checksum_leaves(leaves)
        return {"algorithm": algo,
                "recorded": [int(s) for s in recorded],
                "recomputed": [int(s) for s in recomputed],
                "writer_ok": [int(s) for s in recomputed]
                             == [int(s) for s in recorded]}

    def copy_leaf_chunk(self, buffer_id: int, leaf_idx: int, offset: int,
                        length: int, dest: np.ndarray) -> None:
        leaves, _ = self._leaves(buffer_id)
        flat = np.ascontiguousarray(leaves[leaf_idx]).view(np.uint8).reshape(-1)
        dest[:length] = flat[offset:offset + length]

    def done_serving(self, buffer_id: int) -> None:
        with self._lock:
            self._cache.pop(buffer_id, None)
        self._comp_cache.drop(buffer_id)
        flow = self._flow()
        if flow is not None:
            # the reader's release is reduce-side consumption evidence
            # crossing the wire: it both frees the serve window and
            # feeds the consumption rate the window is derived from
            nb = flow.serve_release(buffer_id)
            if nb:
                flow.on_consumed(nb)

    def invalidate(self, buffer_ids) -> None:
        """Drop serving-cache entries for removed buffers: a fetch racing
        `remove_shuffle` must hit the catalog (and get the typed
        buffer-gone error), not a stale cache copy that silently outlives
        the shuffle."""
        with self._lock:
            for bid in buffer_ids:
                self._cache.pop(bid, None)
        self._comp_cache.invalidate(buffer_ids)
        flow = self._flow()
        if flow is not None:
            for bid in buffer_ids:
                flow.serve_release(bid)


class ShuffleEnv:
    """Per-executor shuffle wiring (GpuShuffleEnv equivalent)."""

    def __init__(self, runtime: TpuRuntime, conf: Optional[TpuConf] = None,
                 executor_id: str = "exec-0",
                 transport: Optional[ShuffleTransport] = None):
        self.runtime = runtime
        self.conf = conf or TpuConf()
        self.executor_id = executor_id
        self.device_resident = bool(self.conf.get(SHUFFLE_DEVICE_RESIDENT))
        self.catalog = ShuffleBufferCatalog()
        self.received = ShuffleReceivedBufferCatalog()
        # end-to-end integrity policy (mem/integrity.py): write paths
        # digest, every fetch/serve path verifies, mismatches run the
        # refetch/diagnose/recompute ladder in _fetch_remote
        self.integrity = policy_from_conf(self.conf,
                                          metrics=runtime.metrics)
        # wire compression policy (compress/): what this env's READS ask
        # peers for, and the chunk/min-size parameters its SERVER frames
        # with; spill compression is conf'd independently on the runtime
        from ..compress import compression_from_conf
        self.compression = compression_from_conf(self.conf,
                                                 metrics=runtime.metrics)
        self.max_refetch = max(0, int(self.conf.get(SHUFFLE_MAX_REFETCH)))
        self.verify_local = bool(
            self.conf.get(SHUFFLE_CHECKSUM_VERIFY_LOCAL))
        # observed per-reduce-partition output sizes, recorded at write
        # time — what adaptive re-planning (adaptive/) runs on
        from ..adaptive.stats import MapOutputTracker
        self.map_stats = MapOutputTracker()
        if transport is None:
            transport = self._resolve_transport()
        self.transport = transport
        # the transport's fetch-side compression/decompression metrics
        # land on this runtime's Metrics (shared transports aggregate
        # across their envs, exactly like transport counters do)
        tcomp = getattr(transport, "compression", None)
        if tcomp is not None and tcomp.metrics is None:
            tcomp.metrics = runtime.metrics
        self.server = ShuffleServer(self)
        transport.register_server(executor_id, self.server)
        # baseline (host-serialized) buffers share the buffer-id space with
        # spillable ones so the catalog + server treat both uniformly
        self._baseline_buffers: Dict[int, Tuple[list, object]] = {}
        self._shuffle_counter = [0]
        self._write_seq = [0]
        self._lock = threading.Lock()

    def _resolve_transport(self) -> ShuffleTransport:
        """Instantiate the conf-named transport class by reflection
        (spark.rapids.shuffle.transport.class; reference:
        RapidsConf.scala:505-510 + UCXShuffleTransport loading).  The pinned
        host pool conf sizes the transport's bounce-buffer staging area."""
        import importlib

        from ..config import (SHUFFLE_BOUNCE_CHUNK_SIZE,
                              SHUFFLE_BOUNCE_POOL_SIZE)
        name = str(self.conf.get(SHUFFLE_TRANSPORT_CLASS))
        mod_name, _, cls_name = name.rpartition(".")
        cls = getattr(importlib.import_module(mod_name), cls_name)
        # bounce geometry comes from the conf registry (single source of
        # truth, spark.rapids.shuffle.bounce.*); a configured pinned pool
        # still overrides the staging-pool size as before
        kwargs = {"max_inflight_bytes":
                  int(self.conf.get(SHUFFLE_MAX_RECV_INFLIGHT)),
                  "pool_size": int(self.conf.get(SHUFFLE_BOUNCE_POOL_SIZE)),
                  "chunk_size":
                  int(self.conf.get(SHUFFLE_BOUNCE_CHUNK_SIZE))}
        pinned = int(self.conf.get(PINNED_POOL_SIZE))
        if pinned > 0:
            kwargs["pool_size"] = pinned
        transport = cls(**kwargs)
        if hasattr(transport, "configure"):
            # retry/backoff/deadline knobs + fault-injection arming
            transport.configure(self.conf)
        return transport

    def baseline_leaves(self, buffer_id: int):
        with self._lock:
            return self._baseline_buffers.get(buffer_id)

    # ---- lifecycle ---------------------------------------------------------

    def new_shuffle_id(self) -> int:
        with self._lock:
            self._shuffle_counter[0] += 1
            return self._shuffle_counter[0]

    def rollback_received(self, shuffle_id: int, mark: int) -> None:
        """Free every remote buffer registered after `mark` (a failed
        fetch attempt's partial registrations — a retry would otherwise
        re-fetch and duplicate them in the pool while memory is
        tightest)."""
        for bid in self.received.drop_since(shuffle_id, mark):
            self.runtime.free_batch(bid)

    def remove_map_outputs(self, shuffle_id: int, map_lo: int,
                           map_hi: int) -> int:
        """Attempt-id guard: drop this executor's registered outputs for
        ONE map fragment (map ids in [map_lo, map_hi)) — buffers, serving
        cache, checksums and AQE statistics.  Called before a task re-run
        registers anything (so a retried or speculated attempt atomically
        supersedes a prior partial attempt on the same worker) and for a
        speculation loser's cleanup.  Returns the number of buffers
        dropped."""
        freed = self.catalog.remove_map_range(shuffle_id, map_lo, map_hi)
        if not freed:
            return 0
        # serving-cache eviction FIRST, same ordering as remove_shuffle:
        # a peer mid-stream must fall through to the catalog's typed
        # buffer-gone, not keep streaming a superseded attempt's bytes
        self.server.invalidate(freed)
        for bid in freed:
            with self._lock:
                if self._baseline_buffers.pop(bid, None) is not None:
                    continue
            self.runtime.free_batch(bid)
        self.map_stats.remove_map_range(shuffle_id, map_lo, map_hi)
        return len(freed)

    def remove_shuffle(self, shuffle_id: int) -> None:
        # the shuffle's map statistics go with its buffers — a long-lived
        # session would otherwise accumulate stats for every query it
        # ever ran (regression-tested in tests/test_adaptive.py)
        self.map_stats.remove_shuffle(shuffle_id)
        freed = self.catalog.remove_shuffle(shuffle_id)
        # evict serving-cache copies FIRST: a peer mid-stream on this
        # shuffle must fall through to the catalog and get the typed
        # buffer-gone frame, not keep streaming from a cache entry that
        # outlives the shuffle
        self.server.invalidate(freed)
        for bid in freed:
            with self._lock:
                if self._baseline_buffers.pop(bid, None) is not None:
                    continue
            self.runtime.free_batch(bid)
        for bid in self.received.remove_shuffle(shuffle_id):
            self.runtime.free_batch(bid)
        pol = getattr(self.runtime, "policy", None)
        if pol is not None:
            # drops next-use state AND settles wasted-prefetch accounting
            pol.shuffle_released(shuffle_id)

    # ---- write path (RapidsCachingWriter.write) ----------------------------

    def write_partition(self, shuffle_id: int, map_id: int, reduce_id: int,
                        batch: ColumnarBatch) -> None:
        block = ShuffleBlockId(shuffle_id, map_id, reduce_id)
        # map-output statistics: DATA bytes (live-row-proportional, so a
        # mostly-dead bucketed capacity does not read as a fat partition)
        # and rows.  split_by_partition stamps known_rows on every
        # sub-batch, so the common write path records without a device
        # sync; direct writers without the stamp pay one.  Recorded only
        # AFTER the buffer registers below — an OOM mid-write retries the
        # whole call, and recording first would double-count the attempt.
        nrows = batch.num_rows_host()
        nbytes = map_output_nbytes(batch.device_size_bytes(),
                                   batch.capacity, nrows)
        if self.device_resident:
            with self._lock:
                self._write_seq[0] += 1
                seq = self._write_seq[0]
            # oldest shuffle output spills first (SpillPriorities.scala)
            prio = (SpillPriorities.OUTPUT_FOR_SHUFFLE_INITIAL_PRIORITY
                    + float(seq))
            bid = self.runtime.add_batch(batch, prio)
            self.catalog.add_buffer(block, bid)
            pol = getattr(self.runtime, "policy", None)
            if pol is not None:
                # feeds victim scoring + proactive unspill: the buffer is
                # now known to be (shuffle, reduce) — dead once consumed,
                # prefetchable once an exchange declares its read order
                pol.note_shuffle_buffer(bid, shuffle_id, reduce_id, nbytes)
        else:
            from ..mem.buffer import fresh_buffer_id
            leaves, meta = batch_to_host(batch)
            bid = fresh_buffer_id()
            with self._lock:
                self._baseline_buffers[bid] = (leaves, meta)
            self.catalog.add_buffer(block, bid)
            if self.integrity.enabled:
                # host-serialized path: the host form exists right now,
                # so the per-block digest is established at WRITE time
                # (the device-resident path digests at first host
                # materialization instead — spill or first serve)
                self.catalog.set_checksums(
                    bid, self.integrity.algorithm,
                    self.integrity.checksum_leaves(leaves))
        self.map_stats.record(shuffle_id, map_id, reduce_id, nbytes, nrows)

    # ---- read path (RapidsCachingReader.read) ------------------------------

    def fetch_partition(self, shuffle_id: int, reduce_id: int,
                        remote_peers: Optional[List[str]] = None,
                        map_range: Optional[tuple] = None
                        ) -> Iterator[ColumnarBatch]:
        """Local blocks from the catalog; remote blocks via transport.
        `map_range=(lo, hi)` restricts the read to blocks written by map
        ids in [lo, hi) — the skew-join slice fetch
        (PartialReducerPartitionSpec, adaptive/stats.py)."""
        from ..metrics.journal import journal_event
        journal_event("fetch", "fetchPartition", shuffle=shuffle_id,
                      reduce=reduce_id, executor=self.executor_id,
                      remote_peers=len(remote_peers or []),
                      map_range=list(map_range) if map_range else None)
        for block in self.catalog.blocks_for_reduce(shuffle_id, reduce_id):
            if map_range is not None \
                    and not map_range[0] <= block.map_id < map_range[1]:
                continue
            for bid in self.catalog.buffers_for(block):
                baseline = self.baseline_leaves(bid)
                if baseline is not None:
                    leaves, meta = baseline
                    if self.verify_local:
                        self._verify_local_read(bid, leaves)
                    self.runtime.reserve(meta.size_bytes,
                                         site="fetch_baseline")
                    yield host_to_batch(leaves, meta)
                else:
                    # spilled tiers verify inside the runtime's
                    # materialize path (mem/runtime.py) under the spill
                    # policy; device-resident batches never left HBM
                    yield self.runtime.get_batch(bid)
        for peer in remote_peers or []:
            yield from self._fetch_remote(peer, shuffle_id, reduce_id,
                                          map_range)

    def _verify_local_read(self, bid: int, leaves) -> None:
        """verifyOnLocalRead: check a local baseline buffer against its
        write-time digest (a local read never crossed a wire, so a
        mismatch is this executor's own memory — classified `reader`)."""
        from ..metrics.journal import journal_event
        rec = self.catalog.checksums_for(bid)
        if not self.integrity.enabled or rec is None \
                or rec[0] != self.integrity.algorithm:
            return
        bad = self.integrity.verify_leaves(leaves, rec[1])
        if bad is None:
            return
        leaf, want, got = bad
        self.runtime.metrics.add(MN.NUM_CHECKSUM_MISMATCHES, 1)
        journal_event("corruption", "localReadMismatch", buffer=bid,
                      leaf=leaf, classification="reader",
                      expected=want, computed=got)
        raise CorruptShuffleBlock(
            f"local read of buffer {bid} leaf {leaf} failed "
            f"{self.integrity.algorithm} verification",
            buffer_id=bid, leaf=leaf, site="reader", expected=want,
            computed=got)

    def fetch_partitions_async(self, shuffle_id: int, reduce_ids,
                               remote_peers: Optional[List[str]] = None):
        """Pipelined multi-partition read: fetch of partition k+1 overlaps
        consumption of partition k, bounded by maxReceiveInflightBytes
        (shuffle/fetch.py; reference RapidsShuffleIterator.scala:17-258)."""
        from ..config import OOM_RETRY_MAX
        from .fetch import AsyncFetchIterator
        pol = getattr(self.runtime, "policy", None)
        return AsyncFetchIterator(
            self, shuffle_id, reduce_ids, remote_peers,
            int(self.conf.get(SHUFFLE_MAX_RECV_INFLIGHT)),
            oom_retries=int(self.conf.get(OOM_RETRY_MAX)),
            flow=pol.flow_controller() if pol is not None else None)

    def _fetch_remote(self, peer: str, shuffle_id: int, reduce_id: int,
                      map_range: Optional[tuple] = None
                      ) -> Iterator[ColumnarBatch]:
        """doFetch (RapidsShuffleClient.scala:350-770): wildcard metadata
        request discovers the peer's blocks for this reduce partition, then
        per-buffer receives register spillable buffers locally.  Everything
        goes through the transport SPI — no peer-object introspection.

        Integrity escalation ladder (SPARK-35275/36206 analogue, see
        docs/tuning-guide.md): a checksum mismatch runs the writer-side
        diagnosis and, for transit corruption, refetches up to
        `maxRefetchAttempts`; writer-side rot, a vanished buffer, or a
        dead/exhausted peer raises a typed FetchFailed that marks the map
        output lost so the cluster recomputes the fragment.

        Tracing: the whole remote read runs inside a `fetch` SPAN named
        fetchRemote, and that span's id becomes the `span` field of the
        trace context stamped on every wire request it issues — so the
        peer's serve record names THIS fetch span exactly and the merged
        timeline can flow-link the two (metrics/timeline.py)."""
        from ..metrics.journal import (active_journal, current_trace,
                                       trace_context)
        journal = active_journal()
        span_id = None
        if journal is not None:
            base = current_trace() or (None, None, None, None)
            span_id = journal.begin(
                "fetch", "fetchRemote", peer=peer, shuffle=shuffle_id,
                reduce=reduce_id, executor=self.executor_id,
                query=base[0], stage=base[1],
                map_range=list(map_range) if map_range else None)
        fetched_bytes = 0
        n_buffers = 0

        def on_wire(fn):
            # trace installed ONLY around non-yielding wire calls: a
            # with-block spanning a generator's yields would leak the
            # context into whatever the consumer runs between pulls
            with trace_context(span=span_id, executor=self.executor_id):
                return fn()

        try:
            try:
                tcomp = getattr(self.transport, "compression", None)
                client = self.transport.make_client(peer)
                if tcomp is None or not tcomp.enabled:
                    # runtime re-selection (policy/codec.py): a
                    # session WITHOUT configured wire compression rides
                    # the advised codec through the same negotiation;
                    # clients are per-fetch objects, so the override
                    # never leaks past this read
                    pol = getattr(self.runtime, "policy", None)
                    if pol is not None \
                            and pol.wire_codec(shuffle_id) is not None:
                        client.compression_override = \
                            pol.codec.reader_policy()
                        tcomp = client.compression_override
                resp = on_wire(lambda: client.fetch_metadata(
                    MetadataRequest(
                        shuffle_id=shuffle_id, reduce_id=reduce_id,
                        map_lo=map_range[0] if map_range else None,
                        map_hi=map_range[1] if map_range else None,
                        codec=tcomp.codec_name
                        if tcomp is not None and tcomp.enabled
                        else None)))
            except (ConnectionError, OSError, KeyError) as e:
                raise self._map_output_lost(peer, shuffle_id,
                                            reduce_id, "peer", e)
            for bm in resp.block_metas:
                for bid in bm.buffer_ids:
                    leaves, meta = on_wire(
                        lambda b=bid: self._fetch_buffer_verified(
                            client, peer, shuffle_id, reduce_id, b))
                    try:
                        on_wire(lambda b=bid: client.release_buffer(b))
                    except (ConnectionError, OSError) as e:
                        # the data already arrived verified; a failed
                        # release only delays the peer's cache eviction
                        log.info("release of buffer %d at %s failed: %r",
                                 bid, peer, e)
                    batch = host_to_batch(leaves, meta)
                    fetched_bytes += meta.size_bytes
                    n_buffers += 1
                    rid = self.runtime.add_batch(batch)
                    self.received.add(shuffle_id, rid)
                    yield self.runtime.get_batch(rid)
        finally:
            if journal is not None:
                journal.end(span_id, buffers=n_buffers,
                            bytes=fetched_bytes)

    def _fetch_buffer_verified(self, client, peer: str, shuffle_id: int,
                               reduce_id: int, bid: int):
        """One buffer through the corruption-recovery ladder."""
        from ..metrics.journal import journal_event
        attempts = self.max_refetch + 1
        for attempt in range(attempts):
            try:
                return client.fetch_buffer(bid)
            except BufferGone as e:
                raise self._map_output_lost(peer, shuffle_id, reduce_id,
                                            "gone", e)
            except CorruptShuffleBlock as e:
                self.runtime.metrics.add(MN.NUM_CHECKSUM_MISMATCHES, 1)
                classification = e.site if e.site in ("writer", "reader") \
                    else self._diagnose(client, bid)
                journal_event("corruption", "checksumMismatch", peer=peer,
                              shuffle=shuffle_id, reduce=reduce_id,
                              buffer=bid, leaf=e.leaf, path=e.site,
                              classification=classification,
                              expected=e.expected, computed=e.computed)
                log.warning(
                    "corrupt shuffle block from %s (buffer %d leaf %s, "
                    "classified %s, attempt %d/%d): %s", peer, bid,
                    e.leaf, classification, attempt + 1, attempts, e)
                if classification == "writer" or attempt + 1 >= attempts:
                    raise self._map_output_lost(peer, shuffle_id,
                                                reduce_id, classification,
                                                e)
                self.runtime.metrics.add(MN.NUM_CORRUPTION_REFETCHES, 1)
                journal_event("refetch", "corruptionRefetch", peer=peer,
                              buffer=bid, attempt=attempt + 1,
                              classification=classification)
            except (ConnectionError, OSError) as e:
                # the transport already burned its own socket retries; a
                # peer that still cannot serve is as good as dead
                raise self._map_output_lost(peer, shuffle_id, reduce_id,
                                            "peer", e)
        raise AssertionError("unreachable")  # pragma: no cover

    def _diagnose(self, client, bid: int) -> str:
        """Classify a reader-detected mismatch with the writer-side
        re-hash: writer (its live data no longer matches its recorded
        digest) vs wire (writer data fine -> corruption was in transit)."""
        diag = getattr(client, "diagnose_buffer", None)
        result = diag(bid) if diag is not None else None
        if result is None:
            return "wire"  # no writer evidence; transit is the default
        return "wire" if result.get("writer_ok", True) else "writer"

    def _map_output_lost(self, peer: str, shuffle_id: int, reduce_id: int,
                         classification: str, cause) -> FetchFailed:
        """Mark a peer's map output lost and build the typed FetchFailed:
        bumps the tracker epoch so any AQE statistics captured from the
        pre-loss map stage are invalidated (re-plan rules never act on a
        dead map stage), counts numLostMapOutputs, and journals the
        recompute trigger.

        Epoch-only on THIS tracker by design: the lost records live in
        the PEER's tracker, which the reader cannot reach through the
        transport SPI — ProcCluster recovery replaces the peer process
        (its tracker dies with it, so post-recompute re-aggregation is
        clean), and an owner that detects its OWN rot drops the records
        itself via `mark_lost` on the serve path (_leaves)."""
        from ..metrics.journal import journal_event
        self.runtime.metrics.add(MN.NUM_LOST_MAP_OUTPUTS, 1)
        self.map_stats.bump_epoch()
        journal_event("recompute", "mapOutputLost", peer=peer,
                      shuffle=shuffle_id, reduce=reduce_id,
                      classification=classification, cause=repr(cause))
        log.error("map output lost: shuffle %d reduce %d at %s (%s): %r",
                  shuffle_id, reduce_id, peer, classification, cause)
        return FetchFailed(
            f"shuffle {shuffle_id} reduce {reduce_id} fetch from {peer} "
            f"failed unrecoverably ({classification}): {cause}",
            peer=peer, shuffle_id=shuffle_id, reduce_id=reduce_id,
            classification=classification)


def map_output_nbytes(device_size_bytes: int, capacity: int,
                      nrows: int) -> int:
    """Map-output-statistics DATA bytes of one written sub-batch:
    live-row-proportional, so a mostly-dead bucketed capacity does not
    read as a fat partition.  ONE formula for both shuffle tiers — the
    socket write path calls it with a real sub-batch's footprint, the
    mesh tier (shuffle/mesh_exchange.py) with the synthetic footprint of
    the sub-batch `split_by_partition` WOULD build — so AQE rules see
    bit-identical statistics wherever the exchange ran (capacities are
    power-of-two buckets, so the division is exact in float)."""
    cap = max(capacity, 1)
    return int(device_size_bytes * min(nrows, cap) / cap)


def get_shuffle_env(runtime: TpuRuntime, conf: TpuConf) -> ShuffleEnv:
    """Lazily attach one ShuffleEnv to a runtime (executor singleton)."""
    env = getattr(runtime, "_shuffle_env", None)
    if env is None:
        env = ShuffleEnv(runtime, conf)
        runtime._shuffle_env = env
    return env
