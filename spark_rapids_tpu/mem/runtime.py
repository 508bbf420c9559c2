"""Per-process device runtime: HBM pool accounting + spill stores + semaphore.

TPU-native analogue of GpuDeviceManager + GpuShuffleEnv wiring
(sql-plugin/.../rapids/GpuDeviceManager.scala:120-243 — RMM pool init with
allocFraction of device memory, pinned pool; GpuShuffleEnv.scala:57-107 —
store construction + OOM event handler install;
DeviceMemoryEventHandler.scala:38-90 — on alloc failure, synchronously spill
the device store and retry).

XLA owns the real HBM allocator, so the pool here is an *accounting* pool:
every registered batch counts its static footprint against
allocFraction * hbm_total, and `reserve()` is the allocation boundary where
the OOM->spill hook runs.  This is the same contract the reference gets from
RMM's onAllocFailure callback, enforced one level up.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Optional

from ..columnar import ColumnarBatch
from ..config import (CONCURRENT_TPU_TASKS, HOST_SPILL_STORAGE_SIZE,
                      TPU_DEBUG, TPU_OOM_SPILL_ENABLED, TpuConf)
from ..metrics import names as MN
from ..metrics.journal import journal_event
from ..utils import faults
from .buffer import SpillPriorities, StorageTier, host_to_batch
from .retry import RetryOOM
from .semaphore import TpuSemaphore
from .stores import (BufferCatalog, DeviceMemoryStore, DiskStore,
                     HostMemoryStore, SpillableBuffer)


def _detect_hbm_bytes() -> int:
    """Total device memory of the first device, from its own
    memory_stats().  On the tpu platform missing stats are an error: a
    guessed pool would mis-size the accounting against the real chip.
    Backends without device memory (the CPU reports no stats) account
    against a nominal 16GiB."""
    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    try:
        stats = dev.memory_stats() or {}
    except Exception as e:  # noqa: BLE001 — a backend may lack stats
        if on_tpu:
            raise
        from ..metrics.registry import count_swallowed
        count_swallowed("numHbmDetectFallbacks", "spark_rapids_tpu.mem",
                        "device memory_stats unavailable (%r); sizing the "
                        "accounted pool from the nominal 16GiB", e,
                        warn=True)
        stats = {}
    for key in ("bytes_limit", "bytes_reservable_limit"):
        if stats.get(key):
            return int(stats[key])
    if on_tpu:
        raise RuntimeError(
            f"{dev} reports no memory limit (memory_stats()={stats!r}); "
            "set spark.rapids.memory.tpu.poolSizeBytes explicitly")
    return 16 << 30


def configured_pool_bytes(conf) -> int:
    """Session-level accounted pool budget: the absolute
    spark.rapids.memory.tpu.poolSizeBytes when set (> 0), else
    allocFraction of detected HBM.  The ONE rule every construction site
    derives from — the engine's cluster-mode halving and TpuCluster's
    per-executor split divide THIS figure, so an explicit byte budget
    stays authoritative in multi-executor deployments too."""
    from ..config import TPU_ALLOC_FRACTION, TPU_POOL_SIZE
    explicit = int(conf.get(TPU_POOL_SIZE))
    if explicit > 0:
        return explicit
    return int(_detect_hbm_bytes() * float(conf.get(TPU_ALLOC_FRACTION)))


class DeviceMemoryEventHandler:
    """OOM->spill hook (DeviceMemoryEventHandler.scala:38-90).

    `retry_count` is the spill-retry count of the CURRENT allocation
    attempt (reset by `reserve()` per attempt); cumulative figures flow
    into the runtime `metrics` so retries and spilled bytes are observable
    from `pool_stats()`."""

    def __init__(self, device_store: DeviceMemoryStore, debug: str = "NONE",
                 metrics=None, ledger=None):
        self.device_store = device_store
        self.debug = debug
        self.metrics = metrics
        self.ledger = ledger
        self.retry_count = 0

    def on_alloc_failure(self, alloc_size: int,
                         site: Optional[str] = None,
                         limit: Optional[int] = None) -> bool:
        """Spill the device store down by `alloc_size`; True = retry the
        allocation.  `site` is the reservation label reserve() already
        knows — journaled so OOM-driven spills are site-attributable —
        and the ledger adds the causal reservation id + the exact victim
        buffer ids this round's synchronous_spill evicted."""
        store_size = self.device_store.current_size
        target = max(0, store_size - alloc_size)
        # spillTime: the 'spill' phase of the serving SLO histograms and
        # the roofline ledger's wait-vs-work split.  Also accumulated on
        # the CALLING thread's query scope — the runtime metric is
        # shared, so under concurrent serving only the scope can say
        # WHICH query's reservation paid the cascade.
        t0 = time.perf_counter()
        spilled = self.device_store.synchronous_spill(target)
        dt = time.perf_counter() - t0
        if self.metrics is not None:
            self.metrics.add(MN.SPILL_TIME, dt)
        if self.ledger is not None:
            scope = self.ledger.current_query_scope()
            if scope is not None:
                scope.spill_seconds += dt
        if self.debug in ("STDOUT", "STDERR"):
            out = sys.stdout if self.debug == "STDOUT" else sys.stderr
            print(f"[tpu-mem] alloc failure of {alloc_size}B: spilled "
                  f"{spilled}B from device store", file=out)
        self.retry_count += 1
        if self.metrics is not None:
            self.metrics.add(MN.OOM_SPILL_RETRIES, 1)
            self.metrics.add(MN.OOM_SPILL_BYTES, spilled)
        extra = {}
        if self.ledger is not None:
            # the ledger record carries the causal chain (reservation id
            # + victim buffer ids); the legacy spill record mirrors the
            # site/victims so both views of the event agree
            extra = self.ledger.on_oom_spill(alloc_size, spilled,
                                             store_size, limit=limit)
        journal_event("spill", "oomSpill", alloc_size=alloc_size,
                      spilled_bytes=spilled, store_size=store_size,
                      site=site if site is not None else extra.get("site"),
                      **{k: v for k, v in extra.items()
                         if k in ("cause", "victims")})
        return spilled > 0


class TpuRuntime:
    """Executor-singleton services (one per TpuSession/process)."""

    def __init__(self, conf: Optional[TpuConf] = None,
                 pool_limit_bytes: Optional[int] = None,
                 spill_dir: Optional[str] = None):
        self.conf = conf or TpuConf()
        faults.INJECTOR.configure_from_conf(self.conf)
        self.pool_limit = (pool_limit_bytes if pool_limit_bytes is not None
                           else configured_pool_bytes(self.conf))
        from ..exec.base import Metrics
        self.metrics = Metrics()
        self.catalog = BufferCatalog()
        # spill-path integrity: the stores digest host leaves at spill
        # time and every later movement verifies through the catalog's
        # policy (mem/integrity.py; conf spark.rapids.memory.spill.*)
        from ..config import SHUFFLE_CHECKSUM_ALGO, SPILL_CHECKSUM_ENABLED
        from .integrity import ChecksumPolicy
        self.catalog.integrity = ChecksumPolicy(
            bool(self.conf.get(SPILL_CHECKSUM_ENABLED)),
            str(self.conf.get(SHUFFLE_CHECKSUM_ALGO)),
            metrics=self.metrics)
        # spill compression (compress/): host->disk writes run through a
        # codec when spark.rapids.memory.spill.compression.codec says so,
        # independently of the shuffle wire codec
        from ..compress import compression_from_conf
        from ..config import SPILL_COMPRESSION_CODEC
        self.catalog.compression = compression_from_conf(
            self.conf, metrics=self.metrics,
            codec_entry=SPILL_COMPRESSION_CODEC)
        self.device_store = DeviceMemoryStore(self.catalog)
        self.host_store = HostMemoryStore(
            self.catalog, int(self.conf.get(HOST_SPILL_STORAGE_SIZE)))
        self.disk_store = DiskStore(self.catalog, spill_dir)
        self.device_store.spill_store = self.host_store
        self.host_store.spill_store = self.disk_store
        # memory-pressure ledger (mem/ledger.py): the catalog carries it
        # (like integrity/compression) so the stores' spill path can
        # append causally-linked records without plumbing
        from ..config import (MEM_LEDGER_ENABLED, MEM_LEDGER_SAMPLE_MS,
                              METRICS_LEVEL)
        from .ledger import MemoryLedger
        self.ledger = MemoryLedger(
            enabled=bool(self.conf.get(MEM_LEDGER_ENABLED)),
            debug=str(self.conf.get(METRICS_LEVEL)).upper() == "DEBUG",
            sample_interval_ms=int(self.conf.get(MEM_LEDGER_SAMPLE_MS)),
            metrics=self.metrics, pools=self._pressure_sample)
        self.catalog.ledger = self.ledger
        self.event_handler = DeviceMemoryEventHandler(
            self.device_store, str(self.conf.get(TPU_DEBUG)).upper(),
            self.metrics, ledger=self.ledger)
        self.oom_spill = bool(self.conf.get(TPU_OOM_SPILL_ENABLED))
        self.semaphore = TpuSemaphore(
            int(self.conf.get(CONCURRENT_TPU_TASKS)), metrics=self.metrics)
        # data-movement policy engine (policy/): rides the catalog like
        # integrity/compression/ledger so the stores' victim pick can
        # consult next-use scores without plumbing; holds only a weakref
        # back to this runtime (a collected runtime ends its thread)
        from ..policy import MovementPolicy
        self.policy = MovementPolicy(self.conf, runtime=self)
        self.catalog.policy = self.policy
        self._lock = threading.Lock()

    # ---- allocation boundary ----------------------------------------------

    def reserve(self, nbytes: int, site: str = "reserve") -> None:
        """Account for an upcoming device allocation; spill if over budget.

        Raises RetryOOM (a MemoryError) when the pool cannot be brought
        under budget (mirrors RMM throwing after the event handler declines
        to retry); retryable blocks (mem/retry.py with_retry) catch it,
        re-spill/split and re-enter here.  `site` labels the call for the
        fault injector and test observability."""
        # lifecycle checkpoint (serve/lifecycle.py): reserve() guards
        # every whole-batch device allocation, which makes it the ONE
        # universal cancel/deadline yield point — a cancelled or
        # past-deadline query raises (typed, non-MemoryError: the retry
        # ladder must never retry it) BEFORE committing more memory.
        # Suspension is not allowed here (stage boundaries only); the
        # no-token path reads one attribute and moves on.
        scope0 = self.ledger.current_query_scope()
        if scope0 is not None and scope0.lifecycle is not None:
            scope0.lifecycle.check()
        faults.INJECTOR.on_reserve(site, nbytes)
        self.event_handler.retry_count = 0  # fresh allocation attempt
        with self.ledger.reservation(site, nbytes):
            # serving-tier per-query budget (mem/ledger.py QueryScope):
            # enforced FIRST and confined to the query's own buffers, so
            # a hog hits its cap and spills itself before it can push
            # the shared pool into spilling its neighbors
            scope = self.ledger.current_query_scope()
            if scope is not None and scope.budget > 0:
                self._enforce_query_budget(scope, nbytes, site)
            for _ in range(8):  # bounded retry loop
                used = self.device_store.current_size
                if used + nbytes <= self.pool_limit:
                    return
                if not (self.oom_spill
                        and self.event_handler.on_alloc_failure(
                            nbytes, site=site, limit=self.pool_limit)):
                    break
            used = self.device_store.current_size
            if used + nbytes > self.pool_limit:
                self.metrics.add(MN.OOM_ALLOC_FAILURES, 1)
                self.ledger.on_oom_fail(site, nbytes, used,
                                        self.pool_limit)
                raise RetryOOM(
                    f"HBM pool exhausted at {site}: need {nbytes}B, used "
                    f"{used}B of {self.pool_limit}B and nothing left to "
                    f"spill", nbytes=nbytes)

    def _enforce_query_budget(self, scope, nbytes: int, site: str) -> None:
        """Per-query device-bytes cap (serving tier): spill the query's
        OWN buffers down to budget, then raise RetryOOM into ITS retry
        ladder (spill-retry -> split -> CPU fallback) — the existing
        machinery, scoped to one query.  Victim selection never touches
        other queries' buffers, so the ledger's spill causality chains
        stay within the over-budget query (tests assert this)."""
        owner, budget = scope.query, scope.budget
        target = max(0, budget - nbytes)
        for _ in range(8):  # bounded like the global loop below
            used = self.device_store.owner_size(owner)
            if used + nbytes <= budget:
                return
            if not self.oom_spill:
                break
            store_size = self.device_store.current_size
            t0 = time.perf_counter()
            spilled = self.device_store.synchronous_spill(
                target, owner=owner)
            dt = time.perf_counter() - t0
            self.metrics.add(MN.SPILL_TIME, dt)
            scope.spill_seconds += dt
            extra = self.ledger.on_oom_spill(nbytes, spilled, store_size,
                                             limit=budget,
                                             budget_owner=owner)
            journal_event("spill", "oomSpill", alloc_size=nbytes,
                          spilled_bytes=spilled, store_size=store_size,
                          site=site, budget_owner=owner,
                          **{k: v for k, v in extra.items()
                             if k in ("cause", "victims")})
            if spilled <= 0:
                break
        used = self.device_store.owner_size(owner)
        if used + nbytes > budget:
            self.metrics.add(MN.NUM_BUDGET_OOMS, 1)
            self.ledger.on_oom_fail(site, nbytes, used, budget,
                                    budget_owner=owner)
            raise RetryOOM(
                f"per-query budget exhausted for {owner} at {site}: need "
                f"{nbytes}B, query holds {used}B of its {budget}B budget "
                "and has nothing of its own left to spill", nbytes=nbytes)

    # ---- spillable batch registry ------------------------------------------

    @property
    def _debug_on(self) -> bool:
        return self.event_handler.debug in ("STDOUT", "STDERR")

    def _debug_log(self, msg: str) -> None:
        """Allocation forensics stream (reference:
        spark.rapids.memory.gpu.debug=stdout|stderr RMM logging,
        RapidsConf.scala:227-234).  Callers guard on _debug_on so the
        disabled (default) path formats nothing and takes no store lock."""
        mode = self.event_handler.debug
        print(f"[tpu-mem] {msg}",
              file=sys.stdout if mode == "STDOUT" else sys.stderr)

    def add_batch(self, batch: ColumnarBatch,
                  spill_priority: float = SpillPriorities.DEFAULT_PRIORITY
                  ) -> int:
        """Register a device batch as spillable; returns its buffer id."""
        nbytes = batch.device_size_bytes()
        self.reserve(nbytes, site="add_batch")
        bid = self.device_store.add_batch(batch, spill_priority,
                                          site="add_batch").id
        if self._debug_on:
            self._debug_log(f"alloc id={bid} {nbytes}B "
                            f"pool={self.device_store.current_size}B")
        return bid

    def get_batch(self, buffer_id: int) -> ColumnarBatch:
        """Materialize a registered batch on device, from whatever tier it
        currently occupies (the read path of RapidsBuffer.getColumnarBatch)."""
        self.policy.note_access(buffer_id)  # prefetch-hit accounting
        buf = self.catalog.acquire(buffer_id)
        try:
            return self._materialize(buf)
        finally:
            self.catalog.release(buf)

    def _materialize(self, buf: SpillableBuffer) -> ColumnarBatch:
        """Return the batch on device, *promoting* the buffer back to the
        device tier so the HBM pool keeps accounting for exactly one copy
        (unlike the reference, which hands out an untracked transient device
        copy — RMM tracks that copy for it; our accounting pool must)."""
        from .stores import read_spilled_leaves, verify_buffer_leaves
        with buf.lock:
            if buf.tier == StorageTier.DEVICE:
                return buf.device_batch
            if buf.tier == StorageTier.HOST:
                leaves, src = buf.host_leaves, self.host_store
                verify_buffer_leaves(self.catalog, buf, leaves,
                                     site="unspill_host")
            else:
                # read_spilled_leaves verifies a COMPRESSED image before
                # decompressing; the decompressed (or raw) leaves then
                # re-verify against the original spill digests here
                leaves, src = read_spilled_leaves(self.catalog, buf), \
                    self.disk_store
                verify_buffer_leaves(self.catalog, buf, leaves,
                                     site="unspill_disk")
            from_tier = buf.tier
            self.reserve(buf.size_bytes, site="materialize")
            batch = host_to_batch(leaves, buf.meta)
            src.untrack(buf)
            if buf.disk_path:
                self.disk_store.delete_file(buf)
            buf.host_leaves = None
            buf.host_checksums = None  # stale once the device copy is live
            buf.device_batch = batch
            self.device_store.track(buf)
            self.ledger.on_unspill(buf.id, buf.size_bytes, from_tier)
            return batch

    def free_batch(self, buffer_id: int) -> None:
        buf = self.catalog.remove(buffer_id)
        if buf is None:
            if self._debug_on:
                self._debug_log(f"free id={buffer_id} DOUBLE-FREE "
                                "(already removed)")
            return
        self.ledger.on_free(buf.id, buf.size_bytes, buf.tier)
        for store in (self.device_store, self.host_store, self.disk_store):
            store.untrack(buf)
        if buf.disk_path:
            self.disk_store.delete_file(buf)
        buf.device_batch = None
        buf.host_leaves = None
        if self._debug_on:
            self._debug_log(f"free id={buffer_id} {buf.size_bytes}B "
                            f"pool={self.device_store.current_size}B")

    def release_owner(self, owner: Optional[str]) -> int:
        """Free every buffer stamped with `owner` across all three tiers
        — the owner-confined cleanup a cancelled/past-deadline query runs
        after its shuffle cleanups, so a killed query can never leak pool
        bytes (its buffers are its own by construction: PR 10's owner
        stamps come from the thread-local query scope).  Returns the
        bytes freed.  Idempotent: free_batch tolerates already-removed
        ids, and a query that leaked nothing frees nothing."""
        if not owner:
            return 0
        freed = 0
        for store in (self.device_store, self.host_store, self.disk_store):
            for bid, nbytes in store.owner_buffers(owner):
                freed += nbytes
                self.free_batch(bid)
        return freed

    def update_priority(self, buffer_id: int, priority: float) -> None:
        buf = self.catalog.acquire(buffer_id)
        try:
            for store in (self.device_store, self.host_store,
                          self.disk_store):
                if buf.tier == store.tier:
                    store.update_priority(buf, priority)
                    return
        finally:
            self.catalog.release(buf)

    # ---- stats -------------------------------------------------------------

    def _pressure_sample(self) -> dict:
        """Per-tier snapshot the ledger samples into `pressure` records
        (the memory lane): cheap — four lock-guarded int reads."""
        return {
            "limit": self.pool_limit,
            "device": self.device_store.current_size,
            "host": self.host_store.current_size,
            "disk": self.disk_store.current_size,
        }

    def pool_stats(self) -> dict:
        stats = {
            "pool_limit": self.pool_limit,
            "device_used": self.device_store.current_size,
            "host_used": self.host_store.current_size,
            "disk_used": self.disk_store.current_size,
            # per-tier high-water marks (reset-aware via reset_peaks):
            # what the heartbeat monitor rolls up into cluster peak memory
            "device_peak": self.device_store.peak_size,
            "host_peak": self.host_store.peak_size,
            "disk_peak": self.disk_store.peak_size,
        }
        stats.update(self.metrics.values)
        return stats

    def reset_peaks(self) -> None:
        """Rebase every store's high-water mark to its CURRENT usage —
        per-interval peak tracking (a monitoring scrape that wants
        peak-since-last-scrape resets after reading pool_stats())."""
        for store in (self.device_store, self.host_store, self.disk_store):
            store.reset_peak()
