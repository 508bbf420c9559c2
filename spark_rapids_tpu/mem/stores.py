"""Three-tier spillable buffer stores + catalog.

TPU-native analogue of the reference's spill framework
(sql-plugin/.../rapids/RapidsBufferStore.scala:40-307 — per-store
BufferTracker ordered by spill priority, synchronousSpill at 141-241;
RapidsBufferCatalog.scala:30-52 — id->buffer lookup with ref-count acquire;
RapidsDeviceMemoryStore.scala / RapidsHostMemoryStore.scala /
RapidsDiskStore.scala).

Differences from the reference, deliberate for TPU:
  * XLA owns HBM, so the device tier holds jnp-array batches and accounts
    for their static footprint instead of sub-allocating an RMM pool;
    "freeing" device memory = dropping the last Python reference so XLA's
    allocator can reuse the pages.
  * One SpillableBuffer object migrates between tiers (the reference copies
    into a new RapidsBuffer per tier); the catalog maps id -> that object.
"""
from __future__ import annotations

import os
import tempfile
import threading
from typing import Dict, List, Optional

from ..columnar import ColumnarBatch
from ..utils import faults
from .buffer import (BatchMeta, SpillPriorities, StorageTier, batch_to_host,
                     fresh_buffer_id, host_leaves_nbytes, host_to_batch,
                     read_leaves, write_leaves)
from .integrity import CorruptBuffer
from .priority_queue import HashedPriorityQueue


def verify_buffer_leaves(catalog: "BufferCatalog", buf: "SpillableBuffer",
                         leaves, site: str) -> None:
    """Verify a buffer's host-form leaves against the checksums recorded
    at spill time; raises a typed CorruptBuffer (journaled + counted) on
    the first mismatching leaf.  No-op when the catalog carries no
    integrity policy or the buffer was never checksummed."""
    policy = getattr(catalog, "integrity", None)
    if policy is None or not policy.enabled or buf.host_checksums is None:
        return
    bad = policy.verify_leaves(leaves, buf.host_checksums)
    if bad is None:
        return
    leaf, want, got = bad
    if policy.metrics is not None:
        from ..metrics import names as MN
        policy.metrics.add(MN.NUM_CHECKSUM_MISMATCHES, 1)
    from ..metrics.journal import journal_event
    journal_event("corruption", "spillChecksumMismatch", buffer=buf.id,
                  leaf=leaf, site=site, algorithm=policy.algorithm,
                  expected=want, computed=got)
    raise CorruptBuffer(
        f"buffer {buf.id} leaf {leaf} failed {policy.algorithm} "
        f"verification at {site}: expected {want:#x}, computed {got:#x}",
        buffer_id=buf.id, leaf=leaf, site=site, expected=want,
        computed=got)


def read_spilled_leaves(catalog: "BufferCatalog",
                        buf: "SpillableBuffer") -> List:
    """Disk-tier leaves of a buffer, decompressing when the spill file
    was written through a codec (HostMemoryStore spill compression).

    Ladder order matters: the COMPRESSED image verifies against its
    spill-time digests FIRST — a flipped bit in the file raises a typed
    CorruptBuffer (site `disk_read`) and never reaches a decompressor —
    and only then do the decompressed leaves go back to the caller, whose
    existing verify_buffer_leaves pass re-checks them against the
    original (uncompressed) spill digests."""
    import numpy as np

    from .buffer import read_leaves, shape_leaves
    if buf.disk_codec is None:
        return read_leaves(buf.disk_path, buf.meta)
    from ..compress import resolve_codec
    from ..native import spill_read
    sizes = buf.disk_comp_sizes
    raw = spill_read(buf.disk_path, sum(sizes))
    frames = []
    off = 0
    for nb in sizes:
        frames.append(np.frombuffer(raw, np.uint8, count=nb, offset=off))
        off += nb
    policy = getattr(catalog, "integrity", None)
    if policy is not None and policy.enabled \
            and buf.disk_checksums is not None:
        bad = policy.verify_leaves(frames, buf.disk_checksums)
        if bad is not None:
            leaf, want, got = bad
            if policy.metrics is not None:
                from ..metrics import names as MN
                policy.metrics.add(MN.NUM_CHECKSUM_MISMATCHES, 1)
            from ..metrics.journal import journal_event
            journal_event("corruption", "spillChecksumMismatch",
                          buffer=buf.id, leaf=leaf, site="disk_read",
                          algorithm=policy.algorithm, expected=want,
                          computed=got, codec=buf.disk_codec)
            raise CorruptBuffer(
                f"buffer {buf.id} compressed spill leaf {leaf} failed "
                f"{policy.algorithm} verification at disk_read: expected "
                f"{want:#x}, computed {got:#x}", buffer_id=buf.id,
                leaf=leaf, site="disk_read", expected=want, computed=got)
    cpol = getattr(catalog, "compression", None)
    codec = resolve_codec(buf.disk_codec)
    if cpol is not None:
        flats = cpol.decompress_leaves(frames, codec)
        if cpol.metrics is not None:
            from ..metrics import names as MN
            cpol.metrics.add(MN.COMPRESSED_SPILL_BYTES_READ, sum(sizes))
    else:
        from ..compress import frame_decompress
        flats = [frame_decompress(codec, f) for f in frames]
    return shape_leaves(flats, buf.meta)


class SpillableBuffer:
    """A registered, spillable columnar batch.

    Ref-counting discipline mirrors RapidsBuffer.addReference/free
    (RapidsBuffer.scala): a buffer with live references cannot be spilled;
    `close()` drops one reference; `free()` removes it from its store."""

    def __init__(self, buffer_id: int, meta: BatchMeta,
                 spill_priority: float):
        self.id = buffer_id
        self.meta = meta
        self.spill_priority = spill_priority
        self.tier = StorageTier.DEVICE
        # owning query (serving tier, mem/ledger.py QueryScope): set at
        # registration from the thread's active query scope; per-query
        # budgets account and spill by this tag.  None = unowned
        # (single-query sessions, helper threads).
        self.owner = None
        self.ref_count = 0
        self.freed = False
        # guards ref_count and tier migration: spilling re-checks ref_count
        # under this lock, acquire increments under it, so a reader can
        # never observe a half-migrated buffer
        self.lock = threading.RLock()
        # tier payloads (exactly one is set, per current tier)
        self.device_batch: Optional[ColumnarBatch] = None
        self.host_leaves = None
        self.disk_path: Optional[str] = None
        # per-leaf digests recorded at device->host spill time; verified
        # on every later movement of the host/disk form (stores.py
        # verify_buffer_leaves) and cleared on re-promotion to device
        self.host_checksums = None
        # spill compression (compress/): when the host->disk write ran
        # through a codec, the file holds FRAMED leaves — codec name,
        # per-leaf framed sizes (the file layout), and digests over the
        # compressed image verified at disk read BEFORE decompression
        self.disk_codec: Optional[str] = None
        self.disk_comp_sizes: Optional[List[int]] = None
        self.disk_checksums = None

    @property
    def size_bytes(self) -> int:
        return self.meta.size_bytes

    def __repr__(self):  # pragma: no cover
        return (f"SpillableBuffer(id={self.id}, tier={self.tier.name}, "
                f"size={self.size_bytes}, refs={self.ref_count})")


class BufferStore:
    """One tier's tracker: insertion-ordered within equal priority, spillable
    candidates ordered by (priority, id) — lower spills first
    (RapidsBufferStore.scala BufferTracker)."""

    tier: StorageTier

    def __init__(self, catalog: "BufferCatalog"):
        self.catalog = catalog
        self.spill_store: Optional["BufferStore"] = None
        self._buffers: Dict[int, SpillableBuffer] = {}
        self._queue: HashedPriorityQueue[int] = HashedPriorityQueue(
            self._priority_of)
        self._size = 0
        self._peak = 0
        # per-owning-query tracked bytes (serving-tier budgets); entries
        # die when they reach zero, so idle sessions cost nothing
        self._owner_sizes: Dict[str, int] = {}
        # scored victim picks accumulated under the lock, journaled by
        # synchronous_spill AFTER it releases (journal taps never run
        # under a store lock — same discipline as _spill_one's ledger)
        self._pending_decisions: List[dict] = []
        self._lock = threading.RLock()

    def _priority_of(self, buffer_id: int):
        # (priority, id): equal-priority victims order by id — creation
        # order — NOT heap/dict insertion accidents, so victim sequences
        # (and the memory ledger's churn records) reproduce across
        # processes
        b = self._buffers[buffer_id]
        return (b.spill_priority, buffer_id)

    @property
    def current_size(self) -> int:
        with self._lock:
            return self._size

    @property
    def peak_size(self) -> int:
        """High-water mark of tracked bytes since construction (or the
        last reset_peak) — pool_stats() device_peak/host_peak/disk_peak."""
        with self._lock:
            return self._peak

    def reset_peak(self) -> None:
        """Rebase the high-water mark to current usage (reset-aware
        peak tracking for interval scrapes)."""
        with self._lock:
            self._peak = self._size

    def track(self, buf: SpillableBuffer) -> None:
        with self._lock:
            self._buffers[buf.id] = buf
            self._queue.offer(buf.id)
            self._size += buf.size_bytes
            if self._size > self._peak:
                self._peak = self._size
            if buf.owner is not None:
                self._owner_sizes[buf.owner] = \
                    self._owner_sizes.get(buf.owner, 0) + buf.size_bytes
            buf.tier = self.tier

    def untrack(self, buf: SpillableBuffer) -> None:
        with self._lock:
            self._untrack_locked(buf)

    def _untrack_locked(self, buf: SpillableBuffer) -> None:
        """Drop a buffer from this store's tracking structures — the ONE
        place size AND owner bookkeeping decrement, so every removal
        path (untrack, synchronous_spill's victim pop) stays balanced
        against track()'s increments."""
        if buf.id in self._buffers:
            del self._buffers[buf.id]
            self._queue.remove(buf.id)
            self._size -= buf.size_bytes
            if buf.owner is not None:
                left = self._owner_sizes.get(buf.owner, 0) \
                    - buf.size_bytes
                if left > 0:
                    self._owner_sizes[buf.owner] = left
                else:
                    self._owner_sizes.pop(buf.owner, None)

    def owner_size(self, owner: Optional[str]) -> int:
        """Bytes this store tracks for one owning query (0 for None —
        unowned buffers never count against a budget)."""
        if owner is None:
            return 0
        with self._lock:
            return self._owner_sizes.get(owner, 0)

    def owner_buffers(self, owner: Optional[str]) -> List[tuple]:
        """(buffer id, size) of every buffer this store tracks for one
        owning query, id-ascending — the enumeration owner-confined
        cleanup walks (runtime.release_owner) when a cancelled or
        past-deadline query's remaining buffers must be freed."""
        if owner is None:
            return []
        with self._lock:
            return sorted((bid, b.size_bytes)
                          for bid, b in self._buffers.items()
                          if b.owner == owner)

    def update_priority(self, buf: SpillableBuffer, priority: float) -> None:
        with self._lock:
            buf.spill_priority = priority
            if buf.id in self._buffers:
                self._queue.update_priority(buf.id)

    def spill_candidates(self, owner: Optional[str] = None) -> List[int]:
        """Spillable buffer ids (unreferenced, owner-confined when asked)
        in the exact order synchronous_spill would consider them:
        (spill_priority, id) ascending.  The stable ordering API policy
        scoring and tests rank against — deterministic for a given set
        of live buffers regardless of heap/dict insertion history."""
        with self._lock:
            return sorted(
                (bid for bid, b in self._buffers.items()
                 if b.ref_count == 0
                 and (owner is None or b.owner == owner)),
                key=self._priority_of)

    def synchronous_spill(self, target_size: int,
                          owner: Optional[str] = None) -> int:
        """Migrate lowest-priority unreferenced buffers to the next tier
        until this store holds <= target_size bytes.  Returns bytes spilled
        (RapidsBufferStore.synchronousSpill, RapidsBufferStore.scala:141-241).
        With `owner`, both the size bound and the victim pool are confined
        to that query's buffers — per-query budget enforcement spills the
        hog itself, never its neighbors (mem/ledger.py QueryScope)."""
        try:
            return self._synchronous_spill(target_size, owner)
        finally:
            self._flush_decisions()

    def _synchronous_spill(self, target_size: int,
                           owner: Optional[str]) -> int:
        spilled = 0
        while True:
            with self._lock:
                cur = self._size if owner is None \
                    else self._owner_sizes.get(owner, 0)
                if cur <= target_size:
                    return spilled
                victim = self._pick_victim(owner)
                if victim is None:
                    return spilled  # nothing spillable (all referenced)
                # balanced removal (size AND owner bytes): the requeue
                # paths below re-track(), which re-increments both
                self._untrack_locked(victim)
            # migrate outside the store lock, pinned by the buffer lock; the
            # timeout bounds any cross-wait with a concurrent reader
            if not victim.lock.acquire(timeout=1.0):
                self.track(victim)
                return spilled
            try:
                if victim.freed:
                    continue
                if victim.ref_count > 0:  # acquired since we picked it
                    self.track(victim)
                    continue
                self._spill_one(victim)
                spilled += victim.size_bytes
            finally:
                victim.lock.release()

    def _pick_victim(self, owner: Optional[str] = None
                     ) -> Optional[SpillableBuffer]:
        policy = getattr(self.catalog, "policy", None)
        if policy is not None and policy.wants_victim_scoring():
            return self._pick_victim_scored(policy, owner)
        # scan from the head of the priority queue for an unreferenced
        # buffer (owned by `owner`, when confined)
        skipped: List[int] = []
        victim = None
        while True:
            bid = self._queue.poll()
            if bid is None:
                break
            b = self._buffers[bid]
            if b.ref_count == 0 and (owner is None or b.owner == owner):
                victim = b
                break
            skipped.append(bid)
        for bid in skipped:
            self._queue.offer(bid)
        if victim is not None:
            self._queue.offer(victim.id)  # restored; caller removes
        return victim

    def _pick_victim_scored(self, policy, owner: Optional[str]
                            ) -> Optional[SpillableBuffer]:
        """Victim by next-use score (policy/engine.py scores_for; lower
        spills first), ties broken by the baseline (priority, id) order
        so an engine that knows nothing picks EXACTLY the baseline
        victim.  Records every pick (and whether it overrode the
        baseline) for the post-lock decision flush."""
        cands = self.spill_candidates(owner)
        if not cands:
            return None
        baseline = min(cands, key=self._priority_of)
        scores = policy.scores_for(cands)
        victim_id = min(cands, key=lambda bid: (scores.get(bid, 1.0),)
                        + tuple(self._priority_of(bid)))
        self._pending_decisions.append({
            "buffer": victim_id,
            "baseline": baseline,
            "overridden": victim_id != baseline,
            "score": scores.get(victim_id, 1.0),
            "owner": owner,
        })
        return self._buffers[victim_id]

    def _flush_decisions(self) -> None:
        """Journal + count the scored picks accumulated during a spill
        sweep — OUTSIDE the store lock (journal taps may block)."""
        with self._lock:
            if not self._pending_decisions:
                return
            decisions, self._pending_decisions = \
                self._pending_decisions, []
        policy = getattr(self.catalog, "policy", None)
        if policy is None:
            return
        for d in decisions:
            policy.record_victim(self.tier, d)

    def _spill_one(self, buf: SpillableBuffer) -> None:
        assert self.spill_store is not None, \
            f"{type(self).__name__} has no spill target"
        self._release_payload_to(buf, self.spill_store)
        self.spill_store.track(buf)
        ledger = getattr(self.catalog, "ledger", None)
        if ledger is not None:
            # causal spill record: the ledger links this eviction to the
            # reservation that forced it (mem/ledger.py) and detects
            # spill churn (the same buffer spilled again after coming
            # back).  Emitted AFTER the migration so the record only
            # ever describes a spill that actually happened.
            ledger.on_spill(buf.id, buf.size_bytes, self.tier,
                            self.spill_store.tier, owner=buf.owner)

    def _release_payload_to(self, buf: SpillableBuffer,
                            dest: "BufferStore") -> None:
        raise NotImplementedError


class DeviceMemoryStore(BufferStore):
    """HBM tier (RapidsDeviceMemoryStore.scala; addTable at :40)."""

    tier = StorageTier.DEVICE

    def add_batch(self, batch: ColumnarBatch,
                  spill_priority: float = SpillPriorities.DEFAULT_PRIORITY,
                  buffer_id: Optional[int] = None,
                  site: Optional[str] = None) -> SpillableBuffer:
        leaves_size = batch.device_size_bytes()
        bid = buffer_id if buffer_id is not None else fresh_buffer_id()
        meta = BatchMeta(batch.schema, batch.capacity, [], (batch.capacity,),
                         leaves_size)
        buf = SpillableBuffer(bid, meta, spill_priority)
        buf.device_batch = batch
        # a registered batch has a second owner (this store: a later
        # spill device_gets its arrays) — it must never be donated to a
        # compiled program afterwards
        from .donation import pin
        pin(batch)
        ledger = getattr(self.catalog, "ledger", None)
        if ledger is not None:
            # owning query (serving tier): the thread's active query
            # scope — stamped BEFORE track() so owner accounting sees it
            buf.owner = ledger.current_query()
        self.track(buf)
        self.catalog.register(buf)
        if ledger is not None:
            # `site` labels the registration path (runtime.add_batch vs
            # a retry-block checkpoint) — the admitting reserve() has
            # already returned, so the label must ride in explicitly
            ledger.on_alloc(bid, leaves_size, site=site, owner=buf.owner)
        return buf

    def _release_payload_to(self, buf: SpillableBuffer,
                            dest: BufferStore) -> None:
        leaves, meta = batch_to_host(buf.device_batch)
        meta.size_bytes = host_leaves_nbytes(leaves)
        buf.meta = meta
        buf.host_leaves = leaves
        policy = getattr(self.catalog, "integrity", None)
        if policy is not None and policy.enabled:
            # digest the host form the moment it exists: everything the
            # bytes do from here (host tier, disk file, unspill, being
            # served over the shuffle wire) verifies against this record
            buf.host_checksums = policy.checksum_leaves(leaves)
        if leaves and faults.INJECTOR.on_corruptible("spill"):
            # injected SPILL-path corruption (after the digest: models
            # host-memory rot between spill and unspill); the leaves are
            # read-only device_get views, so the flip is a copy-swap
            leaves[0] = faults.flip_bit(leaves[0])
        buf.device_batch = None  # drop the jnp refs -> XLA can reuse HBM


class HostMemoryStore(BufferStore):
    """Bounded host tier (RapidsHostMemoryStore.scala;
    spark.rapids.memory.host.spillStorageSize)."""

    tier = StorageTier.HOST

    def __init__(self, catalog: "BufferCatalog", max_size: int):
        super().__init__(catalog)
        self.max_size = max_size

    def track(self, buf: SpillableBuffer) -> None:
        # make room first: host tier is bounded, overflow goes to disk
        if self.spill_store is not None \
                and self.current_size + buf.size_bytes > self.max_size:
            self.synchronous_spill(max(0, self.max_size - buf.size_bytes))
        super().track(buf)

    def _release_payload_to(self, buf: SpillableBuffer,
                            dest: BufferStore) -> None:
        assert isinstance(dest, DiskStore)
        # catch host-tier rot BEFORE it is persisted as ground truth: a
        # corrupted leaf written to disk would verify "clean" against a
        # re-read of the same corrupted bytes
        verify_buffer_leaves(self.catalog, buf, buf.host_leaves,
                             site="host_to_disk")
        path = dest.path_for(buf.id)
        cpol = getattr(self.catalog, "compression", None)
        if cpol is not None and cpol.enabled:
            # spill compression: the disk image holds FRAMED leaves.
            # Digests over the compressed form are recorded here (before
            # write_leaves' disk injection point), so rot in the file is
            # caught at read time before any decompressor sees it; the
            # original host_checksums still verify the decompressed
            # leaves after, closing the loop end to end.
            frames = cpol.compress_leaves(buf.host_leaves)
            policy = getattr(self.catalog, "integrity", None)
            if policy is not None and policy.enabled:
                buf.disk_checksums = tuple(policy.checksum_leaves(frames))
            buf.disk_codec = cpol.codec_name
            buf.disk_comp_sizes = [f.nbytes for f in frames]
            raw_total = host_leaves_nbytes(buf.host_leaves)
            comp_total = sum(buf.disk_comp_sizes)
            cpol.record_ratio(raw_total, comp_total)
            if cpol.metrics is not None:
                from ..metrics import names as MN
                cpol.metrics.add(MN.COMPRESSED_SPILL_BYTES_WRITTEN,
                                 comp_total)
            from ..metrics.journal import journal_event
            journal_event("compress", "spillCompress", buffer=buf.id,
                          codec=cpol.codec_name, raw_bytes=raw_total,
                          comp_bytes=comp_total,
                          ratio=round(raw_total / max(1, comp_total), 3))
            write_leaves(path, frames)
        else:
            write_leaves(path, buf.host_leaves)
        buf.disk_path = path
        buf.host_leaves = None


#: spill-dir naming: tpu_spill_<owner pid>_<random>.  The pid tag is what
#: lets a LATER process tell an abandoned dir (its owner died without
#: cleanup — a SIGKILLed/crashed executor worker leaks every shuffle
#: buffer it ever spilled) from one a live process is still using.
SPILL_DIR_PREFIX = "tpu_spill_"


def sweep_stale_spill_dirs(parent: Optional[str] = None) -> int:
    """Remove spill dirs whose owning process is dead — the worker
    bootstrap hygiene sweep: a replaced worker's predecessor spilled
    shuffle buffers into its own tpu_spill_<pid>_* dir and died without
    `remove_shuffle` ever reaching it (the fresh process never knew the
    sid), so the files leak until SOMEONE checks the owner pid.  Dirs
    without a parseable pid tag (pre-tag naming) are left alone.
    Returns the number of dirs removed."""
    import shutil
    parent = parent or tempfile.gettempdir()
    removed = 0
    try:
        entries = os.listdir(parent)
    except OSError:
        return 0
    for name in entries:
        if not name.startswith(SPILL_DIR_PREFIX):
            continue
        tag = name[len(SPILL_DIR_PREFIX):].split("_", 1)[0]
        if not tag.isdigit():
            continue  # pre-pid-tag dir: owner unknowable, keep
        pid = int(tag)
        try:
            os.kill(pid, 0)  # signal 0: existence probe only
            continue  # owner alive (or pid reused): keep
        except ProcessLookupError:
            pass  # tpulint: disable=TPU006 ProcessLookupError IS the probe's answer (owner dead -> the dir is sweepable garbage)
        except OSError:
            continue  # tpulint: disable=TPU006 EPERM etc means the pid belongs to SOMEONE — conservatively keep the dir
        path = os.path.join(parent, name)
        if not os.path.isdir(path):
            continue
        try:
            shutil.rmtree(path)
            removed += 1
        except OSError:
            from ..metrics.registry import count_swallowed
            count_swallowed("numCleanupErrors", "spark_rapids_tpu.mem",
                            "stale spill dir %s could not be removed",
                            path)
    return removed


class DiskStore(BufferStore):
    """Disk tier (RapidsDiskStore.scala + RapidsDiskBlockManager.scala):
    buffer id -> local spill file."""

    tier = StorageTier.DISK

    def __init__(self, catalog: "BufferCatalog",
                 spill_dir: Optional[str] = None):
        super().__init__(catalog)
        self._dir = spill_dir or tempfile.mkdtemp(
            prefix=f"{SPILL_DIR_PREFIX}{os.getpid()}_")

    def path_for(self, buffer_id: int) -> str:
        return os.path.join(self._dir, f"tpu_buffer_{buffer_id}.bin")

    def _release_payload_to(self, buf, dest):  # pragma: no cover
        raise RuntimeError("disk is the last tier")

    def delete_file(self, buf: SpillableBuffer) -> None:
        if buf.disk_path and os.path.exists(buf.disk_path):
            os.unlink(buf.disk_path)
        buf.disk_path = None
        buf.disk_codec = None
        buf.disk_comp_sizes = None
        buf.disk_checksums = None


class BufferCatalog:
    """id -> buffer registry with ref-counted acquire
    (RapidsBufferCatalog.scala:30-52)."""

    # spill-path ChecksumPolicy (mem/integrity.py), installed by
    # TpuRuntime; None = no spill checksumming (bare-store unit tests)
    integrity = None
    # spill-path CompressionPolicy (compress/), installed by TpuRuntime;
    # None = uncompressed spill files (bare-store unit tests)
    compression = None
    # memory-pressure ledger (mem/ledger.py), installed by TpuRuntime;
    # None = no allocation/spill event stream (bare-store unit tests)
    ledger = None
    # data-movement policy engine (policy/engine.py), installed by
    # TpuRuntime; None = baseline (priority, id) victim order
    policy = None

    def __init__(self):
        self._buffers: Dict[int, SpillableBuffer] = {}
        self._lock = threading.RLock()

    def register(self, buf: SpillableBuffer) -> None:
        with self._lock:
            if buf.id in self._buffers:
                raise ValueError(f"duplicate buffer id {buf.id}")
            self._buffers[buf.id] = buf

    def acquire(self, buffer_id: int) -> SpillableBuffer:
        """Pin the buffer against spilling; caller must `release`."""
        with self._lock:
            buf = self._buffers.get(buffer_id)
        if buf is None:
            raise KeyError(f"unknown buffer {buffer_id}")
        with buf.lock:  # waits out any in-flight migration
            if buf.freed:
                raise KeyError(f"unknown buffer {buffer_id}")
            buf.ref_count += 1
            return buf

    def release(self, buf: SpillableBuffer) -> None:
        with buf.lock:
            assert buf.ref_count > 0, f"over-release of {buf!r}"
            buf.ref_count -= 1

    def lookup_tier(self, buffer_id: int) -> StorageTier:
        with self._lock:
            return self._buffers[buffer_id].tier

    def remove(self, buffer_id: int) -> Optional[SpillableBuffer]:
        with self._lock:
            buf = self._buffers.pop(buffer_id, None)
            if buf is not None:
                buf.freed = True
            return buf

    def ids(self):
        with self._lock:
            return list(self._buffers)
