"""Device-resident cache for in-memory table scans.

Spark keeps hot tables in the storage layer (`df.cache()` /
`CachedBatchSerializer`; the reference adds a GPU-aware columnar cache
serializer in later versions).  The TPU-native equivalent keeps the decoded
device batches HBM-resident: HBM is large (16 GiB on v5e) relative to the
host->device link, so re-uploading an immutable table on every query wastes
the slowest resource in the system (a PCIe-class host link against
819 GB/s of HBM on a v5e).

Keys are (table identity, pruned column names, reader row limit).  A strong
reference to the source table is held so `id()` can never be recycled to a
different live table; pyarrow Tables are immutable, so identity implies
content equality.  The cache is LRU-bounded by `resident_bound(conf)`
device bytes.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import List, Optional, Tuple


class _Entry:
    __slots__ = ("table", "batches", "nbytes")

    def __init__(self, table, batches, nbytes: int):
        self.table = table
        self.batches = batches
        self.nbytes = nbytes


class MemoryScanCache:
    def __init__(self):
        self._entries: "OrderedDict[tuple, _Entry]" = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _key(table, names: Tuple[str, ...], limit: int) -> tuple:
        return (id(table), names, limit)

    def get(self, table, names: Tuple[str, ...], limit: int
            ) -> Optional[List]:
        key = self._key(table, names, limit)
        e = self._entries.get(key)
        if e is None or e.table is not table:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return e.batches

    def put(self, table, names: Tuple[str, ...], limit: int,
            batches: List, max_bytes: int, nbytes: int) -> None:
        """`batches` is a list of (ColumnarBatch, live_row_count) pairs; the
        count is cached host-side so serving a hit costs no device sync.
        `nbytes` is the caller-accumulated device size of `batches` (one
        computation shared with the caller's streaming cutoff)."""
        if nbytes > max_bytes:
            return  # too big to ever fit; don't thrash the cache
        key = self._key(table, names, limit)
        old = self._entries.pop(key, None)
        if old is not None:
            self._bytes -= old.nbytes
        self._entries[key] = _Entry(table, batches, nbytes)
        self._bytes += nbytes
        while self._bytes > max_bytes and len(self._entries) > 1:
            _, evicted = self._entries.popitem(last=False)
            self._bytes -= evicted.nbytes

    def clear(self) -> None:
        self._entries.clear()
        self._bytes = 0
        self.hits = 0
        self.misses = 0

    @property
    def device_bytes(self) -> int:
        return self._bytes


def resident_bound(conf) -> int:
    """Device bytes the scan cache may hold: an explicit
    `spark.rapids.sql.tpu.memoryScanCache.maxSize` > 0, else half of the
    accounted pool (`mem/runtime.configured_pool_bytes`), as Spark's
    `spark.memory.storageFraction` gives storage half of the unified
    region.  The pinned batches are not registered with the runtime, so its
    reservations do not see them: the bound is what keeps them in their
    half."""
    from ..config import MEMORY_SCAN_CACHE_SIZE
    explicit = int(conf.get(MEMORY_SCAN_CACHE_SIZE))
    if explicit > 0:
        return explicit
    from ..mem.runtime import configured_pool_bytes
    return configured_pool_bytes(conf) // 2


MEMORY_SCAN_CACHE = MemoryScanCache()
