"""Memory runtime tests (SURVEY.md §2.4 / §4 tier 1 memory-subsystem suites:
RapidsBufferCatalogSuite, RapidsDeviceMemoryStoreSuite, GpuSemaphoreSuite,
TestHashedPriorityQueue)."""
import threading
import time

import numpy as np
import pytest

from spark_rapids_tpu.columnar import Column, ColumnarBatch
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.mem import (HashedPriorityQueue, SpillPriorities,
                                  StorageTier, TpuRuntime, TpuSemaphore)
from spark_rapids_tpu.types import DoubleType, LongType, Schema, StructField


def make_batch(n=100, cap=1024, seed=0):
    rng = np.random.RandomState(seed)
    schema = Schema([StructField("a", LongType), StructField("b", DoubleType)])
    return ColumnarBatch.from_pydict(
        {"a": rng.randint(0, 50, n).tolist(),
         "b": rng.uniform(-5, 5, n).tolist()}, schema, capacity=cap)


def batch_rows(b):
    return b.to_pylist()


# ---- HashedPriorityQueue ----------------------------------------------------

class TestHashedPriorityQueue:
    def test_offer_poll_order(self):
        prios = {"a": 3.0, "b": 1.0, "c": 2.0}
        q = HashedPriorityQueue(lambda k: prios[k])
        for k in prios:
            q.offer(k)
        assert [q.poll(), q.poll(), q.poll()] == ["b", "c", "a"]
        assert q.poll() is None

    def test_update_priority(self):
        prios = {"a": 1.0, "b": 2.0, "c": 3.0}
        q = HashedPriorityQueue(lambda k: prios[k])
        for k in prios:
            q.offer(k)
        prios["a"] = 10.0
        q.update_priority("a")
        assert q.poll() == "b"
        prios["c"] = 0.0
        q.update_priority("c")
        assert q.poll() == "c"
        assert q.poll() == "a"

    def test_remove(self):
        prios = {"a": 1.0, "b": 2.0}
        q = HashedPriorityQueue(lambda k: prios[k])
        q.offer("a")
        q.offer("b")
        assert q.remove("a")
        assert not q.remove("a")
        assert q.poll() == "b"

    def test_many_random(self):
        rng = np.random.RandomState(7)
        prios = {i: float(rng.uniform(0, 1)) for i in range(200)}
        q = HashedPriorityQueue(lambda k: prios[k])
        for k in prios:
            q.offer(k)
        # random priority updates
        for k in rng.choice(200, 50, replace=False):
            prios[int(k)] = float(rng.uniform(0, 1))
            q.update_priority(int(k))
        out = []
        while len(q):
            out.append(q.poll())
        assert out == sorted(prios, key=lambda k: prios[k])


# ---- catalog + spill --------------------------------------------------------

class TestSpillFramework:
    def runtime(self, pool=1 << 20, host=1 << 20, tmpdir=None):
        conf = TpuConf({"spark.rapids.memory.host.spillStorageSize": host})
        return TpuRuntime(conf, pool_limit_bytes=pool, spill_dir=tmpdir)

    def test_alloc_debug_logging(self, capsys):
        """spark.rapids.memory.tpu.debug=STDOUT logs every alloc/free and
        flags double-frees (reference: RMM allocation logging via
        spark.rapids.memory.gpu.debug, RapidsConf.scala:227-234)."""
        conf = TpuConf({"spark.rapids.memory.tpu.debug": "STDOUT"})
        rt = TpuRuntime(conf, pool_limit_bytes=1 << 20)
        bid = rt.add_batch(make_batch())
        rt.free_batch(bid)
        rt.free_batch(bid)  # double free: logged, not fatal
        out = capsys.readouterr().out
        assert f"alloc id={bid}" in out
        assert f"free id={bid}" in out
        assert "DOUBLE-FREE" in out

    def test_add_get_roundtrip(self):
        rt = self.runtime()
        b = make_batch()
        want = batch_rows(b)
        bid = rt.add_batch(b)
        got = rt.get_batch(bid)
        assert batch_rows(got) == want

    def test_spill_device_to_host_roundtrip(self):
        rt = self.runtime()
        b = make_batch(seed=1)
        want = batch_rows(b)
        bid = rt.add_batch(b)
        spilled = rt.device_store.synchronous_spill(0)
        assert spilled > 0
        assert rt.catalog.lookup_tier(bid) == StorageTier.HOST
        assert rt.device_store.current_size == 0
        got = rt.get_batch(bid)
        assert batch_rows(got) == want

    def test_spill_through_to_disk(self, tmp_path):
        rt = self.runtime(host=1, tmpdir=str(tmp_path))  # host tier ~disabled
        b = make_batch(seed=2)
        want = batch_rows(b)
        bid = rt.add_batch(b)
        rt.device_store.synchronous_spill(0)
        # host store is bounded at 1 byte: buffer lands on disk next track
        rt.host_store.synchronous_spill(0)
        assert rt.catalog.lookup_tier(bid) == StorageTier.DISK
        got = rt.get_batch(bid)
        assert batch_rows(got) == want

    def test_oom_triggers_spill(self):
        b1, b2 = make_batch(seed=3), make_batch(seed=4)
        size = b1.device_size_bytes()
        rt = self.runtime(pool=int(size * 1.5))
        id1 = rt.add_batch(b1)
        id2 = rt.add_batch(b2)  # must force b1 to spill
        assert rt.catalog.lookup_tier(id1) == StorageTier.HOST
        assert rt.catalog.lookup_tier(id2) == StorageTier.DEVICE

    def test_pool_exhausted_raises(self):
        b = make_batch()
        rt = self.runtime(pool=10)  # tiny pool, nothing to spill
        with pytest.raises(MemoryError):
            rt.add_batch(b)

    def test_acquired_buffer_not_spilled(self):
        rt = self.runtime()
        b = make_batch(seed=5)
        bid = rt.add_batch(b)
        buf = rt.catalog.acquire(bid)
        try:
            spilled = rt.device_store.synchronous_spill(0)
            assert spilled == 0
            assert rt.catalog.lookup_tier(bid) == StorageTier.DEVICE
        finally:
            rt.catalog.release(buf)
        assert rt.device_store.synchronous_spill(0) > 0

    def test_spill_priority_order(self):
        rt = self.runtime()
        b1, b2 = make_batch(seed=6), make_batch(seed=7)
        id1 = rt.add_batch(b1, SpillPriorities.ACTIVE_ON_DECK_PRIORITY)
        id2 = rt.add_batch(
            b2, SpillPriorities.OUTPUT_FOR_SHUFFLE_INITIAL_PRIORITY)
        # spill one buffer's worth: the shuffle-output one must go first
        rt.device_store.synchronous_spill(rt.device_store.current_size - 1)
        assert rt.catalog.lookup_tier(id2) == StorageTier.HOST
        assert rt.catalog.lookup_tier(id1) == StorageTier.DEVICE

    def test_update_priority_changes_victim(self):
        rt = self.runtime()
        id1 = rt.add_batch(make_batch(seed=8), 1.0)
        id2 = rt.add_batch(make_batch(seed=9), 2.0)
        rt.update_priority(id1, 100.0)
        rt.device_store.synchronous_spill(rt.device_store.current_size - 1)
        assert rt.catalog.lookup_tier(id2) == StorageTier.HOST
        assert rt.catalog.lookup_tier(id1) == StorageTier.DEVICE

    def test_free_removes_everywhere(self, tmp_path):
        rt = self.runtime(tmpdir=str(tmp_path))
        bid = rt.add_batch(make_batch(seed=10))
        rt.device_store.synchronous_spill(0)
        rt.host_store.synchronous_spill(0)
        buf = rt.catalog.acquire(bid)
        path = buf.disk_path
        rt.catalog.release(buf)
        assert path is not None
        rt.free_batch(bid)
        import os
        assert not os.path.exists(path)
        with pytest.raises(KeyError):
            rt.get_batch(bid)

    def test_unknown_buffer_raises(self):
        rt = self.runtime()
        with pytest.raises(KeyError):
            rt.get_batch(999999)


# ---- semaphore --------------------------------------------------------------

class TestSemaphore:
    def test_reentrant(self):
        s = TpuSemaphore(1)
        s.acquire_if_necessary("t1")
        s.acquire_if_necessary("t1")  # must not deadlock
        assert s.active_tasks() == 1
        s.release_if_necessary("t1")
        assert s.active_tasks() == 1
        s.release_if_necessary("t1")
        assert s.active_tasks() == 0

    def test_caps_concurrency(self):
        s = TpuSemaphore(2)
        running = []
        peak = [0]
        lock = threading.Lock()

        def task(tid):
            s.acquire_if_necessary(tid)
            with lock:
                running.append(tid)
                peak[0] = max(peak[0], len(running))
            time.sleep(0.02)
            with lock:
                running.remove(tid)
            s.task_done(tid)

        threads = [threading.Thread(target=task, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert peak[0] <= 2
        assert s.active_tasks() == 0

    def test_held_context(self):
        s = TpuSemaphore(1)
        with s.held("a"):
            assert s.active_tasks() == 1
        assert s.active_tasks() == 0


class TestMemoryScanCache:
    """Device-resident in-memory scan cache (utils/scan_cache.py)."""

    def _q6ish(self, session, table):
        from spark_rapids_tpu.plan.logical import col, functions as F
        df = session.from_arrow(table)
        return df.filter(col("a") > 2).agg(F.sum(col("a")).alias("s"))

    #: one int64 column at 10 B a row at capacity (data, validity, selection)
    #: in batches of 16,384 rows: 163,840 B a batch
    RESIDENCY_BATCH = 16_384

    @pytest.mark.parametrize("batches,cache_conf,resident", [
        (16, {}, True),                 # 2.6 MB under half of the 8 MB pool
        (32, {}, False),                # 5.2 MB over it
        (32, {"spark.rapids.sql.tpu.memoryScanCache.maxSize": "6m"}, True),
        (16, {"spark.rapids.sql.tpu.memoryScanCache.maxSize": "1m"}, False),
    ], ids=["under_half_the_pool", "over_half_the_pool",
            "explicit_bound_above", "explicit_bound_below"])
    def test_residency_bound_is_half_the_pool(self, batches, cache_conf,
                                              resident):
        """The cache's bound is half of the accounted pool unless maxSize
        says otherwise: a table under it is served from the cache from the
        second query on (every batch, nothing crosses the host link); a
        table over it uploads every query and the counter reads 0."""
        import pyarrow as pa
        from spark_rapids_tpu.engine import TpuSession
        from spark_rapids_tpu.utils.scan_cache import (MEMORY_SCAN_CACHE,
                                                       resident_bound)
        MEMORY_SCAN_CACHE.clear()
        conf = {"spark.rapids.memory.tpu.poolSizeBytes": "8m",
                "spark.rapids.sql.reader.batchSizeRows":
                    str(self.RESIDENCY_BATCH), **cache_conf}
        explicit = cache_conf.get(
            "spark.rapids.sql.tpu.memoryScanCache.maxSize")
        assert resident_bound(TpuConf(conf)) == (
            int(explicit[:-1]) << 20 if explicit else 4 << 20)
        table = pa.table({"a": np.arange(batches * self.RESIDENCY_BATCH)})
        s = TpuSession(conf)
        df = self._q6ish(s, table)
        moved = []
        for _ in range(3):
            before = dict(s.query_metrics_total)
            assert df.collect() == [(int(np.arange(batches * self.RESIDENCY_BATCH)
                                         [3:].sum()),)]
            moved.append({k: v - before.get(k, 0)
                          for k, v in s.query_metrics_total.items()})
        assert moved[0]["scanCacheHitBatches"] == 0
        assert moved[0]["h2dBytes"] > 0
        for m in moved[1:]:
            assert m["scanCacheHitBatches"] == (batches if resident else 0)
            assert m.get("h2dBytes", 0) == (0 if resident else
                                            moved[0]["h2dBytes"])
        assert MEMORY_SCAN_CACHE.device_bytes == (
            batches * self.RESIDENCY_BATCH * 10 if resident else 0)

    def test_default_bound_is_half_the_detected_pool(self):
        from spark_rapids_tpu.mem.runtime import configured_pool_bytes
        from spark_rapids_tpu.utils.scan_cache import resident_bound
        conf = TpuConf()
        # the CPU backend reports no memory limit: a nominal 16 GiB, of
        # which allocFraction 0.9 is the pool and half of that the cache's
        assert configured_pool_bytes(conf) == int((16 << 30) * 0.9)
        assert resident_bound(conf) == configured_pool_bytes(conf) // 2
        assert resident_bound(conf) > 4_322_230_272   # Q1's LINEITEM at SF10

    def test_repeat_query_hits_cache(self):
        import pyarrow as pa
        from spark_rapids_tpu.engine import TpuSession
        from spark_rapids_tpu.utils.scan_cache import MEMORY_SCAN_CACHE
        MEMORY_SCAN_CACHE.clear()
        table = pa.table({"a": list(range(100))})
        s = TpuSession()
        h0, m0 = MEMORY_SCAN_CACHE.hits, MEMORY_SCAN_CACHE.misses
        r1 = self._q6ish(s, table).collect()
        r2 = self._q6ish(s, table).collect()
        assert r1 == r2
        assert MEMORY_SCAN_CACHE.misses == m0 + 1
        assert MEMORY_SCAN_CACHE.hits >= h0 + 1

    def test_identity_not_equality(self):
        """A different (even equal-content) table must not be served."""
        import pyarrow as pa
        from spark_rapids_tpu.engine import TpuSession
        from spark_rapids_tpu.utils.scan_cache import MEMORY_SCAN_CACHE
        MEMORY_SCAN_CACHE.clear()
        s = TpuSession()
        t1 = pa.table({"a": [1, 2, 3]})
        self._q6ish(s, t1).collect()
        t2 = pa.table({"a": [10, 20, 30]})
        rows = self._q6ish(s, t2).collect()
        assert rows[0][0] == 60

    def test_disabled_by_conf(self):
        import pyarrow as pa
        from spark_rapids_tpu.engine import TpuSession
        from spark_rapids_tpu.utils.scan_cache import MEMORY_SCAN_CACHE
        MEMORY_SCAN_CACHE.clear()
        s = TpuSession(
            {"spark.rapids.sql.tpu.memoryScanCache.enabled": "false"})
        table = pa.table({"a": [1, 2, 3, 4]})
        self._q6ish(s, table).collect()
        self._q6ish(s, table).collect()
        assert MEMORY_SCAN_CACHE.hits == 0 and MEMORY_SCAN_CACHE.misses == 0

    def test_lru_eviction_bound(self):
        import pyarrow as pa
        from spark_rapids_tpu.engine import TpuSession
        from spark_rapids_tpu.utils.scan_cache import MEMORY_SCAN_CACHE
        MEMORY_SCAN_CACHE.clear()
        # each 1024-row int64 table is ~10 KiB of device bytes; a 24 KiB cap
        # holds at most 2 entries, so inserting 4 must evict
        s = TpuSession(
            {"spark.rapids.sql.tpu.memoryScanCache.maxSize": "24k"})
        tables = [pa.table({"a": list(range(1024))}) for _ in range(4)]
        for t in tables:
            self._q6ish(s, t).collect()
        assert len(MEMORY_SCAN_CACHE._entries) < 4, "eviction never ran"
        assert MEMORY_SCAN_CACHE.device_bytes <= 24 * 1024
        # the most-recent table survived and is served from cache
        h0 = MEMORY_SCAN_CACHE.hits
        self._q6ish(s, tables[-1]).collect()
        assert MEMORY_SCAN_CACHE.hits == h0 + 1

    def test_pruned_scan_hits_cache(self):
        """Column pruning select()s a fresh table per planning pass; the
        cache must key on the ORIGINAL table identity or it misses forever."""
        import pyarrow as pa
        from spark_rapids_tpu.engine import TpuSession
        from spark_rapids_tpu.plan.logical import col, functions as F
        from spark_rapids_tpu.utils.scan_cache import MEMORY_SCAN_CACHE
        MEMORY_SCAN_CACHE.clear()
        s = TpuSession()
        t = pa.table({"a": list(range(50)), "b": [1.0] * 50,
                      "unused": [0] * 50})
        for _ in range(2):
            rows = (s.from_arrow(t).filter(col("a") >= 25)
                    .agg(F.sum(col("b")).alias("s")).collect())
            assert rows[0][0] == 25.0
        assert MEMORY_SCAN_CACHE.misses == 1
        assert MEMORY_SCAN_CACHE.hits >= 1

    def test_oversized_table_not_pinned(self):
        """A table bigger than maxSize must stream, not accumulate."""
        import pyarrow as pa
        from spark_rapids_tpu.engine import TpuSession
        from spark_rapids_tpu.utils.scan_cache import MEMORY_SCAN_CACHE
        MEMORY_SCAN_CACHE.clear()
        s = TpuSession(
            {"spark.rapids.sql.tpu.memoryScanCache.maxSize": "4k",
             "spark.rapids.sql.reader.batchSizeRows": "1024"})
        t = pa.table({"a": list(range(8192))})
        rows = self._q6ish(s, t).collect()
        assert rows[0][0] == sum(x for x in range(8192) if x > 2)
        assert MEMORY_SCAN_CACHE.device_bytes == 0
