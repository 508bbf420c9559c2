"""Native host runtime tests (native/src/host_runtime.cpp via ctypes)."""
import numpy as np
import pytest

from spark_rapids_tpu import native as N


pytestmark = pytest.mark.skipif(not N.native_available(),
                                reason="native toolchain unavailable")


def test_native_allocator_matches_python():
    from spark_rapids_tpu.mem.address_space import AddressSpaceAllocator
    rng = np.random.RandomState(0)
    py = AddressSpaceAllocator(10_000)
    nat = N.NativeAddressSpaceAllocator(10_000)
    held = []
    for _ in range(300):
        if held and rng.rand() < 0.4:
            i = rng.randint(len(held))
            addr = held.pop(i)
            assert py.free(addr) == nat.free(addr)
        else:
            ln = int(rng.randint(1, 400))
            a1, a2 = py.allocate(ln), nat.allocate(ln)
            assert (a1 is None) == (a2 is None)
            if a1 is not None:
                assert a1 == a2  # same best-fit decisions
                held.append(a1)
        assert py.allocated_bytes == nat.allocated_bytes
        assert py.largest_free_block() == nat.largest_free_block()


def test_native_spill_roundtrip(tmp_path):
    p = str(tmp_path / "buf.bin")
    data = np.random.RandomState(1).bytes(100_000)
    arr = np.frombuffer(data, dtype=np.uint8)
    assert N.spill_write(p, arr) == len(data)
    back = N.spill_read(p, len(data))
    assert bytes(back) == data
    # offset read
    assert bytes(N.spill_read(p, 10, offset=50)) == data[50:60]


def test_native_gather_rows():
    rng = np.random.RandomState(2)
    src = rng.randint(-1000, 1000, size=(5000, 3)).astype(np.int64)
    idx = rng.randint(0, 5000, 20000).astype(np.int32)
    got = N.gather_rows(src, idx)
    assert (got == src[idx]).all()
    # 1-D too
    src1 = rng.uniform(size=10_000)
    idx1 = rng.randint(0, 10_000, 5000).astype(np.int32)
    assert (N.gather_rows(src1, idx1) == src1[idx1]).all()


def test_native_murmur3_matches_device_kernel():
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.hashing import murmur3_long
    rng = np.random.RandomState(3)
    vals = rng.randint(-2**62, 2**62, 1000)
    want = np.asarray(murmur3_long(jnp.asarray(vals), 42))
    got = N.murmur3_long(vals, seed=42)
    assert (got == want).all()


def test_native_murmur3_null_passthrough():
    vals = np.array([1, 2, 3], dtype=np.int64)
    valid = np.array([1, 0, 1], dtype=np.uint8)
    out = N.murmur3_long(vals, valid, seed=42)
    assert out[1] == 42


def test_spill_tier_uses_native_io(tmp_path):
    """End-to-end: disk-tier spill round trip goes through the native I/O."""
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.mem import StorageTier, TpuRuntime
    from spark_rapids_tpu.types import LongType, Schema, StructField
    conf = TpuConf({"spark.rapids.memory.host.spillStorageSize": 1})
    rt = TpuRuntime(conf, pool_limit_bytes=64 << 20, spill_dir=str(tmp_path))
    schema = Schema([StructField("a", LongType)])
    b = ColumnarBatch.from_pydict({"a": list(range(500))}, schema)
    bid = rt.add_batch(b)
    rt.device_store.synchronous_spill(0)
    rt.host_store.synchronous_spill(0)
    assert rt.catalog.lookup_tier(bid) == StorageTier.DISK
    assert rt.get_batch(bid).to_pylist() == [(i,) for i in range(500)]


def test_library_is_named_by_source_hash_and_untracked():
    """A copied tree must never load a stale binary: the library's name
    carries its source's hash, and git does not track it."""
    import hashlib
    import os
    with open(N._SRC_PATH, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert os.path.basename(N._lib_path()) == \
        f"libtpu_host_runtime.{digest}.so"
    assert os.path.exists(N._lib_path())


def test_missing_toolchain_warns_once_and_falls_back(monkeypatch, caplog):
    import logging

    def no_toolchain():
        raise OSError("g++: command not found")
    monkeypatch.setattr(N, "_load", no_toolchain)
    monkeypatch.setattr(N, "_lib", None)
    monkeypatch.setattr(N, "_tried", False)
    with caplog.at_level(logging.WARNING, logger="spark_rapids_tpu.native"):
        assert N.get_lib() is None
        assert N.get_lib() is None  # latched: no second attempt, no spam
    warns = [r for r in caplog.records if "pure-Python fallbacks" in
             r.getMessage()]
    assert len(warns) == 1
    # the fallbacks still answer
    out = N.gather_rows(np.arange(10, dtype=np.int64),
                        np.array([3, 1], dtype=np.int32))
    assert out.tolist() == [3, 1]
