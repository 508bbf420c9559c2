"""ctypes bindings for the native host runtime (native/src/host_runtime.cpp).

The reference's host hot paths live in C++ (RMM allocator, libcudf host
scaffolding, UCX); ours live in libtpu_host_runtime.so: best-fit
address-space allocator, spill file I/O, multi-threaded row gather, Spark
murmur3 batch hashing.  The library is compiled on first use with the
image's g++ into native/, named by a hash of its source so a copied or
updated tree can never load a stale binary (the binary is not tracked by
git).  Every caller has a pure-Python fallback, so a missing toolchain
degrades performance, never correctness — and says so once at WARNING.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_SRC_PATH = os.path.join(_ROOT, "src", "host_runtime.cpp")

_lock = threading.Lock()
_lib = None
_tried = False


def _lib_path() -> str:
    with open(_SRC_PATH, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_ROOT, f"libtpu_host_runtime.{digest}.so")


def _build(lib_path: str) -> None:
    # per-pid temp + atomic replace: test workers and executor processes
    # may all find the library missing at once
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
             "-o", tmp, _SRC_PATH],
            check=True, capture_output=True, timeout=300)
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for stale in glob.glob(os.path.join(_ROOT, "libtpu_host_runtime.*.so")):
        if stale != lib_path:
            os.unlink(stale)


def _load():
    lib_path = _lib_path()
    if not os.path.exists(lib_path):
        _build(lib_path)
    return ctypes.CDLL(lib_path)


def get_lib():
    """The loaded CDLL, or None when unavailable (fallback mode)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            lib = _load()
        except (OSError, subprocess.SubprocessError) as e:
            detail = getattr(e, "stderr", b"") or b""
            logging.getLogger("spark_rapids_tpu.native").warning(
                "native host runtime unavailable (%r %s); using the "
                "pure-Python fallbacks: allocator, spill I/O, row gather, "
                "hashing and the parquet/orc/csv decoders run slower",
                e, detail.decode(errors="replace")[-500:])
            return None
        lib.asalloc_create.restype = ctypes.c_void_p
        lib.asalloc_create.argtypes = [ctypes.c_int64]
        lib.asalloc_destroy.argtypes = [ctypes.c_void_p]
        lib.asalloc_allocate.restype = ctypes.c_int64
        lib.asalloc_allocate.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.asalloc_free.restype = ctypes.c_int64
        lib.asalloc_free.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.asalloc_allocated_bytes.restype = ctypes.c_int64
        lib.asalloc_allocated_bytes.argtypes = [ctypes.c_void_p]
        lib.asalloc_largest_free.restype = ctypes.c_int64
        lib.asalloc_largest_free.argtypes = [ctypes.c_void_p]
        lib.spill_write.restype = ctypes.c_int64
        lib.spill_write.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                                    ctypes.c_int64]
        lib.spill_read.restype = ctypes.c_int64
        lib.spill_read.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                                   ctypes.c_int64, ctypes.c_int64]
        lib.gather_rows.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_void_p, ctypes.c_int64,
                                    ctypes.c_int64, ctypes.c_int32]
        lib.murmur3_long_batch.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                           ctypes.c_void_p, ctypes.c_int64,
                                           ctypes.c_int32]
        lib.csv_tokenize.restype = ctypes.c_int64
        lib.csv_tokenize.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                     ctypes.c_uint8, ctypes.c_void_p,
                                     ctypes.c_void_p, ctypes.c_void_p,
                                     ctypes.c_int64]
        lib.pq_byte_array_scan.restype = ctypes.c_int64
        lib.pq_byte_array_scan.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                           ctypes.c_int64, ctypes.c_void_p,
                                           ctypes.c_void_p]
        lib.pq_rle_decode.restype = ctypes.c_int64
        lib.pq_rle_decode.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                      ctypes.c_int32, ctypes.c_int64,
                                      ctypes.c_void_p]
        lib.pq_page_walk.restype = ctypes.c_int64
        lib.pq_page_walk.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                     ctypes.c_int64, ctypes.c_int64] \
            + [ctypes.c_void_p] * 11
        lib.pq_def_levels.restype = ctypes.c_int64
        lib.pq_def_levels.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                      ctypes.c_int32, ctypes.c_int64,
                                      ctypes.c_int32, ctypes.c_void_p]
        lib.orc_rlev2_decode.restype = ctypes.c_int64
        lib.orc_rlev2_decode.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_int32,
                                         ctypes.c_void_p]
        _lib = lib
        return _lib


# ---------------------------------------------------------------------------
# typed wrappers (None-safe: callers check availability via native_available)
# ---------------------------------------------------------------------------

def native_available() -> bool:
    return get_lib() is not None


class NativeAddressSpaceAllocator:
    """C++ best-fit allocator with the same interface as
    mem.address_space.AddressSpaceAllocator."""

    def __init__(self, size: int):
        lib = get_lib()
        assert lib is not None
        self._lib = lib
        self._h = lib.asalloc_create(size)
        self.size = size

    def allocate(self, length: int):
        addr = self._lib.asalloc_allocate(self._h, length)
        return None if addr < 0 else addr

    def free(self, address: int) -> int:
        n = self._lib.asalloc_free(self._h, address)
        if n < 0:
            raise ValueError(f"free of unallocated address {address}")
        return n

    @property
    def allocated_bytes(self) -> int:
        return self._lib.asalloc_allocated_bytes(self._h)

    @property
    def available_bytes(self) -> int:
        return self.size - self.allocated_bytes

    def largest_free_block(self) -> int:
        return self._lib.asalloc_largest_free(self._h)

    def __del__(self):  # pragma: no cover
        try:
            self._lib.asalloc_destroy(self._h)
        except Exception as e:  # noqa: BLE001 — finalizers must not raise
            try:
                from .metrics.registry import count_swallowed
                count_swallowed("numNativeTeardownErrors",
                                "spark_rapids_tpu.native",
                                "asalloc_destroy failed for handle %r: %r",
                                self._h, e)
            except Exception:  # tpulint: disable=TPU006 interpreter may be tearing down; the counter itself is best-effort in __del__
                pass


def spill_write(path: str, data: np.ndarray) -> int:
    """Whole-buffer native write; returns bytes written."""
    lib = get_lib()
    buf = np.ascontiguousarray(data).view(np.uint8).reshape(-1)
    if lib is None:
        with open(path, "wb") as f:
            f.write(buf.tobytes())
        return buf.nbytes
    n = lib.spill_write(path.encode(), buf.ctypes.data, buf.nbytes)
    if n != buf.nbytes:
        raise OSError(f"native spill write failed ({n}) for {path}")
    return n


def spill_read(path: str, nbytes: int, offset: int = 0) -> np.ndarray:
    """Native read of nbytes at offset; returns a uint8 array."""
    lib = get_lib()
    if lib is None:
        with open(path, "rb") as f:
            f.seek(offset)
            return np.frombuffer(f.read(nbytes), dtype=np.uint8)
    out = np.empty(nbytes, dtype=np.uint8)
    n = lib.spill_read(path.encode(), out.ctypes.data, nbytes, offset)
    if n != nbytes:
        raise OSError(f"native spill read failed ({n}) for {path}")
    return out


def gather_rows(src: np.ndarray, idx: np.ndarray,
                n_threads: int = 0) -> np.ndarray:
    """out[i] = src[idx[i]] for 1-D/2-D fixed-width arrays, multithreaded."""
    lib = get_lib()
    idx = np.ascontiguousarray(idx, dtype=np.int32)
    if lib is None:
        return np.ascontiguousarray(src[idx])
    src_c = np.ascontiguousarray(src)
    row_bytes = src_c.dtype.itemsize * int(
        np.prod(src_c.shape[1:], dtype=np.int64))
    out = np.empty((len(idx),) + src_c.shape[1:], dtype=src_c.dtype)
    if n_threads <= 0:
        n_threads = min(8, os.cpu_count() or 1)
    lib.gather_rows(src_c.ctypes.data, out.ctypes.data, idx.ctypes.data,
                    len(idx), row_bytes, n_threads)
    return out


def murmur3_long(vals: np.ndarray, valid=None, seed: int = 42) -> np.ndarray:
    """Spark hashLong over an int64 batch (nulls pass the seed through)."""
    lib = get_lib()
    v = np.ascontiguousarray(vals, dtype=np.int64)
    out = np.empty(len(v), dtype=np.int32)
    if lib is None:  # pure-python fallback (slow; used only w/o toolchain)
        def one(x, s):
            def rotl(a, r):
                return ((a << r) | (a >> (32 - r))) & 0xffffffff

            def mixk(k):
                k = (k * 0xcc9e2d51) & 0xffffffff
                k = rotl(k, 15)
                return (k * 0x1b873593) & 0xffffffff

            def mixh(h, k):
                h ^= mixk(k)
                h = rotl(h, 13)
                return (h * 5 + 0xe6546b64) & 0xffffffff
            u = x & 0xffffffffffffffff
            h = mixh(s & 0xffffffff, u & 0xffffffff)
            h = mixh(h, u >> 32)
            h ^= 8
            h ^= h >> 16
            h = (h * 0x85ebca6b) & 0xffffffff
            h ^= h >> 13
            h = (h * 0xc2b2ae35) & 0xffffffff
            h ^= h >> 16
            return h - 0x100000000 if h >= 0x80000000 else h
        for i, x in enumerate(v.tolist()):
            if valid is not None and not valid[i]:
                out[i] = seed
            else:
                out[i] = one(x, seed)
        return out
    vmask = None
    if valid is not None:
        vmask = np.ascontiguousarray(valid, dtype=np.uint8)
    lib.murmur3_long_batch(v.ctypes.data,
                           vmask.ctypes.data if vmask is not None else None,
                           out.ctypes.data, len(v), seed)
    return out


def csv_tokenize(data: np.ndarray, sep: int):
    """Quote-aware CSV tokenization (RFC-4180 subset) in one native pass.

    Returns (starts, lens, flags, n_fields) over int64/uint8 arrays, or
    None when the native library is unavailable or the input is outside
    the tokenizer's scope (malformed quoting, CR bytes) — the caller
    decides between the numpy quote-free scan and the host reader.
    flags: low bits 0 unquoted / 1 quoted / 2 quoted-with-escapes;
    bit 2 marks the last field of each row."""
    lib = get_lib()
    if lib is None:
        return None
    d = np.ascontiguousarray(data, dtype=np.uint8)
    # every field ends at a separator, newline, or EOF; quoted embedded
    # separators only OVERcount, so this stays an upper bound at ~1/50th
    # the scratch of a per-byte bound on real data
    cap = int(np.count_nonzero((d == sep) | (d == 0x0A))) + 2
    starts = np.empty(cap, dtype=np.int64)
    lens = np.empty(cap, dtype=np.int64)
    flags = np.empty(cap, dtype=np.uint8)
    nf = lib.csv_tokenize(d.ctypes.data, d.size, sep, starts.ctypes.data,
                          lens.ctypes.data, flags.ctypes.data, cap)
    if nf < 0:
        return None
    return starts[:nf], lens[:nf], flags[:nf], int(nf)


def pq_rle_decode(payload: bytes, bit_width: int, n_values: int,
                  out: np.ndarray, base: int) -> bool:
    """Parquet hybrid RLE/bit-packed stream (AFTER the bit-width byte) ->
    int32 values written into out[base:base+n_values].  Returns False when
    the native library is unavailable or the stream is malformed/out of
    scope (bit width > 24) — the caller runs the python walk instead."""
    lib = get_lib()
    if lib is None or out.dtype != np.int32 or not out.flags.c_contiguous:
        return False
    if base < 0 or base + n_values > out.size:
        return False
    consumed = lib.pq_rle_decode(payload, len(payload), bit_width, n_values,
                                 out.ctypes.data + 4 * base)
    return consumed >= 0


_PAGE_WALK_FIELDS = ("ptype", "data_off", "comp_size", "uncomp_size",
                     "n_vals", "enc", "dl_enc", "dl_len", "rl_len",
                     "comp_flag", "dict_n")


def pq_page_walk(raw: bytes, target_values: int):
    """Parse every parquet page header in a column chunk natively.

    Returns {field: np.ndarray[n_pages]} (see _PAGE_WALK_FIELDS; data_off
    is int64, the rest int32), or None when the native library is
    unavailable or the chunk doesn't parse (caller walks in python)."""
    lib = get_lib()
    if lib is None:
        return None
    cap = max(64, target_values // 500)
    while True:
        arrs = {f: np.empty(cap, np.int64 if f == "data_off" else np.int32)
                for f in _PAGE_WALK_FIELDS}
        n = lib.pq_page_walk(raw, len(raw), target_values, cap,
                             *(arrs[f].ctypes.data
                               for f in _PAGE_WALK_FIELDS))
        if n == -2:
            cap *= 4
            continue
        if n < 0:
            return None
        return {f: a[:n] for f, a in arrs.items()}


def pq_def_levels(payload: bytes, bit_width: int, n_values: int,
                  max_def: int, valid_out: np.ndarray, base: int):
    """Decode definition levels into valid bytes
    (valid_out[base:base+n_values]) and return the non-null count, or None
    (caller decodes in python).  valid_out must be uint8/bool contiguous."""
    lib = get_lib()
    if lib is None or not valid_out.flags.c_contiguous \
            or valid_out.dtype.itemsize != 1:
        return None
    if base < 0 or base + n_values > valid_out.size:
        return None
    nn = lib.pq_def_levels(payload, len(payload), bit_width, n_values,
                           max_def, valid_out.ctypes.data + base)
    return None if nn < 0 else int(nn)


def orc_rlev2_decode(body: bytes, n_values: int, signed: bool):
    """ORC RLEv2 stream (all four sub-encodings) -> int64[n_values], or
    None when the native library is unavailable or the stream is
    malformed (caller runs the python walk)."""
    lib = get_lib()
    if lib is None:
        return None
    out = np.empty(n_values, np.int64)
    consumed = lib.orc_rlev2_decode(body, len(body), n_values,
                                    1 if signed else 0, out.ctypes.data)
    return out if consumed >= 0 else None


def pq_byte_array_scan(data: np.ndarray, n_values: int):
    """Scan a parquet PLAIN BYTE_ARRAY page body into (offsets, lengths)
    int64 arrays (offsets point past each value's u32 length prefix).
    Returns None when the native library is unavailable or the page is
    truncated — the caller then walks the layout in python or falls back."""
    lib = get_lib()
    if lib is None:
        return None
    d = np.ascontiguousarray(data, dtype=np.uint8)
    offsets = np.empty(n_values, dtype=np.int64)
    lens = np.empty(n_values, dtype=np.int64)
    consumed = lib.pq_byte_array_scan(d.ctypes.data, d.size, n_values,
                                      offsets.ctypes.data, lens.ctypes.data)
    if consumed < 0:
        return None
    return offsets, lens
