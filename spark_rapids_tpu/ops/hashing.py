"""Device hashing kernels.

Two uses, mirroring the reference:
  * murmur3_32 with Spark's seed 42 for hash partitioning parity
    (reference: GpuHashPartitioning.scala — cudf murmur3 matches Spark)
  * 64-bit mix hashes for sort-based grouping/joins (the TPU-first stand-in
    for cuDF's hash tables: we SORT by two independent 64-bit hashes and verify
    equality against the previous row, so a wrong group needs a 128-bit
    double collision *and* adjacency interleave)

All pure jnp integer ops; they trace into the surrounding pipeline program.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..columnar import Column
from .expressions import Expression as _Expr

def _u(x):
    return x.astype(jnp.uint64)


def mix64(x):
    """splitmix64-style finalizer (uint64 in/out)."""
    x = _u(x)
    x = x ^ (x >> 33)
    x = x * jnp.uint64(0xff51afd7ed558ccd)
    x = x ^ (x >> 33)
    x = x * jnp.uint64(0xc4ceb9fe1a85ec53)
    x = x ^ (x >> 33)
    return x


def f64_bits(d):
    """Injective int64 encoding of a float64 array's values.

    On CPU this is the exact IEEE bit pattern.  XLA:TPU refuses f64->int
    bitcasts (it carries a double as an f32 pair), so there the encoding
    is (bits(hi_f32) << 32) | bits(lo_f32) where hi = round-to-f32(d),
    lo = d - hi — the pair the compiler stores (measured on a v5e: the
    split reconstructs every value the device holds), hence injective on
    every value the device can represent."""
    import jax
    d = d.astype(jnp.float64)
    if jax.default_backend() == "cpu":
        return jax.lax.bitcast_convert_type(d, jnp.int64)
    hi = d.astype(jnp.float32)
    lo = (d - hi.astype(jnp.float64)).astype(jnp.float32)
    hb = jax_bitcast_i32(hi).astype(jnp.int64)
    lb = jax_bitcast_i32(lo).astype(jnp.int64)
    return (hb << jnp.int64(32)) | (lb & jnp.int64(0xFFFFFFFF))


def _normalize_bits(col: Column):
    """Value bits with Spark key semantics: -0.0 == 0.0, all NaN equal."""
    data = col.data
    if col.dtype.is_floating:
        d = data.astype(jnp.float64)
        # -0.0 -> 0.0 via select, NOT `d + 0.0`: XLA's algebraic
        # simplifier folds x+0 away under jit, skipping the normalization
        d = jnp.where(d == 0.0, jnp.float64(0.0), d)
        canonical_nan = jnp.float64(np.nan)
        d = jnp.where(jnp.isnan(d), canonical_nan, d)
        return f64_bits(d)
    if col.dtype.is_string:
        raise AssertionError("use string path")
    if data.dtype == jnp.bool_:
        return data.astype(jnp.int64)
    return data.astype(jnp.int64)


def hash_column64(col: Column, seed: int):
    """uint64 per-row hash of one column (nulls get a fixed tag)."""
    if col.dtype.is_string:
        h = _hash_bytes(col, seed)
    else:
        bits = _normalize_bits(col)
        h = mix64(_u(bits) ^ jnp.uint64(seed * 0x9e3779b97f4a7c15 % 2**64))
    null_h = mix64(jnp.uint64((seed + 0x51ed2701) % 2**64))
    return jnp.where(col.valid, h, null_h)


def _hash_bytes(col: Column, seed: int):
    """Polynomial rolling hash over the byte matrix, mixed; vectorized over
    rows, lax.scan over the (static) max_len positions."""
    import jax
    data = col.data
    cap, L = data.shape
    pos_mask = jnp.arange(L, dtype=jnp.int32)[None, :] < col.lengths[:, None]
    b = jnp.where(pos_mask, data, 0).astype(jnp.uint64)

    def step(carry, cols):
        byte, m = cols
        carry = jnp.where(m, carry * jnp.uint64(1099511628211) ^ byte, carry)
        return carry, None

    # derive the init from a (possibly shard_map-varying) input so the scan
    # carry has the same varying-axes type as xs: a constant init fails
    # vma typing when this runs inside shard_map (distributed string keys)
    vzero = (col.lengths ^ col.lengths).astype(jnp.uint64)
    init = vzero + jnp.uint64((14695981039346656037 + seed * 31) % 2**64)
    h, _ = jax.lax.scan(step, init, (b.T, pos_mask.T))
    return mix64(h ^ _u(col.lengths.astype(jnp.int64)))


def hash_columns_double(cols, live):
    """(h1, h2) independent uint64 hashes over multiple key columns.
    Dead rows get uint64 max so a stable sort pushes them last."""
    h1 = jnp.zeros(live.shape, dtype=jnp.uint64)
    h2 = jnp.zeros(live.shape, dtype=jnp.uint64)
    for i, c in enumerate(cols):
        h1 = mix64(h1 ^ hash_column64(c, 2 * i + 1))
        h2 = mix64(h2 ^ hash_column64(c, 7919 * (i + 1)))
    maxu = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    h1 = jnp.where(live, h1, maxu)
    h2 = jnp.where(live, h2, maxu)
    return h1, h2


# ---- murmur3 32-bit, Spark-compatible (seed 42) ---------------------------

def _rotl32(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _mmh3_mix_k(k):
    k = k * jnp.uint32(0xcc9e2d51)
    k = _rotl32(k, 15)
    return k * jnp.uint32(0x1b873593)


def _mmh3_mix_h(h, k):
    h = h ^ _mmh3_mix_k(k)
    h = _rotl32(h, 13)
    return h * jnp.uint32(5) + jnp.uint32(0xe6546b64)


def _mmh3_final(h, length):
    h = h ^ jnp.uint32(length)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85ebca6b)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xc2b2ae35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _seed_u32(seed, shape):
    if isinstance(seed, (int, np.integer)):
        return jnp.full(shape, np.uint32(seed % 2**32), dtype=jnp.uint32)
    return seed.astype(jnp.uint32)


def murmur3_int(x_i32, seed):
    """Spark hashInt: one 4-byte block."""
    h = _mmh3_mix_h(_seed_u32(seed, x_i32.shape), x_i32.astype(jnp.uint32))
    return _mmh3_final(h, 4).astype(jnp.int32)


def murmur3_long(x_i64, seed):
    """Spark hashLong: low word then high word."""
    u = x_i64.astype(jnp.uint64)
    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
    h = _mmh3_mix_h(_seed_u32(seed, x_i64.shape), lo)
    h = _mmh3_mix_h(h, hi)
    return _mmh3_final(h, 8).astype(jnp.int32)


def spark_hash_column(col: Column, seed):
    """Spark Murmur3Hash semantics per type (null -> seed passthrough).

    reference: GpuHashPartitioning uses cudf murmur3 which matches Spark's
    Murmur3Hash expression for these types."""
    dt = col.dtype
    if dt.is_string:
        return _spark_hash_string(col, seed)
    if dt.name in ("int", "short", "byte", "date"):
        h = murmur3_int(col.data.astype(jnp.int32), seed)
    elif dt.name in ("long", "timestamp"):
        h = murmur3_long(col.data.astype(jnp.int64), seed)
    elif dt.name == "boolean":
        h = murmur3_int(col.data.astype(jnp.int32), seed)
    elif dt.name == "float":
        # normalize -0.0 and NaN in the INTEGER domain: float compares
        # flush subnormals to zero on XLA (FTZ), which would alias 5e-45
        # with 0.0 while the Spark oracle hashes the true bits
        bits = jax_bitcast_i32(col.data.astype(jnp.float32))
        bits = jnp.where(bits == jnp.int32(-2**31), jnp.int32(0), bits)
        exp = bits & jnp.int32(0x7F800000)
        mant = bits & jnp.int32(0x007FFFFF)
        is_nan = (exp == jnp.int32(0x7F800000)) & (mant != 0)
        bits = jnp.where(is_nan, jnp.int32(0x7FC00000), bits)
        h = murmur3_int(bits, seed)
    elif dt.name == "double":
        # exact Spark bit parity on CPU; injective pair encoding on TPU
        # (documented incompat: emulated f64 has no true IEEE bits)
        bits = f64_bits(col.data.astype(jnp.float64))
        bits = jnp.where(bits == jnp.int64(-2**63), jnp.int64(0), bits)
        exp = bits & jnp.int64(0x7FF0000000000000)
        mant = bits & jnp.int64(0x000FFFFFFFFFFFFF)
        is_nan = (exp == jnp.int64(0x7FF0000000000000)) & (mant != 0)
        bits = jnp.where(is_nan, jnp.int64(0x7FF8000000000000), bits)
        h = murmur3_long(bits, seed)
    else:
        raise NotImplementedError(f"spark hash of {dt.name}")
    if isinstance(seed, (int, np.integer)):
        seed_arr = jnp.full(h.shape, seed, dtype=jnp.int32)
    else:
        seed_arr = seed
    return jnp.where(col.valid, h, seed_arr)


def jax_bitcast_i32(x):
    import jax
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _spark_hash_string(col: Column, seed):
    """Murmur3 over UTF-8 bytes, 4-byte little-endian blocks + tail, exactly
    Spark's UTF8String hashing."""
    import jax
    data = col.data
    cap, L = data.shape
    nblocks_max = L // 4
    h0 = _seed_u32(seed, (cap,))
    lens = col.lengths
    nblocks = lens // 4

    if nblocks_max > 0:
        blocks = data[:, :nblocks_max * 4].reshape(cap, nblocks_max, 4)
        words = (blocks[:, :, 0].astype(jnp.uint32)
                 | (blocks[:, :, 1].astype(jnp.uint32) << 8)
                 | (blocks[:, :, 2].astype(jnp.uint32) << 16)
                 | (blocks[:, :, 3].astype(jnp.uint32) << 24))

        def step(carry, cols):
            w, active = cols
            nh = _mmh3_mix_h(carry, w)
            return jnp.where(active, nh, carry), None

        active = (jnp.arange(nblocks_max, dtype=jnp.int32)[None, :]
                  < nblocks[:, None])
        # the carry takes the rows' type: inside a shard_map the rows vary
        # along the mesh axis and a constant seed does not, and scan wants
        # the carry's type to equal the body's output's
        h, _ = jax.lax.scan(step, h0 + jnp.zeros_like(lens, jnp.uint32),
                            (words.T, active.T))
    else:
        h = h0
    # tail: Spark's hashUnsafeBytes mixes each remaining byte individually
    # as a sign-extended int
    tail_start = nblocks * 4
    for t in range(3):
        idx = jnp.clip(tail_start + t, 0, L - 1)
        byte = jnp.take_along_axis(data, idx[:, None], axis=1)[:, 0]
        sb = byte.astype(jnp.int8).astype(jnp.int32)  # sign-extended
        active = (tail_start + t) < lens
        nh = _mmh3_mix_h(h, sb.astype(jnp.uint32))
        h = jnp.where(active, nh, h)
    # finalizer with per-row byte length
    hh = h ^ lens.astype(jnp.uint32)
    hh = hh ^ (hh >> jnp.uint32(16))
    hh = hh * jnp.uint32(0x85ebca6b)
    hh = hh ^ (hh >> jnp.uint32(13))
    hh = hh * jnp.uint32(0xc2b2ae35)
    hh = hh ^ (hh >> jnp.uint32(16))
    res = hh.astype(jnp.int32)
    seed_arr = _seed_u32(seed, res.shape).astype(jnp.int32)
    return jnp.where(col.valid, res, seed_arr)


def spark_hash_columns(cols, seed: int = 42):
    """Spark's Murmur3Hash(cols): fold, each column re-seeding with the
    previous hash."""
    h = None
    for c in cols:
        h = spark_hash_column(c, seed if h is None else h)
    return h


class Murmur3Hash(_Expr):
    """Spark `hash(...)` expression: murmur3_32 folded across the argument
    columns with seed 42, nulls passing the running seed through unchanged
    (reference: Murmur3Hash in HashExpression; GpuMurmur3Hash delegates to
    the same cudf kernel the partitioner uses)."""

    def __init__(self, *children, seed: int = 42):
        self.children = tuple(children)
        self.seed = int(seed)

    @property
    def dtype(self):
        from ..types import IntegerType
        return IntegerType

    def eval(self, batch):
        from ..types import IntegerType
        h = self.seed
        for ch in self.children:
            h = spark_hash_column(ch.eval(batch), h)
        cap = batch.capacity
        if isinstance(h, int):  # no children: constant seed
            h = jnp.full(cap, h, dtype=jnp.int32)
        valid = jnp.ones(cap, dtype=jnp.bool_)
        return Column(h.astype(jnp.int32), valid, IntegerType)
