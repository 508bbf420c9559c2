"""One-shot packed-key argsort.

XLA:CPU (and the TPU sort HLO) pay a steep premium for VARIADIC sorts:
on the build host a single-operand 2M-row u64 sort runs ~180 ms while
the same rows through a 2-operand key/value sort cost ~1060 ms and a
5-key lexsort ~2260 ms (BENCH_PALLAS `argsort_*` rows) — the generic
multi-operand comparator loop defeats the specialized single-key path.
`jnp.lexsort`/`jnp.argsort` are ALWAYS variadic (they append an iota
operand), so every sort in the engine was paying it.

This module sorts with SINGLE-operand `jax.lax.sort` calls only:

  * the caller's order-preserving integer key components (each a uint64
    array holding values < 2^width) concatenate — conceptually — into
    one big-endian bit string;
  * the ROW ID is embedded in the low `r = log2(capacity)` bits of every
    sort word, so one unstable single-operand sort yields both the order
    and the permutation, and ties break by original index — which is
    exactly `lexsort` stability;
  * when the total key width fits `64 - r` bits, ONE sort call does the
    whole job (the one-shot packed-key path);
  * wider keys run a stable LSD radix: sort by the LEAST significant
    `64 - r` key bits first, gather, repeat toward the most significant
    chunk — each pass a single-operand sort, `ceil(total_bits/(64-r))`
    passes in all.

The permutation returned is BIT-IDENTICAL to
`jnp.lexsort(tuple(reversed(keys)))` over the same components (stable,
same comparison order), so callers may switch freely per the
`spark.rapids.sql.tpu.sort.packed.enabled` kill switch without changing
results.  All ops are jit-safe (pure jnp/lax; widths and pass structure
are static).

A Pallas tiled bitonic variant (`ops/pallas_kernels.bitonic_sort_u64`)
can take the single-pass sort when `spark.rapids.sql.tpu.pallas.enabled`
is on and the backend is a TPU; a kernel that fails to lower raises.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# latched from conf by the sort/aggregate execs (mirrors
# aggregate._PALLAS_CUMSUM): [0] = packed path enabled, [1] = pallas
# bitonic wanted for the single-pass sort
_PACKED = [True]
_PALLAS_SORT = [False]


def set_packed_enabled(enabled: bool) -> None:
    _PACKED[0] = bool(enabled)  # tpulint: disable=TPU009 per-session conf latch: an atomic boolean store, and every concurrent query of one session writes the same session-conf value


def packed_enabled() -> bool:
    return _PACKED[0]


def set_pallas_sort(enabled: bool) -> None:
    _PALLAS_SORT[0] = bool(enabled)  # tpulint: disable=TPU009 per-session conf latch: atomic boolean store, same-value writers under one session conf


def _u64(x: int):
    return jnp.uint64(x)


def _mask(bits: int):
    return _u64((1 << bits) - 1 if bits < 64 else 0xFFFFFFFFFFFFFFFF)


def plan_passes(total_bits: int, cap: int) -> int:
    """Number of single-operand sort passes a packed argsort of
    `total_bits` key bits over `cap` rows needs (cap a power of two)."""
    r = cap.bit_length() - 1
    chunk = 64 - r
    return max(1, -(-total_bits // chunk))


def _sort_words(keys):
    """Single-operand u64 sort, through the Pallas tiled bitonic network
    when the flag is on and the backend is a TPU (a kernel that fails to
    lower raises: the flag never quietly means lax.sort)."""
    if _PALLAS_SORT[0] and jax.default_backend() == "tpu":
        from ..ops.pallas_kernels import bitonic_sort_u64
        return bitonic_sort_u64(keys)
    return jax.lax.sort(keys, dimension=0, is_stable=False)


def packed_argsort(components: Sequence[Tuple[jnp.ndarray, int]],
                   cap: int) -> jnp.ndarray:
    """Stable argsort by `components` (MSB-first `(uint64 array, width)`
    pairs, every value < 2^width) — returns the int32 permutation equal
    to `jnp.lexsort` over the same keys (ties keep original order)."""
    assert cap and (cap & (cap - 1)) == 0, f"capacity {cap} not a power of 2"
    r = cap.bit_length() - 1
    chunk = 64 - r
    iota = jnp.arange(cap, dtype=jnp.uint64)
    mask_r = _mask(r)
    total = sum(w for _, w in components)
    if total == 0:
        return jnp.arange(cap, dtype=jnp.int32)

    # pack the components into 64-bit words, LSB-first: bit 0 of the
    # conceptual key is the LSB of the LAST component
    nwords = (total + 63) // 64
    words: List[Optional[jnp.ndarray]] = [None] * nwords
    pos = 0
    for arr, w in reversed(list(components)):
        a = arr.astype(jnp.uint64)
        lo, sh = pos // 64, pos % 64
        part = (a << _u64(sh)) if sh else a
        words[lo] = part if words[lo] is None else words[lo] | part
        if sh + w > 64:
            hi = a >> _u64(64 - sh)
            words[lo + 1] = (hi if words[lo + 1] is None
                             else words[lo + 1] | hi)
        pos += w
    zeros = jnp.zeros(cap, dtype=jnp.uint64)
    words = [w if w is not None else zeros for w in words]

    def extract(p: int):
        """Key bits [p*chunk, (p+1)*chunk) of the conceptual key,
        counted from the LSB."""
        start = p * chunk
        cw = min(chunk, total - start)
        lo, sh = start // 64, start % 64
        v = words[lo] >> _u64(sh) if sh else words[lo]
        if sh + cw > 64 and lo + 1 < nwords:
            v = v | (words[lo + 1] << _u64(64 - sh))
        return v & _mask(cw)

    npasses = plan_passes(total, cap)
    perm = None
    for p in range(npasses):  # LSD radix: least-significant chunk first
        bits = extract(p)
        if perm is not None:
            bits = jnp.take(bits, perm)
        s = _sort_words((bits << _u64(r)) | iota)
        step = (s & mask_r).astype(jnp.int32)
        perm = step if perm is None else jnp.take(perm, step)
    return perm
