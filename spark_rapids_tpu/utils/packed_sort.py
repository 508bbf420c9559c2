"""Stable argsort by integer key components: the one way this package
sorts.

XLA:CPU (and the TPU sort HLO) pay a steep premium for VARIADIC sorts:
on the build host a single-operand 2M-row u64 sort runs ~180 ms while
the same rows through a 2-operand key/value sort cost ~1060 ms and a
5-key lexsort ~2260 ms — the generic multi-operand comparator loop
defeats the specialized single-key path.  `jnp.lexsort`/`jnp.argsort`
are ALWAYS variadic (they append an iota operand), and on the v5e a
variadic sort with an f64 comparator compiled for nine minutes (PR 22).

`stable_argsort` is the entry.  For a power-of-two capacity (every
capacity `columnar/batch.py bucket_rows` makes) it sorts with
SINGLE-operand `jax.lax.sort` calls only:

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

Any other capacity has no whole number of row-id bits to plan with and
takes one variadic `jnp.lexsort` over the same components, each in the
narrowest unsigned type its width fits.  Such capacities are real: a
whole-stage program concatenates its N per-batch states inside the
program (TPC-H Q6 merges 6 x 8 = 48 state rows, Q1 sorts 6 x 1,024 =
6,144), and tests build batches by hand.  Either way the permutation is
BIT-IDENTICAL to
`jnp.lexsort(tuple(reversed(keys)))` over the components (stable, same
comparison order); callers never choose.  All ops are jit-safe (pure
jnp/lax; widths and pass structure are static).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def _u64(x: int):
    return jnp.uint64(x)


def _mask(bits: int):
    return _u64((1 << bits) - 1 if bits < 64 else 0xFFFFFFFFFFFFFFFF)


def _narrowest(arr, width: int):
    """`arr` (values < 2^width) in the narrowest unsigned type that holds
    it: XLA:TPU emulates 64-bit compares, and a 1-bit mask needs none."""
    for bits in (8, 16, 32):
        if width <= bits:
            return arr.astype(f"uint{bits}")
    return arr.astype(jnp.uint64)


def plan_passes(total_bits: int, cap: int) -> int:
    """Number of single-operand sort passes `stable_argsort` makes for
    `total_bits` key bits over `cap` rows; 0 when `cap` is not a power
    of two (one variadic lexsort instead)."""
    if cap & (cap - 1):
        return 0
    r = cap.bit_length() - 1
    chunk = 64 - r
    return max(1, -(-total_bits // chunk))


def stable_argsort(components: Sequence[Tuple[jnp.ndarray, int]],
                   cap: int) -> jnp.ndarray:
    """Stable argsort by `components` (MSB-first `(uint64 array, width)`
    pairs, every value < 2^width) — returns the int32 permutation equal
    to `jnp.lexsort` over the same keys (ties keep original order)."""
    components = list(components)
    total = sum(w for _, w in components)
    if total == 0:
        return jnp.arange(cap, dtype=jnp.int32)
    npasses = plan_passes(total, cap)
    if not npasses:
        # lexsort: LAST key is primary -> pass minor-to-major
        return jnp.lexsort(tuple(_narrowest(a, w)
                                 for a, w in reversed(components))
                           ).astype(jnp.int32)
    r = cap.bit_length() - 1
    chunk = 64 - r
    iota = jnp.arange(cap, dtype=jnp.uint64)
    mask_r = _mask(r)

    # pack the components into 64-bit words, LSB-first: bit 0 of the
    # conceptual key is the LSB of the LAST component
    nwords = (total + 63) // 64
    words: List[Optional[jnp.ndarray]] = [None] * nwords
    pos = 0
    for arr, w in reversed(components):
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

    perm = None
    for p in range(npasses):  # LSD radix: least-significant chunk first
        bits = extract(p)
        if perm is not None:
            bits = jnp.take(bits, perm)
        s = jax.lax.sort((bits << _u64(r)) | iota, dimension=0,
                         is_stable=False)
        step = (s & mask_r).astype(jnp.int32)
        perm = step if perm is None else jnp.take(perm, step)
    return perm
