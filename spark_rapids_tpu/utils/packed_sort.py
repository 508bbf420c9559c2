"""Stable argsort by integer key components: the one way this package
sorts; and `merge_windows`, the one way it ranks one batch's hashes among
another's sorted hashes (the join's candidate windows), made of the same
single-operand sort.

XLA:CPU (and the TPU sort HLO) pay a steep premium for VARIADIC sorts:
on the build host a single-operand 2M-row u64 sort runs ~180 ms while
the same rows through a 2-operand key/value sort cost ~1060 ms and a
5-key lexsort ~2260 ms — the generic multi-operand comparator loop
defeats the specialized single-key path.  `jnp.lexsort`/`jnp.argsort`
are ALWAYS variadic (they append an iota operand), and on the v5e a
variadic sort with an f64 comparator compiled for nine minutes (PR 22).
On that chip (`_chip/window_cost.py`, PR 30) a single-operand sort of 2M
uint64 words takes 3.8 ms (1M: 2.1 ms, 2M uint32: 2.2 ms), a prefix scan
of 2M int32 1.0 to 1.4 ms, a 1M-row gather through a random index 8.3 ms
a uint32 and 17.4 ms a uint64, a 1M-row scatter through a permutation 5.6
ms: a sort costs less than half a gather, so whatever can be phrased as
sort-and-scan is, and a chain of dependent gathers (a binary search: 21
steps of them over 1M rows, 1.05 s for both sides of a window) is the
form to avoid.

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


def merge_windows(h_sorted, h_query, live):
    """Candidate windows of `h_query` in `h_sorted` from ONE merge:
    -> (lo, hi, max_width), int32 `[lo[i], hi[i])` holding every j with
    `h_sorted[j] == h_query[i]`, and the widest window of a `live` query.

    `h_sorted` is ascending uint64 (cap_b), `h_query` any uint64 (cap_l).
    Both concatenate, build first, to `2^r` elements; one single-operand
    sort orders the words `top (64 - r) hash bits | r-bit position id`,
    so inside a run of equal prefixes the build elements come first.  In
    that order `hi` of a query is the running count of build elements and
    `lo` that count at the start of its run (both monotone: two prefix
    scans).  Two more single-operand sorts, of `query index | lo` and
    `query index | hi`, bring both back to the queries' own order: three
    sorts and two scans whatever cap_b is, and no gather, where a binary
    search chains `2 log2(cap_b)` dependent gathers a side (the header
    has what each costs on the chip).

    The window is over the kept PREFIX, a superset of the equal-hash
    window (equal to it unless two different hashes share their top
    `64 - r` bits); callers verify candidates by key, so a wider window
    is a cost and never a wrongness."""
    cap_b, cap_l = h_sorted.shape[0], h_query.shape[0]
    total = cap_b + cap_l
    r = max(1, (total - 1).bit_length())
    n = 1 << r
    mask_r = _mask(r)
    ids = jnp.arange(n, dtype=jnp.uint64)
    # padding sorts last: the all-ones prefix under the highest ids
    h = jnp.concatenate([h_sorted, h_query,
                         jnp.full(n - total, _mask(64), jnp.uint64)])
    s = jax.lax.sort((h & ~mask_r) | ids, dimension=0, is_stable=False)
    sid = s & mask_r
    prefix = s >> _u64(r)
    is_build = (sid < _u64(cap_b)).astype(jnp.int32)
    hi_all = jnp.cumsum(is_build)
    run_start = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), prefix[1:] != prefix[:-1]])
    lo_all = jax.lax.cummax(jnp.where(run_start, hi_all - is_build, 0))
    # back to query order: a query's word is its own index above the
    # value (at most cap_b < 2^r), everything else sorts behind them
    is_query = (sid >= _u64(cap_b)) & (sid < _u64(total))
    slot = (sid - _u64(cap_b)) << _u64(r)

    def in_query_order(vals):
        words = jnp.where(is_query, slot | vals.astype(jnp.uint64),
                          _mask(64))
        back = jax.lax.sort(words, dimension=0, is_stable=False)[:cap_l]
        return (back & mask_r).astype(jnp.int32)

    lo, hi = in_query_order(lo_all), in_query_order(hi_all)
    return lo, hi, jnp.max(jnp.where(live, hi - lo, 0))
