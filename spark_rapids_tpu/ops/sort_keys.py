"""Order-preserving sort keys and the sort permutation.

Every sort column is encoded into order-preserving integer keys and one
stable argsort orders the whole batch — no comparator kernels:

  * numerics/dates/timestamps -> integers (floats via the IEEE monotone
    bit transform; NaN canonicalized above +inf, Spark's "NaN greatest");
  * strings -> big-endian uint64 words over the padded byte matrix (UTF-8
    byte order == code-point order) + length tiebreak;
  * null placement -> a per-column rank key (before/after non-nulls);
  * dead rows -> a most-major key pushing them to the back.

Descending columns invert their key bits (~k), which reverses order without
overflow.  The sort operator (exec/sort.py), the window operator, the
range partitioner (shuffle/partition.py) and the mesh sort
(parallel/distributed.py) all order rows through `sort_order`.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, ColumnarBatch
from . import expressions as E

_I64_MIN = np.int64(-(2**63))
_I32_MIN = np.int32(-(2**31))
_NAN_BITS = np.int64(0x7FF8000000000000)
_NAN_BITS32 = np.int32(0x7FC00000)


def float_sort_keys(data) -> List[jnp.ndarray]:
    """Order keys for float64 values with Spark semantics (NaN above +inf,
    all NaN equal, -0.0 == 0.0).

    CPU backend: ONE monotone int64 bit-pattern key — exact, including
    subnormals (XLA's flush-to-zero would make a float compare call
    5e-324 == 0.0).

    XLA:TPU refuses f64<->int bitcasts (it carries a double as a pair of
    f32), so there the keys are [nan_flag, native f64 value] and the
    comparator runs in float: exact over the values the device holds.
    This is the lexsort fallback only; the packed path orders doubles by
    f64_pair_keys below."""
    d = data.astype(jnp.float64)
    nan = jnp.isnan(d)
    if jax.default_backend() == "cpu":
        bits = jax.lax.bitcast_convert_type(d, jnp.int64)
        bits = jnp.where(bits == _I64_MIN, jnp.int64(0), bits)  # -0.0 -> 0.0
        bits = jnp.where(nan, _NAN_BITS, bits)
        return [jnp.where(bits >= 0, bits, ~bits + _I64_MIN)]
    v = jnp.where(nan | (d == 0.0), jnp.float64(0.0), d)
    return [nan.astype(jnp.int32), v]


def column_sort_keys(c: Column, ascending: bool) -> List[jnp.ndarray]:
    """Order-preserving keys for one column, most-significant first
    (integer keys, except a native-f64 value key for float columns).
    Null rows are zeroed (a separate null-rank key places them)."""
    if c.dtype.is_string:
        cap, L = c.data.shape
        assert L % 8 == 0, L  # bucket_strlen yields power-of-two >= 8
        w = c.data.reshape(cap, L // 8, 8).astype(jnp.uint64)
        shifts = jnp.arange(56, -8, -8, dtype=jnp.uint64)
        words = jnp.sum(w << shifts, axis=2, dtype=jnp.uint64)
        keys = [words[:, j] for j in range(L // 8)]
        keys.append(c.lengths.astype(jnp.int64))
    elif c.dtype.is_floating:
        keys = float_sort_keys(c.data)
    else:
        keys = [c.data.astype(jnp.int64)]
    keys = [jnp.where(c.valid, k, jnp.zeros((), k.dtype)) for k in keys]
    if not ascending:
        # integers invert bitwise; float value keys invert by negation
        keys = [(-k if jnp.issubdtype(k.dtype, jnp.floating) else ~k)
                for k in keys]
    return keys


# --------------------------------------------------------------------------
# packed-key components (ops-level twin of column_sort_keys: same order-
# preserving encodings, but as (uint64 value < 2^width, width) pairs so
# utils/packed_sort can fuse several columns into one 64-bit sort word)
# --------------------------------------------------------------------------

_INT_WIDTHS = {"boolean": 1, "byte": 8, "short": 16, "int": 32,
               "date": 32, "long": 64, "timestamp": 64}


def _biased(vals_i64, width: int):
    """Signed int64 values known to fit `width` bits -> uint64 with the
    same order under UNSIGNED compare (add 2^(width-1), i.e. flip the
    sign bit of the width-bit representation)."""
    if width == 64:
        return vals_i64.astype(jnp.uint64) ^ jnp.uint64(1 << 63)
    return (vals_i64.astype(jnp.int64)
            + jnp.int64(1 << (width - 1))).astype(jnp.uint64)


def _f32_key(data) -> jnp.ndarray:
    """32-bit monotone integer key for float32 values with the same
    Spark semantics as float_sort_keys (NaN above +inf, all NaN equal,
    -0.0 == 0.0), via the IEEE bit transform on the NATIVE width —
    half the key bits of the f64 route, same order."""
    d = data.astype(jnp.float32)
    nan = jnp.isnan(d)
    bits = jax.lax.bitcast_convert_type(d, jnp.int32)
    bits = jnp.where(bits == _I32_MIN, jnp.int32(0), bits)  # -0.0 -> 0.0
    bits = jnp.where(nan, _NAN_BITS32, bits)
    return jnp.where(bits >= 0, bits, ~bits + _I32_MIN).astype(jnp.int64)


def f64_pair_keys(data) -> List[jnp.ndarray]:
    """Two 32-bit monotone integer keys (hi, lo; MSB-first) for float64
    values where the compiler has no f64<->int bitcast: XLA:TPU carries a
    double as hi = f32(d), lo = f32(d - hi), and ordering that pair
    lexicographically IS the device's own float order (measured on a v5e:
    the split reconstructs every value the device holds).  A multi-operand
    sort with an f64 comparator took 9 minutes to compile for the chip at
    64k rows; these keys ride the single-operand packed sort instead."""
    d = data.astype(jnp.float64)
    hi = d.astype(jnp.float32)
    lo = (d - hi.astype(jnp.float64)).astype(jnp.float32)
    return [_f32_key(hi), _f32_key(lo)]


def column_key_components(c: Column, ascending: bool):
    """Packed-sort components for one column, MSB-first, or None when
    this column's keys are not order-preserving integers (an unknown
    device dtype).  Null rows are zeroed (the caller's null-rank
    component places them); descending inverts within the component's
    width."""
    from ..types import FloatType
    comps = []  # (int64-or-uint64 values, width, already_unsigned)
    if c.dtype.is_string:
        cap, L = c.data.shape
        assert L % 8 == 0, L
        w = c.data.reshape(cap, L // 8, 8).astype(jnp.uint64)
        shifts = jnp.arange(56, -8, -8, dtype=jnp.uint64)
        words = jnp.sum(w << shifts, axis=2, dtype=jnp.uint64)
        for j in range(L // 8):
            comps.append((words[:, j], 64, True))
        comps.append((c.lengths.astype(jnp.int64),
                      max(1, int(L).bit_length()), True))
    elif c.dtype.is_floating:
        if c.dtype is FloatType:
            comps.append((_f32_key(c.data), 32, False))
        elif jax.default_backend() == "cpu":
            comps.append((float_sort_keys(c.data)[0], 64, False))
        else:
            comps.extend((k, 32, False) for k in f64_pair_keys(c.data))
    else:
        width = _INT_WIDTHS.get(c.dtype.name)
        if width is None:
            return None  # unknown device dtype: keep the lexsort path
        # booleans are already unsigned 0/1; signed ints bias below
        comps.append((c.data.astype(jnp.int64), width,
                      c.dtype.name == "boolean"))
    out = []
    for vals, width, unsigned in comps:
        u = (vals.astype(jnp.uint64) if unsigned
             else _biased(vals, width))
        u = jnp.where(c.valid, u, jnp.uint64(0))
        if not ascending:
            # complement within the width: reverses unsigned order
            mask = jnp.uint64((1 << width) - 1 if width < 64
                              else 0xFFFFFFFFFFFFFFFF)
            u = (~u) & mask
        out.append((u, width))
    return out


def packed_sort_components(batch: ColumnarBatch,
                           cols: Sequence[Column],
                           ascending: Sequence[bool],
                           nulls_first: Sequence[bool]):
    """All components of the full sort spec (live flag, per-column null
    rank + keys), or None when any column is packed-ineligible."""
    live = batch.sel
    comps = [((~live).astype(jnp.uint64), 1)]
    for c, asc, nf in zip(cols, ascending, nulls_first):
        # one bit, not the lexsort path's 0/1/2 rank: per column only
        # TWO of the three rank values ever occur (nulls before valids
        # or after), and packed bits are precious
        null_rank = jnp.where(c.valid,
                              jnp.uint64(1) if nf else jnp.uint64(0),
                              jnp.uint64(0) if nf else jnp.uint64(1))
        comps.append((null_rank, 1))
        ck = column_key_components(c, asc)
        if ck is None:
            return None
        comps.extend(ck)
    return comps


def sort_order(batch: ColumnarBatch, exprs: Sequence[E.Expression],
               ascending: Sequence[bool], nulls_first: Sequence[bool],
               stats: dict = None):
    """Stable permutation ordering live rows by the sort spec, dead rows
    last.  `nulls_first` is the EFFECTIVE placement (already accounts for
    direction, like SortOrder.effective_nulls_first).

    The key components fuse into 64-bit words with the row id embedded
    in the low bits, ordered by SINGLE-operand sort passes (one pass when
    everything fits one word; utils/packed_sort) — identical permutation
    to the variadic lexsort below, minus its multi-operand comparator
    cost.  `stats`, when given, records which path the trace took
    (host-side, trace-time: the exec's numPackedSorts counter reads it)."""
    from ..utils import packed_sort as PS
    live = batch.sel
    cols = [e.eval(batch) for e in exprs]
    comps = packed_sort_components(batch, cols, ascending, nulls_first)
    if comps is not None:
        npasses = PS.plan_passes(sum(w for _, w in comps), batch.capacity)
        # a very wide spec (many long string columns) can need more
        # radix passes than the lexsort has keys — not a win there
        if npasses <= max(8, len(comps)):
            if stats is not None:
                stats["packed"] = npasses > 0
                stats["passes"] = npasses
            return PS.stable_argsort(comps, batch.capacity)
    if stats is not None:
        stats["packed"] = False
    major: List[jnp.ndarray] = [(~live).astype(jnp.int32)]
    for c, asc, nf in zip(cols, ascending, nulls_first):
        null_rank = jnp.where(c.valid, jnp.int32(1),
                              jnp.int32(0) if nf else jnp.int32(2))
        major.append(null_rank)
        major.extend(column_sort_keys(c, asc))
    # lexsort: LAST key is primary -> pass minor-to-major
    return jnp.lexsort(tuple(reversed(major))).astype(jnp.int32)
