"""TPU hash aggregate.

Reference behavior: rapids/aggregate.scala — streaming per-partition loop
(per batch: update-aggregate; across batches: concat running state and
merge-aggregate; finally: finalize projection), Partial/Final phases bound
separately (setupReferences :585).

TPU-first implementation: no hash table.  Scatter is slow on TPU, so
grouping is SORT-based with static shapes:

  1. hash keys twice (64-bit each), stable-sort rows by (h1, h2) — dead
     rows get max hash and fall to the back;
  2. group boundary = hash changed OR any key column differs from the
     previous sorted row (hash collisions cannot create wrong groups unless
     BOTH 64-bit hashes collide AND rows interleave);
  3. group id = prefix-sum of boundaries; segment reductions with
     indices_are_sorted=True (XLA lowers these without scatter);
  4. output keys gathered from each group's first row; output capacity =
     input capacity, live rows = number of groups.

Multi-batch streams fold through the same kernel: the running state batch is
concatenated with each new partial result and re-grouped (merge aggregates),
exactly the reference's concatenateBatches + merge pass.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, ColumnarBatch, bucket_rows
from ..columnar.batch import _normalize_devices
from ..ops import expressions as E
from ..ops.aggregates import AggregateExpression
from ..ops.hashing import hash_columns_double
from ..types import (DoubleType, LongType, Schema, StructField)
from ..utils.tracing import named_range
from .base import ExecContext, ExecNode, TpuExec, record_output_batch
from ..metrics import names as MN

_I64_MAX = np.int64(2**63 - 1)
_I64_MIN = np.int64(-(2**63))

# kernel keys whose bucket update came back dirty (more groups a batch
# than its state holds): skip it for them from then on
_BUCKET_DIRTY_KEYS: set = set()


def _flatten_stacked(partials: ColumnarBatch, state_schema) -> ColumnarBatch:
    """vmapped per-batch partial states [k, pcap, ...] -> one [k*pcap]
    merge input (shared by the sort and bucket whole-stage programs)."""
    cols = []
    for c in partials.columns:
        data = c.data.reshape((-1,) + c.data.shape[2:])
        valid = c.valid.reshape(-1)
        lengths = c.lengths.reshape(-1) if c.lengths is not None else None
        cols.append(Column(data, valid, c.dtype, lengths))
    return ColumnarBatch(cols, partials.sel.reshape(-1), state_schema)


def _stack_states(states: Sequence[ColumnarBatch]) -> ColumnarBatch:
    """Equal-capacity states -> the [k, pcap, ...] form _flatten_stacked
    takes; string columns are padded to the widest first (a string state
    is as wide as the batch it was taken from)."""
    widths = [max(c.max_len for c in cols) if cols[0].dtype.is_string
              else None for cols in zip(*(s.columns for s in states))]
    padded = [ColumnarBatch([c if w is None else c.pad_strings_to(w)
                             for c, w in zip(s.columns, widths)],
                            s.sel, s.schema) for s in states]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


def _head_rows(batch: ColumnarBatch, cap: int) -> ColumnarBatch:
    """The first `cap` rows of a batch whose live rows are in front (a
    merged state's are: `sel = iota < ngroups`)."""
    cols = [Column(c.data[:cap], c.valid[:cap], c.dtype,
                   c.lengths[:cap] if c.lengths is not None else None)
            for c in batch.columns]
    return ColumnarBatch(cols, batch.sel[:cap], batch.schema)


def _part_rows(parts: Sequence[ColumnarBatch]):
    """int32[K]: the live rows of each part, for the host's one read."""
    return jnp.stack([p.num_rows() for p in parts])


def _concat_prefixes(parts: Sequence[ColumnarBatch],
                     cap: int) -> ColumnarBatch:
    """What `concat_batches(parts, cap)` gives, traced, for parts whose
    live rows are a prefix (every partial state is: `sel = iota <
    ngroups`): each part's live prefix at its running offset, in part
    order, string columns padded to the widest part, zeros past the
    total.  A part is written whole at its offset and the next one
    overwrites its dead tail, so no row is sorted, compacted or scanned;
    the buffer has room for the last part's capacity past `cap` (a
    `dynamic_update_slice` that does not fit would be moved back)."""
    schema = parts[0].schema
    counts = [p.num_rows().astype(jnp.int32) for p in parts]
    offsets = [jnp.int32(0)]
    for n in counts[:-1]:
        offsets.append(offsets[-1] + n)
    keep = jnp.arange(cap, dtype=jnp.int32) < offsets[-1] + counts[-1]
    room = cap + max(p.capacity for p in parts)

    def place(leaves, dtype):
        buf = jnp.zeros((room,) + leaves[0].shape[1:], dtype)
        for x, off in zip(leaves, offsets):
            buf = jax.lax.dynamic_update_slice(
                buf, x, (off,) + (jnp.int32(0),) * (x.ndim - 1))
        head = buf[:cap]
        mask = keep.reshape((cap,) + (1,) * (head.ndim - 1))
        return jnp.where(mask, head, jnp.zeros((), dtype))

    cols = []
    for ci, f in enumerate(schema):
        pcols = [p.columns[ci] for p in parts]
        valid = place([c.valid for c in pcols], jnp.bool_)
        if f.dtype.is_string:
            width = max(c.max_len for c in pcols)
            pcols = [c.pad_strings_to(width) for c in pcols]
            cols.append(Column(place([c.data for c in pcols], jnp.uint8),
                               valid, f.dtype,
                               place([c.lengths for c in pcols], jnp.int32)))
        else:
            cols.append(Column(place([c.data for c in pcols],
                                     f.dtype.jnp_dtype), valid, f.dtype))
    return ColumnarBatch(cols, keep, schema)


def _thread_params(fn, params):
    """Parameter-threaded twin of an absorbing program: the bound values
    of the absorbed chain's plan-cache parameters lead the arguments and
    install as the active binding while the program traces (see
    exec/basic.bound_param_builder)."""
    if not params:
        return fn
    slots = [p.slot for p in params]

    def fn_p(pv, *args):
        with E.bound_params(dict(zip(slots, pv))):
            return fn(*args)
    return fn_p


def _type_max(dt):
    """Identity element for Min over dtype dt (largest value)."""
    j = dt.jnp_dtype
    if dt.is_floating:
        return jnp.asarray(jnp.inf, j)
    return jnp.asarray(jnp.iinfo(j).max if dt.name != "boolean" else True,
                       j)


def _type_min(dt):
    """Identity element for Max over dtype dt (smallest value)."""
    j = dt.jnp_dtype
    if dt.is_floating:
        return jnp.asarray(-jnp.inf, j)
    return jnp.asarray(jnp.iinfo(j).min if dt.name != "boolean" else False,
                       j)


def _key_equal_slots(c: Column, rows):
    """[G, cap]: row i's key value-equals the key at row rows[g] (Spark
    grouping equality: nulls equal, NaN equal, -0.0 == 0.0 — the same
    contract as _col_differs_from_prev).  A broadcast compare against the
    G gathered keys: no index operand has cap elements."""
    from ..ops.hashing import _normalize_bits
    vg = jnp.take(c.valid, rows)[:, None]
    v = c.valid[None, :]
    if c.dtype.is_string:
        # 4 bytes to a word, one [G, cap] compare per word: a reduce over
        # the byte axis would materialise its [G, cap] result
        cap, L = c.data.shape
        w = jax.lax.bitcast_convert_type(
            c.data.reshape(cap, L // 4, 4), jnp.uint32)
        wg = jnp.take(w, rows, axis=0)
        lg = jnp.take(c.lengths, rows)
        dd = c.lengths[None, :] == lg[:, None]
        for j in range(L // 4):
            dd &= w[None, :, j] == wg[:, j, None]
    else:
        bits = _normalize_bits(c)
        dd = bits[None, :] == jnp.take(bits, rows)[:, None]
    return jnp.where(v & vg, dd, v == vg)


def group_rows(key_cols: Sequence[Column], live, value_cols=None):
    """-> (order, gid_sorted, boundary_sorted, num_groups).

    order: stable permutation putting equal keys adjacent, dead rows last.
    gid_sorted[i]: group id of sorted position i (garbage for dead rows),
    `cumsum(boundary) - 1`: it rises by one at each group's first row, so
    once the callers move dead rows to cap - 1 it is sorted and
    `_seg_bounds` reads each group's rows off the places it changes.
    `value_cols`: optional minor sort keys — equal values land adjacent
    WITHIN each group (the distinct-aggregate dedup needs this)."""
    from ..utils.packed_sort import stable_argsort
    cap = live.shape[0]
    if not key_cols and not value_cols:
        # one group — but the contract (dead rows LAST) must still hold:
        # merge states interleave live/dead rows, and the segmented
        # reducers read their bounds off gid, sorted after the dead->cap-1
        # remap
        order = stable_argsort([((~live).astype(jnp.uint64), 1)], cap)
        gid = jnp.zeros(cap, dtype=jnp.int32)
        live_s = jnp.take(live, order)
        boundary = jnp.zeros(cap, dtype=jnp.bool_).at[0].set(live_s[0])
        return order, gid, boundary, jnp.minimum(jnp.sum(live), 1)
    h1, h2 = hash_columns_double(key_cols, live) if key_cols else (
        jnp.zeros(cap, jnp.uint64), jnp.zeros(cap, jnp.uint64))
    # stable sort: primary h1, secondary h2, then the value hashes,
    # tertiary original index
    comps = [(h1, 64), (h2, 64)]
    if value_cols:
        comps += [(vh, 64) for vh in hash_columns_double(value_cols, live)]
    order = stable_argsort(comps, cap)
    if not key_cols:
        live_s = jnp.take(live, order)
        gid = jnp.zeros(cap, dtype=jnp.int32)
        boundary = jnp.zeros(cap, dtype=jnp.bool_).at[0].set(live_s[0])
        return order, gid, boundary, jnp.minimum(jnp.sum(live), 1)
    live_s = jnp.take(live, order)
    h1s = jnp.take(h1, order)
    h2s = jnp.take(h2, order)
    differs = (h1s != _shift1(h1s)) | (h2s != _shift1(h2s))
    for c in key_cols:
        cs = c.take(order)
        differs = differs | _col_differs_from_prev(cs)
    boundary = live_s & differs
    boundary = boundary.at[0].set(live_s[0])
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    return order, gid, boundary, num_groups


def _shift1(x):
    """x shifted down by one position (x[i-1]); position 0 gets x[0]."""
    return jnp.roll(x, 1)


def _col_differs_from_prev(c: Column):
    """Row i differs from row i-1 (null-aware, Spark key equality: nulls
    equal, NaN equal, -0.0 == 0.0 — the hash normalizes floats, and direct
    bit compare after the same normalization keeps it consistent)."""
    from ..ops.hashing import _normalize_bits
    vprev = _shift1(c.valid)
    both_null = (~c.valid) & (~vprev)
    valid_mismatch = c.valid != vprev
    if c.dtype.is_string:
        data_diff = jnp.any(c.data != _shift1_rows(c.data), axis=1) \
            | (c.lengths != _shift1(c.lengths))
    else:
        bits = _normalize_bits(c)
        data_diff = bits != _shift1(bits)
    return jnp.where(both_null, False,
                     jnp.where(valid_mismatch, True,
                               jnp.where(c.valid, data_diff, False)))


def _shift1_rows(m):
    return jnp.roll(m, 1, axis=0)


# --------------------------------------------------------------------------
# segment reducers (sorted ids, masked)
# --------------------------------------------------------------------------
#
# The segment bounds come from the sorted ids' own boundaries
# (`_seg_bounds`): a row whose id differs from its neighbour's starts or
# ends a segment, and one scatter each places those rows.  Two binary
# searches over every segment id would be a `while` of dependent gathers
# (on a v5e 4.4 of the 10.15 s TPC-H Q18's sort program took, PERF.md).
# Integer sums, min and max are a scan that RESTARTS at each segment's
# first row (`_seg_scan`), read at the segment's last row: no scatter (a
# scatter-min serializes on the TPU: 73 ms over 1M rows on a v5e where the
# scan and its bounds take 26) and no difference of running prefixes (a
# cumsum and two 64-bit gathers, 62 ms more than the bounds; PERF.md).
# All three are exact in any order: integer sums wrap as per-segment
# accumulation does, modular addition being associative.  FLOAT sums keep the scatter: it adds a segment's
# rows in row order, which a scan's tree does not, and the streaming tier
# answers bit for bit what a batch query would (three partial sums folded
# as (a + b) + c, never a + (b + c)); a difference of running prefixes
# would not even be close (a float segment vanishes under a 1e300-scale
# running total: catastrophic cancellation, not an "order variance" the
# variableFloatAgg conf covers).

def _seg_bounds(gid, cap):
    """-> (start, end): int32 [cap], segment g is rows [start[g], end[g])
    of sorted `gid`; an id no row carries has start == end == 0.

    A row starts a segment where its id differs from the row before's
    (row 0 always) and ends one where it differs from the row after's
    (the last row always); one scatter each puts those rows at their ids,
    every other row goes out of range and is dropped.  Nothing searches
    and nothing loops, and ids past `cap` (fewer segments than rows) are
    dropped the same way."""
    n = gid.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    step = gid[1:] != gid[:-1]
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), step])
    last = jnp.concatenate([step, jnp.ones(1, jnp.bool_)])
    start = jnp.zeros(cap, jnp.int32).at[jnp.where(first, gid, cap)].set(
        rows, mode="drop")
    end = jnp.zeros(cap, jnp.int32).at[jnp.where(last, gid, cap)].set(
        rows + 1, mode="drop")
    return start, end


def _first_rows(bounds, ngroups, cap):
    """Sorted position of each group's first row (a group's rows are all
    live, dead rows sort last); cap - 1 for the slots past `ngroups`."""
    return jnp.where(jnp.arange(cap, dtype=jnp.int32) < ngroups,
                     bounds[0], cap - 1)


def _seg_scan(scans, first):
    """Inclusive scans that restart at every `first` row: `scans` is a
    list of (combine, values), one result each.  Step j combines a row
    with the row 2**j before it unless a segment starts in between, so
    after ceil(log2 n) steps each row holds its segment's reduction up to
    itself (Hillis-Steele); a step is shifts and selects, nothing loops
    on the device and nothing scatters."""
    n = first.shape[0]
    vals = [v for _, v in scans]
    k = 1
    while k < n:
        vals = [jnp.where(first, v, combine(
                    jnp.concatenate([v[:k], v[:-k]]), v))
                for (combine, _), v in zip(scans, vals)]
        first = first | jnp.concatenate([jnp.ones(k, jnp.bool_),
                                         first[:-k]])
        k *= 2
    return vals


def _reduce_identity(op, dtype):
    """What segment_sum/min/max give a segment no row carries."""
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if op == "min" else info.min, dtype)


_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _seg_multi(reqs, gid, cap, bounds=None):
    """All requested segmented reductions over sorted `gid`.

    `reqs`: list of (op, vals, contribute, fill) with op in
    'sum'|'min'|'max' — contribute masks rows out (sum: add 0; min/max:
    compare fill).  Returns one [cap] array per request, what
    segment_sum/min/max give: float sums BY that scatter (row order),
    the rest as restarting scans read at each segment's last row, which
    share one shifted segment-start mask.  `bounds`: `_seg_bounds(gid,
    cap)`, made here where the caller has none to share."""
    n = gid.shape[0]
    start, end = _seg_bounds(gid, cap) if bounds is None else bounds
    last_row = jnp.clip(end - 1, 0, n - 1)
    nonempty = end > start
    results = [None] * len(reqs)
    scans, scanned = [], []
    for i, (op, vals, contribute, fill) in enumerate(reqs):
        if op == "sum":
            fill = jnp.zeros((), vals.dtype)
        v = jnp.where(contribute, vals, fill)
        if op == "sum" and jnp.issubdtype(vals.dtype, jnp.floating):
            results[i] = jax.ops.segment_sum(v, gid, num_segments=cap,
                                             indices_are_sorted=True)
        else:
            scans.append((_COMBINE[op], v))
            scanned.append(i)
    if scans:
        first = jnp.concatenate([jnp.ones(1, jnp.bool_),
                                 gid[1:] != gid[:-1]])
        for i, r in zip(scanned, _seg_scan(scans, first)):
            results[i] = jnp.where(nonempty, jnp.take(r, last_row),
                                   _reduce_identity(reqs[i][0], r.dtype))
    return results


def _seg_sum(vals, gid, contribute, cap, bounds=None):
    return _seg_multi([("sum", vals, contribute, 0)], gid, cap, bounds)[0]


def _seg_min(vals, gid, contribute, cap, fill, bounds=None):
    return _seg_multi([("min", vals, contribute, fill)], gid, cap,
                      bounds)[0]


def _seg_max(vals, gid, contribute, cap, fill, bounds=None):
    return _seg_multi([("max", vals, contribute, fill)], gid, cap,
                      bounds)[0]


class _AggState:
    """Internal state layout per aggregate: list of (field_suffix, dtype)."""

    @staticmethod
    def fields(agg: AggregateExpression):
        f = agg.func
        if f == "Count":
            return [("count", LongType)]
        if f == "Average":
            return [("sum", DoubleType), ("count", LongType)]
        if f == "Sum":
            return [("sum", agg.dtype)]
        if f in ("Min", "Max"):
            return [(f.lower(), agg.child.dtype)]
        if f in ("First", "Last"):
            return [("val", agg.child.dtype), ("pos", LongType)]
        raise NotImplementedError(f)


def _update_one(agg: AggregateExpression, col, gid, live_s, cap, bounds,
                dedup=None):
    """Compute state columns for one aggregate from sorted input values.

    `dedup`: for distinct aggregates, the is-first-occurrence-of-(group,
    value) mask over sorted rows — duplicate values contribute nothing.
    `bounds`: the kernel's `_seg_bounds(gid, cap)`."""
    f = agg.func
    if f == "Count":
        if col is None:  # count(*)
            contribute = live_s
        else:
            contribute = live_s & col.valid
        if agg.distinct and dedup is not None:
            contribute = contribute & dedup
        cnt = _seg_sum(contribute.astype(jnp.int64), gid, live_s, cap,
                       bounds)
        return [Column(cnt, jnp.ones(cap, jnp.bool_), LongType)]
    valid = col.valid
    contribute = live_s & valid
    if f in ("Sum", "Average") and agg.distinct and dedup is not None:
        contribute = contribute & dedup
    if f in ("Sum", "Average"):
        out_t = DoubleType if f == "Average" else agg.dtype
        v = col.data.astype(out_t.jnp_dtype)
        # one fused segmented pass for the value sum AND its count
        s, nvalid = _seg_multi(
            [("sum", v, contribute, 0),
             ("sum", contribute.astype(jnp.int64), live_s, 0)],
            gid, cap, bounds)
        sum_col = Column(s, nvalid > 0, out_t).mask_invalid()
        if f == "Sum":
            return [sum_col]
        return [sum_col, Column(nvalid, jnp.ones(cap, jnp.bool_), LongType)]
    if f in ("Min", "Max"):
        # distinct is a no-op for min/max
        if agg.child.dtype.is_string:
            return [_minmax_string(f, col, gid, contribute, cap, bounds)]
        return [_minmax(f, agg.child.dtype, col.data, gid, contribute, cap,
                        bounds)]
    raise NotImplementedError(f)


def _string_order_keys(col: Column):
    """Order-preserving int64 keys for a string column, most significant
    first: big-endian uint64 words over the padded byte matrix (UTF-8 byte
    order == code-point order) + length tiebreak, sign-bias mapped so int64
    compare equals unsigned compare."""
    cap, L = col.data.shape
    assert L % 8 == 0, L  # bucket_strlen yields power-of-two >= 8
    w = col.data.reshape(cap, L // 8, 8).astype(jnp.uint64)
    shifts = jnp.arange(56, -8, -8, dtype=jnp.uint64)
    words = jnp.sum(w << shifts, axis=2, dtype=jnp.uint64)
    bias = jnp.uint64(1 << 63)
    keys = [(words[:, j] ^ bias).astype(jnp.int64) for j in range(L // 8)]
    keys.append(col.lengths.astype(jnp.int64))
    return keys


def _minmax_string(f, scol: Column, gid, contribute, cap, bounds):
    """Per-group lexicographic min/max of a string column: iterated
    segmented reductions narrow the candidate set one 8-byte word at a
    time, then the winning row's bytes are gathered (the byte-matrix
    segment reduction the round-1 verdict flagged as pending)."""
    keys = _string_order_keys(scol)
    nvalid = _seg_sum(contribute.astype(jnp.int64), gid,
                      jnp.ones_like(contribute), cap, bounds)
    cand = contribute
    gidc = jnp.clip(gid, 0, cap - 1)
    for k in keys:
        if f == "Min":
            best = _seg_min(k, gid, cand, cap, jnp.int64(_I64_MAX),
                            bounds)
        else:
            best = _seg_max(k, gid, cand, cap, jnp.int64(_I64_MIN),
                            bounds)
        cand = cand & (k == jnp.take(best, gidc))
    rowpos = jnp.arange(cap, dtype=jnp.int64)
    win = _seg_min(jnp.where(cand, rowpos, _I64_MAX), gid,
                   jnp.ones_like(cand), cap, jnp.int64(_I64_MAX), bounds)
    widx = jnp.clip(win, 0, cap - 1).astype(jnp.int32)
    out = scol.take(widx)
    return out.with_valid(nvalid > 0).mask_invalid()


def _minmax(f, dtype, vals, gid, contribute, cap, bounds=None):
    ones = jnp.ones_like(contribute)
    if dtype.is_floating:
        v = vals.astype(jnp.float64)
        isnan = jnp.isnan(v)
        # every reduction this aggregate needs, one fused segmented pass
        if f == "Min":
            has_nan_i, nvalid, n_non_nan, r = _seg_multi(
                [("max", (contribute & isnan).astype(jnp.int32), ones,
                  jnp.int32(0)),
                 ("sum", contribute.astype(jnp.int64), ones, 0),
                 ("sum", (contribute & ~isnan).astype(jnp.int32), ones, 0),
                 ("min", jnp.where(isnan, jnp.inf, v), contribute,
                  jnp.float64(np.inf))], gid, cap, bounds)
            # NaN only wins min when the group has NO non-NaN values
            # (min(+inf, NaN) is +inf: NaN is greatest)
            only_nan = (has_nan_i > 0) & (n_non_nan == 0)
            r = jnp.where(only_nan, jnp.nan, r)
        else:
            has_nan_i, nvalid, r = _seg_multi(
                [("max", (contribute & isnan).astype(jnp.int32), ones,
                  jnp.int32(0)),
                 ("sum", contribute.astype(jnp.int64), ones, 0),
                 ("max", jnp.where(isnan, -jnp.inf, v), contribute,
                  jnp.float64(-np.inf))], gid, cap, bounds)
            r = jnp.where(has_nan_i > 0, jnp.nan, r)  # NaN is greatest
        out = r.astype(dtype.jnp_dtype)
        return Column(out, nvalid > 0, dtype).mask_invalid()
    v = vals.astype(jnp.int64)
    if f == "Min":
        nvalid, r = _seg_multi(
            [("sum", contribute.astype(jnp.int64), ones, 0),
             ("min", v, contribute, jnp.int64(_I64_MAX))], gid, cap,
            bounds)
    else:
        nvalid, r = _seg_multi(
            [("sum", contribute.astype(jnp.int64), ones, 0),
             ("max", v, contribute, jnp.int64(_I64_MIN))], gid, cap,
            bounds)
    return Column(r.astype(dtype.jnp_dtype), nvalid > 0, dtype) \
        .mask_invalid()


class TpuHashAggregateExec(TpuExec):
    coalesce_after = True

    def __init__(self, grouping: Sequence[E.Expression],
                 group_names: Sequence[str],
                 aggregates: Sequence[AggregateExpression], child: ExecNode):
        super().__init__(child)
        self.grouping = list(grouping)
        self.group_names = list(group_names)
        self.aggregates = list(aggregates)
        fields = [StructField(n, g.dtype)
                  for n, g in zip(group_names, grouping)]
        fields += [StructField(a.output_name or a.func.lower(), a.dtype)
                   for a in self.aggregates]
        self._schema = Schema(fields)
        self._state_schema = self._make_state_schema()
        if self._distinct_child() is not None:
            # distinct dedup happens inside one update kernel call: partial
            # states are NOT mergeable across batches (the same value may
            # appear in several), so the child must coalesce to one batch
            # (the reference falls back to CPU for these shapes instead;
            # aggregate.scala GpuHashAggregateMeta.tagPlanForGpu)
            self.child_coalesce_goal = "single"

    def _distinct_child(self):
        """The single distinct-aggregate child expression, or None.
        The planner rejects plans with more than one distinct child."""
        for a in self.aggregates:
            if a.distinct and a.func in ("Sum", "Count", "Average") \
                    and a.child is not None:
                return a.child
        return None

    @property
    def schema(self):
        return self._schema

    def describe(self):
        gs = ", ".join(map(repr, self.grouping))
        ags = ", ".join(map(repr, self.aggregates))
        return f"TpuHashAggregateExec[keys=[{gs}] aggs=[{ags}]]"

    def _make_state_schema(self) -> Schema:
        fields = [StructField(f"_k{i}", g.dtype)
                  for i, g in enumerate(self.grouping)]
        for ai, a in enumerate(self.aggregates):
            for suffix, dt in _AggState.fields(a):
                fields.append(StructField(f"_a{ai}_{suffix}", dt))
        return Schema(fields)

    # ---- per-batch kernels (jitted) ---------------------------------------

    def _update_kernel(self, batch: ColumnarBatch) -> ColumnarBatch:
        """input batch -> state batch (update aggregation)."""
        cap = batch.capacity
        keys = [g.eval(batch) for g in self.grouping]
        live = batch.sel
        dchild = self._distinct_child()
        if dchild is not None:
            # sort equal (group, value) pairs adjacent; first occurrence of
            # each pair is the only row a distinct aggregate counts
            dval = dchild.eval(batch)
            order, gid, boundary, ngroups = group_rows(keys, live, [dval])
            dval_s = dval.take(order)
            dedup = boundary | _col_differs_from_prev(dval_s)
            dedup = dedup.at[0].set(True)
        else:
            order, gid, boundary, ngroups = group_rows(keys, live)
            dedup = None
        live_s = jnp.take(live, order)
        gid = jnp.where(live_s, gid, cap - 1)

        # every segmented reduction of this kernel shares one set of bounds
        bounds = _seg_bounds(gid, cap)
        state_cols: List[Column] = []
        # group keys: first row of each group (the boundary rows, compacted)
        first_idx = jnp.take(order, _first_rows(bounds, ngroups, cap))
        for k in keys:
            state_cols.append(k.take(first_idx))
        for a in self.aggregates:
            col = a.child.eval(batch) if a.child is not None else None
            scol = col.take(order) if col is not None else None
            f = a.func
            if f in ("First", "Last"):
                # first/last over live rows INCLUDING null values (Spark
                # ignoreNulls=false default).  Position = rank among LIVE
                # rows in original order (the driver advances the offset by
                # live-row count, so raw indices of non-compacted batches
                # would break cross-batch ordering) + partition row offset.
                rank_orig = jnp.cumsum(live.astype(jnp.int64)) - 1
                pos = jnp.take(rank_orig, order)
                if f == "First":
                    best = _seg_min(pos, gid, live_s, cap,
                                    jnp.int64(_I64_MAX), bounds)
                else:
                    best = _seg_max(pos, gid, live_s, cap, jnp.int64(-1),
                                    bounds)
                # original index of the winning row: sorted position whose
                # pos equals the group's best
                is_best = live_s & (pos == jnp.take(best,
                                                    jnp.clip(gid, 0,
                                                             cap - 1)))
                rowpos = jnp.arange(cap, dtype=jnp.int64)
                win_sorted = _seg_min(jnp.where(is_best, rowpos, _I64_MAX),
                                      gid, live_s, cap, jnp.int64(_I64_MAX),
                                      bounds)
                widx = jnp.take(
                    order, jnp.clip(win_sorted, 0, cap - 1).astype(jnp.int32))
                state_cols.append(col.take(widx))
                gpos = best + E.current_row_offset()
                state_cols.append(Column(gpos, jnp.ones(cap, jnp.bool_),
                                         LongType))
            else:
                state_cols.extend(_update_one(a, scol, gid, live_s, cap,
                                              bounds, dedup=dedup))
        sel = jnp.arange(cap, dtype=jnp.int32) < ngroups
        # zero out dead state rows
        state_cols = [c.with_valid(c.valid & sel).mask_invalid()
                      if not c.dtype.is_string else c for c in state_cols]
        return ColumnarBatch(state_cols, sel, self._state_schema)

    # ---- low-cardinality bucket fast path ---------------------------------

    # groups a batch's bucket state holds
    _BUCKETS = 1024
    # groups one pass of _bucket_update_kernel reduces: the
    # largest of 8, 16, 32 at which a 1M-row pass on a v5e stays under a
    # tenth of what the scatter form it replaced took (7.0 ms against
    # 980 ms, PERF.md PR 26)
    _DENSE_GROUPS = 32

    def _bucketable(self) -> bool:
        """Aggregate set eligible for the bucket fast path: mergeable
        states a masked reduce computes (sum/count/avg, non-string min/max),
        no distinct dedup, no arrival-order state."""
        if not self.grouping:
            return False
        for a in self.aggregates:
            if a.distinct or a.func in ("First", "Last"):
                return False
            if a.func in ("Min", "Max") and a.child.dtype.is_string:
                return False
            if a.func not in ("Count", "Sum", "Average", "Min", "Max"):
                return False
        return True

    @staticmethod
    def _bucket_ids(h1):
        """int32 group id of a row from its 64-bit key hash: the top 31
        bits, so an id is never negative.  The one place that says how wide
        an id is (tests narrow it to force two keys onto one id)."""
        return (h1 >> jnp.uint64(33)).astype(jnp.int32)

    def _bucket_update_kernel(self, batch: ColumnarBatch):
        """-> (took: int32[], state batch at capacity _BUCKETS).

        The sort-free grouped update: every live row carries the 31-bit
        fold `sid` of its keys' h1 hash, and the reductions over the rows
        of one id are a group's partial state IF every id in the batch
        stands for one distinct group.  That is checked EXACTLY per batch
        (`clean`: each live row's key VALUE-equals its id's
        representative's, with Spark key semantics: nulls equal, NaN
        equal, -0.0 == 0.0, string length compared).  Two distinct keys
        share an id once in 2**31 pairs, so a batch is answered here
        whenever it holds at most _BUCKETS distinct groups; one of more,
        or one with such a collision, is dirty and takes the sort path.
        The state has the sort path's schema and capacity _BUCKETS, its
        groups a dense prefix in id order, so the merge/finalize kernels
        take either.

        Nothing is scattered or gathered per row.  A pass takes the next
        _DENSE_GROUPS ids present in the batch in order (successive masked
        minima of `sid`), compares each row against those ids and their
        representatives' keys (a [G, cap] broadcast compare inside its
        fusion) and makes every aggregate a masked reduce along the rows;
        slot g of pass p is state row p * G + g.  A `while_loop` on the
        device runs as many passes as the batch's own groups ask for, at
        most _BUCKETS / G, and stops at the first dirty one.  The ids are
        uniform, so the (G + 1)-th smallest says how many groups the
        batch holds: where the first pass reads more than four times
        _BUCKETS from it (1,024 groups read so less than once in 10**9
        batches) the loop ends there, dirty, and not after every pass
        the state has room for.

        What the chip showed (v5e; PERF.md, PRs 26 and 34): as
        `segment_sum/min/max` XLA lowers the reductions to serial
        scatter-adds of 74-80 ms each, a 1M-row batch of TPC-H Q1 980 ms;
        one pass here takes the same batch in 7.0 ms.  A 786,432-row
        batch of 171 groups over two string keys and an int: six passes,
        11.2 ms, where the sort-based update takes 708 ms; a pass 1.3 ms.

        `took`: -1 dirty, 1 clean in one pass, 0 clean in more: the one
        integer a caller reads where it read `clean`."""
        B, G = self._BUCKETS, self._DENSE_GROUPS
        keys = [g.eval(batch) for g in self.grouping]
        cols = [a.child.eval(batch) if a.child is not None else None
                for a in self.aggregates]
        live = batch.sel
        cap = batch.capacity
        h1, _h2 = hash_columns_double(keys, live)
        # dead rows carry an id no live row can: a live row that folds to
        # it is clamped one below (one more possible collision, which
        # `clean` catches), never dropped
        dead = jnp.int32(np.iinfo(np.int32).max)
        sid = jnp.where(live, jnp.minimum(self._bucket_ids(h1), dead - 1),
                        dead)
        # ids are uniform over 2**31: with n groups the (G + 1)-th
        # smallest sits near (G + 1) * 2**31 / n; below this, n > 4 B
        too_many_below = jnp.int32(((G + 1) << 31) // (4 * B))
        iota = jnp.arange(cap, dtype=jnp.int32)
        agg_fields = self._state_schema.fields[len(keys):]

        def next_id(prev, _):
            nxt = jnp.min(jnp.where(sid > prev, sid, dead))
            return nxt, nxt

        def one_pass(carry):
            prev, _more, passes, clean, occ, rep, state = carry
            # the next G + 1 ids present, in order, `dead` where no more
            # are: slot g stands for the g-th, the last one only says
            # whether another pass has to follow
            _, lowest = jax.lax.scan(next_id, prev, None, length=G + 1)
            slot_id = lowest[:G]
            match = sid[None, :] == slot_id[:, None]          # [G, cap]
            rep_row = jnp.max(jnp.where(match, iota[None, :], 0), axis=1)
            eq = jnp.ones((G, cap), jnp.bool_)
            for k in keys:
                eq &= _key_equal_slots(k, rep_row)
            clean &= jnp.all(jnp.where(match & live[None, :], eq, True))
            # a batch read as too many groups ends the loop as a dirty
            # pass does (only the first pass can read so: later ones see
            # larger ids)
            clean &= lowest[G] >= too_many_below

            def reduce(op, vals, mask, fill):
                reducer = {"sum": jnp.sum, "min": jnp.min,
                           "max": jnp.max}[op]
                return reducer(jnp.where(match & mask[None, :],
                                         vals[None, :], fill), axis=1)

            def count(mask):
                # cap < 2**31: int32 sums, widened after
                return reduce("sum", mask.astype(jnp.int32), live,
                              jnp.int32(0)).astype(jnp.int64)

            def put(into, r):
                # this pass's G slots, after those of the passes before
                return jax.lax.dynamic_update_slice(into, r, (passes * G,))
            slots = self._bucket_states(cols, live, count, reduce)
            state = [(put(d, c.data), put(v, c.valid))
                     for (d, v), c in zip(state, slots)]
            # unused slots hold `dead`: the groups found are a dense prefix
            return (slot_id[G - 1], lowest[G] < dead, passes + 1, clean,
                    put(occ, slot_id < dead), put(rep, rep_row), state)

        def unfinished(carry):
            _prev, more, passes, clean, *_ = carry
            return more & clean & (passes < B // G)

        empty = jnp.zeros(B, jnp.bool_)
        _, more, passes, clean, occ, rep, state = jax.lax.while_loop(
            unfinished, one_pass,
            (jnp.int32(-1), jnp.bool_(True), jnp.int32(0), jnp.bool_(True),
             empty, jnp.zeros(B, jnp.int32),
             [(jnp.zeros(B, f.dtype.jnp_dtype), empty)
              for f in agg_fields]))
        key_state = [k.take(rep) for k in keys]
        agg_state = [Column(d, v, f.dtype)
                     for (d, v), f in zip(state, agg_fields)]
        state_cols = [c.with_valid(c.valid & occ).mask_invalid()
                      if not c.dtype.is_string else c
                      for c in key_state + agg_state]
        # groups left over after the last pass the state has room for are
        # as dirty as a collision
        took = jnp.where(clean & ~more, (passes == 1).astype(jnp.int32),
                         jnp.int32(-1))
        return took, ColumnarBatch(state_cols, occ, self._state_schema)

    def _bucket_states(self, cols, live, count, reduce) -> List[Column]:
        """Aggregate state columns of one pass of _bucket_update_kernel,
        a row a slot, over the pass's two reducers: `count(mask)` ->
        int64 rows of each slot's group under mask, `reduce(op, vals,
        mask, fill)` -> the group's sum/min/max of vals under mask
        (`fill` where none)."""
        state_cols: List[Column] = []
        for a, col in zip(self.aggregates, cols):
            f = a.func
            if f == "Count":
                cnt = count(live if col is None else live & col.valid)
                state_cols.append(Column(cnt, jnp.ones_like(cnt, jnp.bool_),
                                         LongType))
                continue
            contribute = live & col.valid
            nvalid = count(contribute)
            if f in ("Sum", "Average"):
                out_t = DoubleType if f == "Average" else a.dtype
                v = col.data.astype(out_t.jnp_dtype)
                s = reduce("sum", v, contribute,
                           jnp.zeros((), out_t.jnp_dtype))
                state_cols.append(Column(s, nvalid > 0, out_t)
                                  .mask_invalid())
                if f == "Average":
                    state_cols.append(Column(nvalid,
                                             jnp.ones_like(nvalid, jnp.bool_),
                                             LongType))
            else:  # Min / Max (numeric)
                dt = a.child.dtype
                v = col.data
                op = f.lower()
                fill = _type_max(dt) if f == "Min" else _type_min(dt)
                if dt.is_floating:
                    # Spark float ordering: NaN greatest, -0.0 == 0.0
                    # (the sort path's [nan_flag, value] key, as direct
                    # reductions: no f64 bitcasts — unimplemented on the
                    # emulated-f64 TPU backend)
                    isnan = jnp.isnan(v)
                    v = jnp.where(v == 0.0, jnp.zeros((), v.dtype), v)
                    nn_mask = contribute & ~isnan
                    n_nonnan = count(nn_mask)
                    m = reduce(op, v, nn_mask, fill)
                    # min: NaN only for an all-NaN group; max: NaN for
                    # any NaN in the group (NaN greatest)
                    nan_wins = ((nvalid > 0) & (n_nonnan == 0)
                                if f == "Min" else nvalid > n_nonnan)
                    m = jnp.where(nan_wins, jnp.asarray(jnp.nan, v.dtype),
                                  m)
                else:
                    m = reduce(op, v, contribute, fill)
                state_cols.append(Column(m, nvalid > 0, dt)
                                  .mask_invalid())
        return state_cols

    def _fold_program(self, cap: int):
        """`jit_agg.fold` at merge capacity `cap`: the streaming loop's
        partial states (a list, live rows a prefix in each) concatenated
        in order and merged in one launch, the merge's input the rows
        `concat_batches` would give it at that capacity."""
        from ..utils.kernel_cache import cached_kernel
        merge = self._merge_kernel
        return cached_kernel(
            ("fold", self.kernel_key(), cap),
            lambda: lambda parts: merge(_concat_prefixes(parts, cap)))

    def _merge_kernel(self, state: ColumnarBatch) -> ColumnarBatch:
        """state batch (concat of partials) -> merged state batch."""
        cap = state.capacity
        nkeys = len(self.grouping)
        keys = list(state.columns[:nkeys])
        live = state.sel
        order, gid, boundary, ngroups = group_rows(keys, live)
        live_s = jnp.take(live, order)
        gid = jnp.where(live_s, gid, cap - 1)
        bounds = _seg_bounds(gid, cap)
        out_cols: List[Column] = []
        first_idx = jnp.take(order, _first_rows(bounds, ngroups, cap))
        for k in keys:
            out_cols.append(k.take(first_idx))
        ci = nkeys
        for a in self.aggregates:
            f = a.func
            nfields = len(_AggState.fields(a))
            cols = state.columns[ci:ci + nfields]
            ci += nfields
            if f == "Count":
                scol = cols[0].take(order)
                s = _seg_sum(scol.data, gid, live_s & scol.valid, cap,
                             bounds)
                out_cols.append(Column(s, jnp.ones(cap, jnp.bool_),
                                       LongType))
            elif f == "Sum":
                scol = cols[0].take(order)
                contribute = live_s & scol.valid
                s, nvalid = _seg_multi(
                    [("sum", scol.data, contribute, 0),
                     ("sum", contribute.astype(jnp.int64), live_s, 0)],
                    gid, cap, bounds)
                out_cols.append(Column(s, nvalid > 0, cols[0].dtype)
                                .mask_invalid())
            elif f == "Average":
                scol = cols[0].take(order)
                ccol = cols[1].take(order)
                contribute = live_s & scol.valid
                # ccol holds per-partial COUNTS (not 0/1 flags): their
                # sum is unbounded, so no int32 is_count narrowing
                s, n = _seg_multi(
                    [("sum", scol.data, contribute, 0),
                     ("sum", ccol.data, live_s & ccol.valid, 0)],
                    gid, cap, bounds)
                out_cols.append(Column(s, n > 0, DoubleType).mask_invalid())
                out_cols.append(Column(n, jnp.ones(cap, jnp.bool_),
                                       LongType))
            elif f in ("Min", "Max"):
                scol = cols[0].take(order)
                contribute = live_s & scol.valid
                if scol.dtype.is_string:
                    out_cols.append(_minmax_string(f, scol, gid, contribute,
                                                   cap, bounds))
                else:
                    out_cols.append(_minmax(f, scol.dtype, scol.data, gid,
                                            contribute, cap, bounds))
            elif f in ("First", "Last"):
                vcol = cols[0].take(order)
                pcol = cols[1].take(order)
                if f == "First":
                    best = _seg_min(pcol.data, gid, live_s, cap,
                                    jnp.int64(_I64_MAX), bounds)
                else:
                    best = _seg_max(pcol.data, gid, live_s, cap,
                                    jnp.int64(-1), bounds)
                is_best = live_s & (pcol.data == jnp.take(best, gid))
                # position of the winning row in sorted order
                rowpos = jnp.arange(cap, dtype=jnp.int64)
                win = _seg_min(jnp.where(is_best, rowpos, _I64_MAX), gid,
                               live_s, cap, jnp.int64(_I64_MAX), bounds)
                widx = jnp.clip(win, 0, cap - 1).astype(jnp.int32)
                out_cols.append(vcol.take(widx))
                out_cols.append(Column(best, jnp.ones(cap, jnp.bool_),
                                       LongType))
            else:
                raise NotImplementedError(f)
        sel = jnp.arange(cap, dtype=jnp.int32) < ngroups
        out_cols = [c.with_valid(c.valid & sel).mask_invalid()
                    if not c.dtype.is_string else c for c in out_cols]
        return ColumnarBatch(out_cols, sel, self._state_schema)

    def _finalize_kernel(self, state: ColumnarBatch) -> ColumnarBatch:
        nkeys = len(self.grouping)
        out_cols = list(state.columns[:nkeys])
        ci = nkeys
        for a in self.aggregates:
            nfields = len(_AggState.fields(a))
            cols = state.columns[ci:ci + nfields]
            ci += nfields
            if a.func == "Average":
                s, n = cols[0], cols[1]
                nz = n.data > 0
                avg = s.data / jnp.where(nz, n.data, 1).astype(jnp.float64)
                out_cols.append(Column(avg, s.valid & nz, DoubleType)
                                .mask_invalid())
            elif a.func in ("First", "Last"):
                out_cols.append(cols[0])
            else:
                c = cols[0]
                if c.dtype is not a.dtype and not c.dtype.is_string:
                    c = Column(c.data.astype(a.dtype.jnp_dtype), c.valid,
                               a.dtype)
                out_cols.append(c)
        return ColumnarBatch(out_cols, state.sel, self._schema)

    # ---- ungrouped fast path ----------------------------------------------

    def _global_kernel(self, batch: ColumnarBatch) -> ColumnarBatch:
        """No grouping keys: masked whole-batch reductions to a 1-row state."""
        live = batch.sel
        cap = 8  # tiny static output
        cols: List[Column] = []
        dchild = self._distinct_child()
        first_occ = None
        if dchild is not None:
            # value-sorted first-occurrence mask over the whole batch
            dval = dchild.eval(batch)
            dorder, _g, _b, _n = group_rows([], live, value_cols=[dval])
            dval_s = dval.take(dorder)
            occ_sorted = _col_differs_from_prev(dval_s).at[0].set(True)
            first_occ = jnp.zeros(batch.capacity, jnp.bool_
                                  ).at[dorder].set(occ_sorted)
        for a in self.aggregates:
            col = a.child.eval(batch) if a.child is not None else None
            f = a.func
            distinct = (a.distinct and first_occ is not None
                        and f in ("Sum", "Count", "Average"))
            if f == "Count":
                contribute = live if col is None else live & col.valid
                if distinct:
                    contribute = contribute & first_occ
                v = jnp.sum(contribute.astype(jnp.int64))
                cols.append(_scalar_col(v, True, LongType, cap))
                continue
            contribute = live & col.valid
            if distinct:
                contribute = contribute & first_occ
            nvalid = jnp.sum(contribute.astype(jnp.int64))
            if f in ("Min", "Max") and col.dtype.is_string:
                keys = _string_order_keys(col)
                cand = contribute
                for k in keys:
                    if f == "Min":
                        best = jnp.min(jnp.where(cand, k, _I64_MAX))
                    else:
                        best = jnp.max(jnp.where(cand, k, _I64_MIN))
                    cand = cand & (k == best)
                rowpos = jnp.arange(batch.capacity, dtype=jnp.int64)
                win = jnp.min(jnp.where(cand, rowpos, _I64_MAX))
                widx = jnp.clip(win, 0, batch.capacity - 1).astype(jnp.int32)
                taken = col.take(jnp.full((cap,), widx, dtype=jnp.int32))
                row0 = jnp.arange(cap, dtype=jnp.int32) < 1
                cols.append(taken.with_valid(row0 & (nvalid > 0))
                            .mask_invalid())
                continue
            if f in ("Sum", "Average"):
                out_t = DoubleType if f == "Average" else a.dtype
                v = jnp.sum(jnp.where(contribute,
                                      col.data.astype(out_t.jnp_dtype),
                                      jnp.zeros((), out_t.jnp_dtype)))
                cols.append(_scalar_col(v, nvalid > 0, out_t, cap))
                if f == "Average":
                    cols.append(_scalar_col(nvalid, True, LongType, cap))
            elif f in ("Min", "Max"):
                mm = _minmax(f, col.dtype, col.data,
                             jnp.zeros(batch.capacity, jnp.int32),
                             contribute, 1)
                cols.append(_scalar_col(mm.data[0], mm.valid[0], col.dtype,
                                        cap))
            elif f in ("First", "Last"):
                pos = jnp.arange(batch.capacity, dtype=jnp.int64)
                if f == "First":
                    raw = jnp.min(jnp.where(live, pos, _I64_MAX))
                else:
                    raw = jnp.max(jnp.where(live, pos, -1))
                idx = jnp.clip(raw, 0, batch.capacity - 1).astype(jnp.int32)
                # rank among live rows, for cross-batch ordering
                rank = jnp.cumsum(live.astype(jnp.int64)) - 1
                best = rank[idx]
                # strings need a take-based path (no scalar buffer dtype)
                taken = col.take(jnp.full((cap,), idx, dtype=jnp.int32))
                row0 = jnp.arange(cap, dtype=jnp.int32) < 1
                cols.append(taken.with_valid(taken.valid & row0))
                cols.append(_scalar_col(best + E.current_row_offset(), True,
                                        LongType, cap))
            else:
                raise NotImplementedError(f)
        sel = jnp.arange(cap, dtype=jnp.int32) < 1
        return ColumnarBatch(cols, sel, self._state_schema)

    # ---- driver -----------------------------------------------------------

    def _needs_offset(self) -> bool:
        if any(a.func in ("First", "Last") for a in self.aggregates):
            return True
        exprs = list(self.grouping)
        exprs += [a.child for a in self.aggregates if a.child is not None]
        return any(E.tree_needs_row_offset(e) for e in exprs)

    def kernel_key(self) -> tuple:
        from ..utils.kernel_cache import expr_key, schema_key
        return ("TpuHashAggregateExec",
                tuple(expr_key(g) for g in self.grouping),
                tuple(self.group_names),
                tuple(expr_key(a) for a in self.aggregates),
                tuple(a.output_name for a in self.aggregates),
                schema_key(self._schema))

    def _absorbed_child(self):
        """(source, pre_builder, pre_params, pre_key): what a program of
        this aggregate that absorbs its row-local child runs before the
        update and over which node's batches; a child that is not
        row-local is itself the source and nothing is absorbed.  None
        where the child cannot be absorbed."""
        from .basic import RowLocalExec
        child = self.children[0]
        if not isinstance(child, RowLocalExec):
            return child, None, [], ()
        if child._needs_row_offset() or child._needs_input_file():
            # the fused stage threads a per-batch row offset
            # (monotonically_increasing_id / rand); absorbing it with
            # offset 0 would silently repeat per-batch streams.
            # input_file_name() likewise bakes a per-FILE constant that
            # one program cannot vary across batches
            return None
        pre_params = child.stage_params()
        if pre_params:
            # plan-cache parameters in the absorbed chain: value-free
            # pre-key + the bound values as a leading traced argument
            # of the absorbing program, so literal-variant
            # re-submissions replay this compiled program
            from ..utils.kernel_cache import param_free_keys
            with param_free_keys():
                pre_key = child.kernel_key()
            pre_key += ("params", E.parameter_signature(pre_params))
        else:
            pre_key = child.kernel_key()
        return child.children[0], child.batch_fn, pre_params, pre_key

    # ---- whole-stage path --------------------------------------------------

    def _try_whole_stage(self, ctx: ExecContext):
        """Scan -> row-local -> aggregate as ONE compiled dispatch (the TPU
        analogue of Spark's whole-stage codegen): equal-capacity input
        batches stack on a leading axis, the per-batch pre+update work is
        vmapped, partials merge and finalize inside the same program.  On a
        host link with millisecond round trips (PCIe) this collapses
        O(batches) kernel dispatches + host syncs into one.

        Returns the result batch, or None when the stage shape doesn't
        qualify (caller falls back to the streaming loop)."""
        from .. import config as C
        from ..utils.kernel_cache import cached_kernel
        # FUSION_ENABLED is the master whole-stage kill switch (plan/
        # fusion.py); WHOLE_STAGE_ENABLED remains the aggregate-specific
        # knob for this absorption path
        if not ctx.conf.get(C.WHOLE_STAGE_ENABLED) \
                or not ctx.conf.get(C.FUSION_ENABLED) \
                or self._needs_offset():
            return None, None
        absorbed = self._absorbed_child()
        if absorbed is None:
            return None, None
        source, pre_builder, pre_params, pre_key = absorbed
        # drain INCREMENTALLY: eligibility (leaf shapes, byte budget) is
        # checked per batch so an over-budget input bails to the streaming
        # loop with the tail still unconsumed — the probe must never pin a
        # bigger working set than whole-stage itself would use
        src_iter = iter(source.execute(ctx))
        batches: list = []
        shape0 = None
        cap = 0
        byte_budget = ctx.conf.get(C.BATCH_SIZE_BYTES) // 2
        total_bytes = 0
        from ..serve.lifecycle import ctx_checkpoint
        for b in src_iter:
            # stage-boundary lifecycle checkpoint: the probe drain is
            # the last per-batch loop before the fused agg becomes ONE
            # device dispatch, so this is the agg's cancel/suspend point
            ctx_checkpoint(ctx, allow_suspend=True)
            shapes = [tuple(x.shape) for x in
                      jax.tree_util.tree_flatten(b)[0]]
            total_bytes += b.device_size_bytes()
            if shape0 is None:
                cap = b.capacity
                shape0 = shapes
            batches.append(b)
            reason = ("shapes" if shapes != shape0 else
                      "names" if b.schema.names != batches[0].schema.names
                      else "bytes" if total_bytes > byte_budget else None)
            if reason is not None:
                # a zero-length mark: why this input takes the streaming
                # loop, and after how many batches the probe knew
                with named_range("agg_whole_stage_bail", reason=reason,
                                 batches=len(batches)):
                    pass
                return None, (source, batches, src_iter)
        if not batches:
            return None, (source, batches, src_iter)
        k = len(batches)
        if pre_builder is not None:
            # the absorbed row-local child runs inside this aggregate's
            # program: its per-batch counters (an Expand's fan-out) are
            # added here, on its own plan node
            for b in batches:
                self.children[0].count_input(b.capacity)
        grouped = bool(self.grouping)
        update = self._update_kernel if grouped else self._global_kernel
        merge = self._merge_kernel
        finalize = self._finalize_kernel
        state_schema = self._state_schema

        flat0, treedef = jax.tree_util.tree_flatten(batches[0])
        flats = [jax.tree_util.tree_flatten(b)[0] for b in batches]
        nleaf = len(flat0)

        def _unrolled(leaves, one):
            # per-batch UNROLLED inside the compiled program: each batch's
            # pre+update chain fuses with its own input params, and only
            # the small per-batch STATES stack for the merge.  (Earlier
            # versions stacked the full inputs — first eagerly, then
            # in-jit — which materialized a whole-input concatenate before
            # any real work; for a 192MB q6 scan that copy was ~0.5s.)
            partial_list = []
            for j in range(k):
                b = jax.tree_util.tree_unflatten(
                    treedef, leaves[j * nleaf:(j + 1) * nleaf])
                partial_list.append(one(b))
            return jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *partial_list)

        pvals = E.parameter_values(pre_params) if pre_params else None

        def build():
            def whole(*leaves):
                pre = pre_builder() if pre_builder is not None else None

                def one(b):
                    if pre is not None:
                        b = pre(b)
                    return update(b)
                partials = _unrolled(leaves, one)   # leaves [k, pcap, ...]
                both = _flatten_stacked(partials, state_schema)
                return finalize(merge(both))
            return _thread_params(whole, pre_params)

        def build_bucket():
            bupdate = self._bucket_update_kernel

            def whole_bucket(*leaves):
                pre = pre_builder() if pre_builder is not None else None

                def one(b):
                    if pre is not None:
                        b = pre(b)
                    return bupdate(b)
                took, partials = _unrolled(leaves, one)
                both = _flatten_stacked(partials, state_schema)
                # one integer for the host's one read: the batches done
                # in one dense pass, or -1 if any was dirty
                n_dense = jnp.where(jnp.all(took >= 0), jnp.sum(took), -1)
                return n_dense, finalize(merge(both))
            return _thread_params(whole_bucket, pre_params)

        # treedef in the key: the per-batch structure is baked into the
        # compiled closure (tree_unflatten over bare leaves), so two
        # stages with equal agg shape but different batch layouts must
        # not share a cache entry
        key = (("whole_stage", k, cap, pre_key, str(treedef))
               + self.kernel_key())
        all_leaves = [leaf for f in flats for leaf in f]
        # buffer donation for the FINAL whole-stage program (never the
        # bucket probe — a dirty probe re-dispatches the same leaves):
        # the drained source batches are dead after this one dispatch
        # when the fusion-pass whitelist admits the source and no batch
        # is pinned; leaf ids must also be globally unique (a buffer
        # appearing twice cannot be donated once and read once)
        donate_leaf_argnums: tuple = ()
        from .. import config as _C
        if bool(ctx.conf.get(_C.DONATION_ENABLED)):
            from ..mem import donation as _donation
            from ..plan.fusion import source_donatable
            if source_donatable(source) \
                    and all(_donation.donatable(b) for b in batches):
                ids = [id(x) for x in all_leaves]
                if len(set(ids)) == len(ids):
                    base = 1 if pre_params else 0
                    donate_leaf_argnums = tuple(
                        base + i for i in range(len(all_leaves)))
        if grouped and self._bucketable() \
                and ctx.conf.get(C.AGG_BUCKET_GROUPS) \
                and key not in _BUCKET_DIRTY_KEYS:
            # sort-free program first: per-batch bucket states + an exact
            # all-clean check; only the k*_BUCKETS-row merge sorts.  A
            # dirty batch (more than _BUCKETS groups / two keys of one
            # id) falls through to the sort-based program below and
            # latches the key dirty so later executions skip the probe.
            fnb = cached_kernel(key + ("bucket",), build_bucket)
            with named_range("agg_whole_stage_bucket", self.metrics,
                             MN.COMPUTE_AGG_TIME):
                from ..utils.kernel_cache import record_dispatch
                record_dispatch()
                n_dense, out = (fnb(pvals, *all_leaves) if pre_params
                                else fnb(*all_leaves))
            n_dense = int(n_dense)  # host sync: dirty, or one-pass batches
            self.metrics.add(MN.AGG_HOST_SYNCS, 1)
            if n_dense >= 0:
                self.metrics.add(MN.AGG_BUCKET_BATCHES, k)
                self.metrics.add(MN.AGG_DENSE_BATCHES, n_dense)
                self.metrics.add(MN.NUM_FUSED_STAGES, 1)
                record_output_batch(self.metrics, out, ctx.runtime)
                return out, None
            _BUCKET_DIRTY_KEYS.add(key)
        fn = cached_kernel(key, build,
                           **({"donate_argnums": donate_leaf_argnums}
                              if donate_leaf_argnums else {}))
        with named_range("agg_whole_stage", self.metrics,
                         MN.COMPUTE_AGG_TIME):
            from ..utils.kernel_cache import record_dispatch
            record_dispatch()
            if donate_leaf_argnums:
                from ..mem import donation as _donation
                _donation.record_donated_dispatch(
                    len(donate_leaf_argnums), self.metrics)
            out = fn(pvals, *all_leaves) if pre_params else fn(*all_leaves)
        if grouped:
            self.metrics.add(MN.AGG_SORT_PATH_BATCHES, k)
        self.metrics.add(MN.NUM_FUSED_STAGES, 1)
        record_output_batch(self.metrics, out, ctx.runtime)
        return out, None

    def _live_rows_host(self, batch) -> int:
        """`batch.num_rows_host()`, counted in aggHostSyncs where it has
        to read the device for it."""
        if batch.known_rows is None:
            self.metrics.add(MN.AGG_HOST_SYNCS, 1)
        return batch.num_rows_host()

    def _cpu_twin(self):
        """CPU re-execution plan for OOM fallback (exec/retryable.py):
        the CPU aggregate over the device child bridged through D2H."""
        from .basic import DeviceToHostExec
        from .cpu_relational import CpuHashAggregateExec
        return CpuHashAggregateExec(self.grouping, self.group_names,
                                    self.aggregates,
                                    DeviceToHostExec(self.children[0]))

    def execute(self, ctx: ExecContext):
        from .retryable import execute_with_cpu_fallback
        yield from execute_with_cpu_fallback(
            self, ctx, self._execute_device(ctx), self._cpu_twin)

    def _execute_device(self, ctx: ExecContext):
        from ..utils.kernel_cache import cached_kernel
        from .. import config as C
        whole, materialized = self._try_whole_stage(ctx)
        if whole is not None:
            yield whole
            return
        if not self.grouping:
            yield self._stream_keyless(ctx, materialized)
            return
        base_update = self._update_kernel
        needs_off = self._needs_offset()
        key = self.kernel_key()
        if needs_off:
            update = cached_kernel(
                key + ("update_off",),
                lambda: lambda b, off: E.eval_with_row_offset(
                    base_update, b, off))
        else:
            update = cached_kernel(key + ("update",), lambda: base_update)
        finalize = cached_kernel(key + ("finalize",),
                                 lambda: self._finalize_kernel)
        part_rows = cached_kernel(("part_rows",), lambda: _part_rows)
        # Deferred merging: buffer per-batch partials and merge FAN_IN at a
        # time, so the expensive sort-based merge kernel and the host's
        # row-count read run once per FAN_IN input batches instead of once
        # per batch.  Merge aggregates are associative, and order-sensitive
        # ones (First/Last) carry explicit row-offset tiebreak columns in
        # the partial state, so K-way concat-then-merge equals the pairwise
        # fold.
        from ..config import AGG_MERGE_FAN_IN
        fan_in = max(2, ctx.conf.get(AGG_MERGE_FAN_IN))

        from .retryable import run_retryable

        def fold(state, pending):
            parts = ([state] if state is not None else []) + pending
            if len(parts) == 1:
                return parts[0]
            parts = _normalize_devices(parts)

            def attempt_merge(_):
                # merge allocates the K-way concat: reserve it so the
                # spill cascade (and the fault injector) see the boundary
                if ctx.runtime is not None:
                    ctx.runtime.reserve(
                        sum(p.device_size_bytes() for p in parts),
                        site="agg.merge")
                # every part's live rows are a prefix: the parts' counts,
                # in one read, size the merge as concat_batches would
                counts = jax.device_get(part_rows(parts))  # tpulint: disable=TPU001 the fold's one read: the merge's capacity is the bucket of the parts' live rows
                total = sum(counts.tolist())
                self.metrics.add(MN.AGG_HOST_SYNCS, 1)
                fused = self._fold_program(bucket_rows(max(total, 1)))
                with self.metrics.timer(MN.SEG_AGG_TIME), \
                        named_range("agg_merge", self.metrics,
                                    MN.MERGE_AGG_TIME):
                    merged = fused(parts)
                self.metrics.add(MN.AGG_FUSED_FOLDS, 1)
                return merged
            # retry-only: partial states are merge inputs, not splittable
            # row ranges (splitting them would change nothing — the merge
            # concat is the allocation)
            with named_range("agg_fold", parts=len(parts)):
                return run_retryable(ctx, self.metrics, "aggMerge",
                                     attempt_merge, [None])[0]

        # if the whole-stage probe already drained the source, stream the
        # materialized batches through the child's per-batch kernel instead
        # of re-executing the scan (it would double I/O and decode work)
        if materialized is not None:
            import itertools
            from .basic import RowLocalExec
            src_exec, src_batches, src_rest = materialized
            upstream = itertools.chain(src_batches, src_rest)
            child = self.children[0]
            if isinstance(child, RowLocalExec) \
                    and src_exec is child.children[0]:
                # parameter-threaded like RowLocalExec.execute's plain
                # path, so the replay shares the same compiled kernel
                child_fn = child.parameterized_kernel()

                def replay(b):
                    child.count_input(b.capacity)
                    return child_fn(b)
                input_iter = (replay(b) for b in upstream)
            else:
                input_iter = upstream
        else:
            input_iter = self.children[0].execute(ctx)

        bucket_fn = None
        if self._bucketable() and not needs_off \
                and ctx.conf.get(C.AGG_BUCKET_GROUPS) \
                and key not in _BUCKET_DIRTY_KEYS:
            # needs_off excluded: the bucket kernel evaluates expressions
            # outside eval_with_row_offset, so a row-offset expression
            # would silently restart at 0 every batch
            bucket_fn = cached_kernel(key + ("bucket",),
                                      lambda: self._bucket_update_kernel)
        state = None
        pending: list = []
        hot = {"bucket_fn": bucket_fn, "offset": 0}
        from ..mem.retry import split_batch_rows
        # distinct dedup happens inside ONE update call (partial states
        # are not mergeable across batches) — a row-range split would
        # double-count values straddling the halves, so distinct shapes
        # are retry-only (exhaustion -> CPU fallback)
        update_split = (None if self._distinct_child() is not None
                        else split_batch_rows)

        def attempt_update(b):
            """Retryable per-batch update: reserve the partial-state
            footprint, then run the bucket probe / sort-based update.  A
            split input re-enters here piece by piece IN ORDER, so the
            row offset (First/Last tiebreaks) advances exactly as the
            unsplit batch would have."""
            if ctx.runtime is not None:
                ctx.runtime.reserve(b.device_size_bytes(),
                                    site="agg.update")
            partial = None
            with self.metrics.timer(MN.SEG_AGG_TIME):
                bfn = hot["bucket_fn"]
                if bfn is not None:
                    took, bstate = bfn(b)
                    took = int(took)  # host sync: pick the sort-free state
                    self.metrics.add(MN.AGG_HOST_SYNCS, 1)
                    if took >= 0:
                        self.metrics.add(MN.AGG_BUCKET_BATCHES, 1)
                        self.metrics.add(MN.AGG_DENSE_BATCHES, took)
                        partial = bstate
                    else:
                        # dirty latch: a high-cardinality shape stays
                        # dirty — stop probing it (this query AND this
                        # kernel key process-wide)
                        hot["bucket_fn"] = None
                        _BUCKET_DIRTY_KEYS.add(key)
                if partial is None:
                    self.metrics.add(MN.AGG_SORT_PATH_BATCHES, 1)
                    partial = update(b, jnp.int64(hot["offset"])) \
                        if needs_off else update(b)
            if needs_off:
                hot["offset"] += self._live_rows_host(b)
            return partial

        # present and 0 where the bucket update takes every batch
        self.metrics.add(MN.AGG_SORT_PATH_BATCHES, 0)
        from ..serve.lifecycle import ctx_checkpoint
        for batch in input_iter:
            # stage-boundary lifecycle checkpoint (serve/lifecycle.py):
            # between per-batch updates no reservation is mid-flight —
            # partial states are spillable like any owned buffers, so a
            # preemption suspend here parks and resumes bit-for-bit
            ctx_checkpoint(ctx, allow_suspend=True)
            # the GROUPED update kernel sorts at batch CAPACITY: a
            # selective upstream filter leaves mostly-dead batches, so
            # shrink first (capacity check is static: dense small batches
            # skip the num_rows_host device sync entirely).  A keyless
            # aggregate never comes here: its update is masked reductions,
            # linear in capacity (_stream_keyless)
            if batch.capacity >= 8192:
                with named_range("agg_shrink"):
                    batch = batch.maybe_shrink(self._live_rows_host(batch))
            self.metrics.add(MN.AGG_STREAMED_BATCHES, 1)
            with named_range("agg_update", self.metrics, MN.COMPUTE_AGG_TIME):
                partials = run_retryable(ctx, self.metrics, "aggUpdate",
                                         attempt_update, [batch],
                                         split=update_split)
            pending.extend(partials)
            if len(pending) >= fan_in:
                state = fold(state, pending)
                pending = []
        if pending:
            state = fold(state, pending)
        if state is None:
            return
        out = finalize(state)
        record_output_batch(self.metrics, out, ctx.runtime)
        yield out

    def _stream_keyless(self, ctx: ExecContext, materialized):
        """The streaming loop of an aggregate with no grouping keys: ONE
        program per input batch, `carry' = merge(carry, update(pre(batch)))`
        over a 1-row running state that stays on the device, and no host
        read before the output is collected.  `_global_kernel` is masked
        reductions, linear in capacity, so nothing is gained by shrinking
        a mostly-dead batch first, and the states are 1 row each, so
        nothing by folding them `mergeFanIn` at a time: the grouped loop's
        live-row read, shrink and concat cost this shape 250 times its
        device work (PERF.md, PR 28).

        The carry is `(state,)`, or `(state, row offset)` where First/Last
        or a row-offset expression needs the live rows before a batch:
        the offset advances on the device by each batch's live rows."""
        import itertools
        from ..mem.retry import split_batch_rows
        from ..serve.lifecycle import ctx_checkpoint
        from ..utils.kernel_cache import cached_kernel, record_dispatch
        from .retryable import run_retryable
        needs_off = self._needs_offset()
        if materialized is not None:
            # the whole-stage probe drained (part of) the source and bailed:
            # the step absorbs the row-local child as that program would
            # have, so filter, projection and reduction are one launch
            source, pre_builder, pre_params, pre_key = self._absorbed_child()
            _, drained, rest = materialized
            input_iter = itertools.chain(drained, rest)
        else:
            source, pre_builder, pre_params, pre_key = (
                self.children[0], None, [], ())
            input_iter = source.execute(ctx)
        update, merge = self._global_kernel, self._merge_kernel
        state_schema = self._state_schema
        cap = 8  # _global_kernel's state capacity

        def build_init():
            def init():
                dead = ColumnarBatch(
                    [Column.all_null(f.dtype, cap) for f in state_schema],
                    jnp.zeros(cap, jnp.bool_), state_schema)
                return (dead, jnp.int64(0)) if needs_off else (dead,)
            return init

        def build_step():
            pre = pre_builder() if pre_builder is not None else None

            def step(carry, batch):
                if pre is not None:
                    batch = pre(batch)
                if needs_off:
                    state, off = carry
                    partial = E.eval_with_row_offset(update, batch, off)
                    off = off + jnp.sum(batch.sel.astype(jnp.int64))
                else:
                    (state,), partial = carry, update(batch)
                both = _flatten_stacked(_stack_states([state, partial]),
                                        state_schema)
                state = _head_rows(merge(both), cap)
                return (state, off) if needs_off else (state,)
            return _thread_params(step, pre_params)

        key = self.kernel_key()
        pvals = (E.parameter_values(pre_params),) if pre_params else ()
        # the carry is donated to the step (never the batch: the scan
        # cache's leaves are pinned): it is this loop's own, made by init
        # or by the step before and referenced by nothing else, and a
        # launch on a v5e costs 0.13 ms of host time per output buffer it
        # has to allocate, 0.24 of a step's 0.48 ms (PERF.md, PR 28)
        from .. import config as C
        donate = ({"donate_argnums": (len(pvals),)}
                  if ctx.conf.get(C.DONATION_ENABLED) else {})
        init = cached_kernel(("stream_init", needs_off) + key, build_init)
        step = cached_kernel(("stream_step", needs_off, pre_key) + key,
                             build_step, **donate)
        finalize = cached_kernel(key + ("finalize",),
                                 lambda: self._finalize_kernel)
        hot = {"carry": init()}
        # distinct dedup happens inside ONE update call: a row-range split
        # would double-count values straddling the halves (retry-only)
        update_split = (None if self._distinct_child() is not None
                        else split_batch_rows)

        def attempt_step(b):
            """Retryable: only the reservation raises what the retry
            ladder catches, and it does before the step is issued (and
            the carry donated), so a retried batch and the pieces of a
            split one, IN ORDER, merge once each."""
            if ctx.runtime is not None:
                ctx.runtime.reserve(b.device_size_bytes(), site="agg.update")
            record_dispatch()
            hot["carry"] = step(*pvals, hot["carry"], b)

        def run(b):
            with named_range("agg_update", self.metrics, MN.COMPUTE_AGG_TIME):
                run_retryable(ctx, self.metrics, "aggUpdate", attempt_step,
                              [b], split=update_split)

        # present and 0: this loop reads nothing back
        self.metrics.add(MN.AGG_HOST_SYNCS, 0)
        streamed = 0
        for batch in input_iter:
            # stage-boundary lifecycle checkpoint (serve/lifecycle.py), as
            # in the grouped loop: no reservation is mid-flight here
            ctx_checkpoint(ctx, allow_suspend=True)
            self.metrics.add(MN.AGG_STREAMED_BATCHES, 1)
            self.metrics.add(MN.AGG_SYNC_FREE_BATCHES, 1)
            if pre_builder is not None:
                self.children[0].count_input(batch.capacity)
            run(batch)
            streamed += 1
        if not streamed:
            # global agg over empty input still yields one row: the step
            # over an all-dead batch of its input's schema
            data = {f.name: [] for f in source.schema}
            run(ColumnarBatch.from_pydict(data, source.schema))
        out = finalize(hot["carry"][0])
        record_output_batch(self.metrics, out, ctx.runtime)
        return out


def _scalar_col(value, valid, dtype, cap):
    data = jnp.zeros(cap, dtype=dtype.jnp_dtype).at[0].set(
        value.astype(dtype.jnp_dtype) if hasattr(value, "astype") else value)
    v = jnp.zeros(cap, dtype=jnp.bool_).at[0].set(valid)
    return Column(data, v, dtype).mask_invalid()
