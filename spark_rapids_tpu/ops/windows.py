"""Window functions: resolution + device kernels.

TPU-native analogue of GpuWindowExpression / GpuWindowExec
(rapids/GpuWindowExpression.scala:87-233 — window specs mapped to device
rolling aggregations, row-based frames, row_number; GpuWindowExec.scala:92+).
Where cuDF evaluates each window spec with a rolling-window kernel, the TPU
implementation sorts ONCE by (partition keys, order keys) and computes every
function with segmented scans / prefix sums over the sorted batch — one XLA
program, no per-row loops:

  * segment boundaries      = neighbour inequality on partition keys
  * row_number/rank/dense   = iota arithmetic on segment/peer starts
  * sum/count/avg any frame = prefix sums + clamped frame-bound gathers
  * min/max unbounded side  = segmented associative scans
  * min/max bounded frames  = static stack of shifted gathers (width-capped)
  * lag/lead                = shifted gathers fenced at segment bounds
  * default frame w/ order  = RANGE UNBOUNDED PRECEDING..CURRENT ROW, i.e.
    the frame end is the last PEER row (Spark default-frame tie semantics)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, ColumnarBatch
from ..types import (DataType, DoubleType, IntegerType, LongType, Schema,
                     StructField)
from . import expressions as E

UNBOUNDED = 1 << 62
MAX_BOUNDED_MINMAX_WIDTH = 256

RANKING_FUNCS = ("RowNumber", "Rank", "DenseRank")
OFFSET_FUNCS = ("Lag", "Lead")
AGG_WINDOW_FUNCS = ("Sum", "Min", "Max", "Count", "Average", "First", "Last")


@dataclass
class WindowFunc:
    """One resolved window function over a shared (partition, order) spec."""
    kind: str
    child: Optional[E.Expression]      # value expression (aggs, lag/lead)
    frame: Tuple                       # ("rows", start, end) |
                                       # ("range_to_current",) | ("whole",)
    name: str
    dtype: DataType
    offset: int = 1                    # lag/lead
    default: object = None             # lag/lead


class WindowUnsupported(Exception):
    pass


def _result_dtype(kind: str, child: Optional[E.Expression]) -> DataType:
    if kind in RANKING_FUNCS:
        return IntegerType
    if kind == "Count":
        return LongType
    if kind == "Average":
        return DoubleType
    if kind == "Sum":
        assert child is not None
        return LongType if child.dtype.is_integral else DoubleType
    assert child is not None
    return child.dtype


def resolve_window_func(func_ce, spec, schema: Schema, resolve,
                        device: bool = True) -> WindowFunc:
    """ColumnExpr window function + WindowSpec -> WindowFunc.

    Semantic violations always raise WindowUnsupported; device-capability
    limits (frame widths the TPU kernels cap) raise only when `device` is
    True, mirroring the reference's tagging-vs-capability split
    (GpuWindowExpression.scala tag checks)."""
    op = func_ce.op
    name = func_ce.output_name
    has_order = bool(spec.orders)

    if spec.frame is not None:
        _kind, start, end = spec.frame
        start = -UNBOUNDED if start <= -UNBOUNDED else start
        end = UNBOUNDED if end >= UNBOUNDED else end
        if start > end:
            raise WindowUnsupported(f"empty frame [{start}, {end}]")
        frame = ("rows", start, end)
    elif has_order:
        frame = ("range_to_current",)
    else:
        frame = ("whole",)

    if op in RANKING_FUNCS:
        if not has_order:
            raise WindowUnsupported(f"{op} requires an ORDER BY")
        return WindowFunc(op, None, frame, name, IntegerType)

    if op in OFFSET_FUNCS:
        child_ce, offset, default = func_ce.args
        if not has_order:
            raise WindowUnsupported(f"{op} requires an ORDER BY")
        child = resolve(child_ce, schema)
        return WindowFunc(op, child, frame, name, child.dtype,
                          offset=int(offset), default=default)

    from .aggregates import AGG_FUNCS
    if op in AGG_FUNCS:
        if op == "Percentile":
            raise WindowUnsupported("percentile window aggregates")
        child_ce, distinct = func_ce.args
        if distinct:
            raise WindowUnsupported("DISTINCT window aggregates")
        if op == "Count" and (child_ce.op == "lit"
                              and child_ce.args[0] in (1, "*")):
            child = None
        else:
            child = resolve(child_ce, schema)
        if op in ("Sum", "Average") and child is not None \
                and not child.dtype.is_numeric:
            raise WindowUnsupported(f"{op} over {child.dtype.name}")
        if device and op in ("Min", "Max") and frame[0] == "rows":
            start, end = frame[1], frame[2]
            bounded = start > -UNBOUNDED and end < UNBOUNDED
            if bounded and end - start + 1 > MAX_BOUNDED_MINMAX_WIDTH:
                raise WindowUnsupported(
                    f"bounded {op} frame wider than "
                    f"{MAX_BOUNDED_MINMAX_WIDTH} rows")
            if start > -UNBOUNDED and child is not None \
                    and child.dtype.is_string:
                # the string kernel is a forward segmented scan: it needs
                # the frame to start at the partition start
                raise WindowUnsupported(
                    f"{op} over strings with a bounded frame start")
        if child is not None and child.dtype.is_string \
                and op not in ("Min", "Max", "First", "Last", "Count"):
            raise WindowUnsupported(f"{op} over strings")
        return WindowFunc(op, child, frame, name,
                          _result_dtype(op, child))

    raise WindowUnsupported(f"{op} is not a window function")


# --------------------------------------------------------------------------
# device kernels (all operate on the SORTED batch; segments contiguous)
# --------------------------------------------------------------------------

def _shift_prev(x):
    return jnp.concatenate([x[:1], x[:-1]])


def _neq_prev(c: Column) -> jnp.ndarray:
    """True where a row's value differs from the previous row's (null-safe:
    null == null)."""
    pv = _shift_prev(c.valid)
    if c.dtype.is_string:
        data_eq = jnp.all(c.data == _shift_prev(c.data), axis=1)
        data_eq = data_eq & (c.lengths == _shift_prev(c.lengths))
    else:
        d = c.data
        if c.dtype.is_floating:
            # NaN == NaN and -0.0 == 0.0 for grouping/ordering purposes;
            # value compare stays in float (XLA:TPU has no f64 bitcast)
            f = d.astype(jnp.float64)
            nan = jnp.isnan(f)
            v = jnp.where(nan | (f == 0.0), jnp.float64(0.0), f)
            data_eq = (v == _shift_prev(v)) & (nan == _shift_prev(nan))
        else:
            data_eq = d == _shift_prev(d)
    eq = jnp.where(c.valid & pv, data_eq, c.valid == pv)
    return ~eq


def segment_flags(sorted_batch: ColumnarBatch,
                  part_exprs: Sequence[E.Expression],
                  order_exprs: Sequence[E.Expression]):
    """(seg_start, new_peer) boolean flags on the sorted batch."""
    cap = sorted_batch.capacity
    first = jnp.arange(cap, dtype=jnp.int32) == 0
    live = sorted_batch.sel
    seg_start = first | (live != _shift_prev(live))
    for e in part_exprs:
        seg_start = seg_start | _neq_prev(e.eval(sorted_batch))
    new_peer = seg_start
    for e in order_exprs:
        new_peer = new_peer | _neq_prev(e.eval(sorted_batch))
    return seg_start, new_peer


def segment_indices(seg_start, new_peer):
    """Per-row segment-first / segment-last / peer-first / peer-last row
    indices (all int32)."""
    cap = seg_start.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    seg_first = jax.lax.cummax(jnp.where(seg_start, iota, 0))
    peer_first = jax.lax.cummax(jnp.where(new_peer, iota, 0))
    seg_end_flag = jnp.concatenate([seg_start[1:],
                                    jnp.ones(1, dtype=jnp.bool_)])
    peer_end_flag = jnp.concatenate([new_peer[1:],
                                     jnp.ones(1, dtype=jnp.bool_)])
    seg_last = jnp.flip(jax.lax.cummin(
        jnp.flip(jnp.where(seg_end_flag, iota, cap))))
    peer_last = jnp.flip(jax.lax.cummin(
        jnp.flip(jnp.where(peer_end_flag, iota, cap))))
    return seg_first, seg_last.astype(jnp.int32), peer_first, \
        peer_last.astype(jnp.int32)


def _segmented_scan(vals, reset, op, reverse=False):
    """Associative segmented scan: within a segment, running `op`; resets at
    `reset` flags (forward: segment starts; reverse: segment ends)."""
    def combine(a, b):
        va, fa = a
        vb, fb = b
        return jnp.where(fb, vb, op(va, vb)), fa | fb
    v, _ = jax.lax.associative_scan(combine, (vals, reset), reverse=reverse)
    return v


def _frame_bounds(func: WindowFunc, iota, seg_first, seg_last, peer_last):
    """Per-row inclusive [a, b] frame row-index bounds."""
    if func.frame[0] == "whole":
        return seg_first, seg_last
    if func.frame[0] == "range_to_current":
        return seg_first, peer_last
    _r, start, end = func.frame
    a = seg_first if start <= -UNBOUNDED else \
        jnp.maximum(seg_first, iota + jnp.int32(start))
    b = seg_last if end >= UNBOUNDED else \
        jnp.minimum(seg_last, iota + jnp.int32(end))
    return a, b


def _prefix_sum_frame(vals_f, a, b, seg_start=None):
    """Sum over rows [a, b] via prefix sums; empty frame -> 0.

    With `seg_start` the prefix sum is SEGMENTED (resets at every segment
    start).  Frames never cross segment boundaries, and a global float
    cumsum would let one segment's values poison every later frame's
    subtraction — catastrophically (a 1e300 value absorbs everything
    below ~1e284) or absorbingly (inf - inf = NaN).  Integer counts are
    exact under wraparound, so callers may omit seg_start for them."""
    if seg_start is None:
        p = jnp.cumsum(vals_f)
    else:
        def comb(x, y):
            vx, rx = x
            vy, ry = y
            return (jnp.where(ry, vy, vx + vy), rx | ry)
        p, _ = jax.lax.associative_scan(comb, (vals_f, seg_start))
    p = jnp.concatenate([jnp.zeros(1, dtype=p.dtype), p])
    take = lambda idx: jnp.take(p, jnp.clip(idx, 0, p.shape[0] - 1))
    if seg_start is None:
        lower = take(a)
    else:
        # frames start no earlier than their own segment (a >= seg_first);
        # when a IS the segment start the lower term is 0 — take(a) would
        # be the PREVIOUS segment's tail, which the reset already excluded
        # from take(b + 1)
        a_c = jnp.clip(a, 0, seg_start.shape[0] - 1)
        lower = jnp.where(jnp.take(seg_start, a_c),
                          jnp.zeros((), p.dtype), take(a))
    return jnp.where(b >= a, take(b + 1) - lower, jnp.zeros((), p.dtype))


def eval_window_func(func: WindowFunc, sorted_batch: ColumnarBatch,
                     seg_start, new_peer) -> Column:
    """Evaluate one window function on the sorted batch."""
    cap = sorted_batch.capacity
    iota = jnp.arange(cap, dtype=jnp.int32)
    seg_first, seg_last, peer_first, peer_last = \
        segment_indices(seg_start, new_peer)

    if func.kind == "RowNumber":
        out = (iota - seg_first + 1).astype(jnp.int32)
        return Column(out, jnp.ones(cap, dtype=jnp.bool_), IntegerType)
    if func.kind == "Rank":
        out = (peer_first - seg_first + 1).astype(jnp.int32)
        return Column(out, jnp.ones(cap, dtype=jnp.bool_), IntegerType)
    if func.kind == "DenseRank":
        changes = (new_peer & ~seg_start).astype(jnp.int32)
        c = jnp.cumsum(changes)
        out = (c - jnp.take(c, seg_first) + 1).astype(jnp.int32)
        return Column(out, jnp.ones(cap, dtype=jnp.bool_), IntegerType)

    if func.kind in OFFSET_FUNCS:
        c = func.child.eval(sorted_batch)
        k = func.offset if func.kind == "Lag" else -func.offset
        src = iota - jnp.int32(k)
        ok = (src >= seg_first) & (src <= seg_last)
        src_c = jnp.clip(src, 0, cap - 1)
        g = c.take(src_c)
        if func.default is not None:
            dflt = E.lit(func.default, func.dtype).eval(sorted_batch)
            if func.dtype.is_string and dflt.max_len != g.max_len:
                # bucketed byte-matrix widths must agree before the select
                width = max(dflt.max_len, g.max_len)
                dflt = dflt.pad_strings_to(width)
                g = g.pad_strings_to(width)
            data = jnp.where(_bmask(ok, g.data), g.data, dflt.data)
            valid = jnp.where(ok, g.valid, dflt.valid)
            if func.dtype.is_string:
                lens = jnp.where(ok, g.lengths, dflt.lengths)
                return Column(data, valid, func.dtype, lens)
            return Column(data, valid, func.dtype)
        valid = ok & g.valid
        return Column(g.data, valid, func.dtype, g.lengths)

    # aggregates over frames
    a, b = _frame_bounds(func, iota, seg_first, seg_last, peer_last)

    if func.kind == "Count":
        if func.child is None:
            ones = jnp.ones(cap, dtype=jnp.int64)
        else:
            ones = func.child.eval(sorted_batch).valid.astype(jnp.int64)
        out = _prefix_sum_frame(ones, a, b)
        return Column(out, jnp.ones(cap, dtype=jnp.bool_), LongType)

    c = func.child.eval(sorted_batch).mask_invalid()

    if func.kind in ("First", "Last"):
        idx = jnp.clip(a if func.kind == "First" else b, 0, cap - 1)
        g = c.take(idx)
        valid = (b >= a) & g.valid
        return Column(g.data, valid, func.dtype, g.lengths)

    if func.kind in ("Sum", "Average"):
        acc_dtype = jnp.int64 if (func.kind == "Sum"
                                  and c.dtype.is_integral) else jnp.float64
        vals = jnp.where(c.valid, c.data.astype(acc_dtype),
                         jnp.zeros((), acc_dtype))
        n = _prefix_sum_frame(c.valid.astype(jnp.int64), a, b)
        if acc_dtype == jnp.float64:
            # float sums are SEGMENTED (cross-segment cancellation: one
            # huge value would absorb every later segment's values in a
            # global cumsum) and split finite/non-finite: an inf/NaN
            # inside the segment but OUTSIDE a bounded frame must not
            # leak in via the prefix subtraction, so the IEEE result is
            # rebuilt from per-frame counts of nan/+inf/-inf
            finite = jnp.isfinite(vals)
            s = _prefix_sum_frame(jnp.where(finite, vals, 0.0), a, b,
                                  seg_start)
            n_nan = _prefix_sum_frame(
                jnp.isnan(vals).astype(jnp.int64), a, b)
            n_pinf = _prefix_sum_frame(
                (vals == jnp.inf).astype(jnp.int64), a, b)
            n_ninf = _prefix_sum_frame(
                (vals == -jnp.inf).astype(jnp.int64), a, b)
            s = jnp.where(
                (n_nan > 0) | ((n_pinf > 0) & (n_ninf > 0)), jnp.nan,
                jnp.where(n_pinf > 0, jnp.inf,
                          jnp.where(n_ninf > 0, -jnp.inf, s)))
        else:
            s = _prefix_sum_frame(vals, a, b)
        if func.kind == "Sum":
            return Column(s.astype(func.dtype.jnp_dtype), n > 0, func.dtype)
        avg = s.astype(jnp.float64) / jnp.maximum(n, 1).astype(jnp.float64)
        return Column(avg, n > 0, DoubleType)

    assert func.kind in ("Min", "Max"), func.kind
    if c.dtype.is_floating:
        return _min_max_float(func, c, a, b, iota, seg_start)
    return _min_max(func, c, a, b, iota, seg_start, seg_first, seg_last)


def _bmask(ok, data):
    return ok[:, None] if data.ndim == 2 else ok


def _min_max_float(func: WindowFunc, c: Column, a, b, iota,
                   seg_start) -> Column:
    """Floats: (nan_flag, value) pair scans — NaN greatest (Spark), nulls
    never win, NO f64<->int bitcast (XLA:TPU refuses it)."""
    cap = iota.shape[0]
    is_min = func.kind == "Min"
    d = c.data.astype(jnp.float64)
    nan = jnp.isnan(d)
    v = jnp.where(nan | (d == 0.0), jnp.float64(0.0), d)
    inf = jnp.float64(np.inf)
    # sentinel pair for nulls: always loses
    flag = jnp.where(c.valid, nan.astype(jnp.int32),
                     jnp.int32(2 if is_min else -1))
    v = jnp.where(c.valid, v, inf if is_min else -inf)

    def better(x, y):
        fx, vx = x
        fy, vy = y
        if is_min:
            keep_x = (fx < fy) | ((fx == fy) & (vx <= vy))
        else:
            keep_x = (fx > fy) | ((fx == fy) & (vx >= vy))
        return (jnp.where(keep_x, fx, fy), jnp.where(keep_x, vx, vy))

    def seg_scan(pair, reset, reverse=False):
        def comb(p, q):
            (fp, vp, rp), (fq, vq, rq) = p, q
            nf, nv = better((fp, vp), (fq, vq))
            return (jnp.where(rq, fq, nf), jnp.where(rq, vq, nv), rp | rq)
        f, val, _ = jax.lax.associative_scan(
            comb, (pair[0], pair[1], reset), reverse=reverse)
        return f, val

    n_valid = _prefix_sum_frame(c.valid.astype(jnp.int64), a, b)
    frame = func.frame
    if frame[0] in ("whole", "range_to_current") or \
            (frame[0] == "rows" and frame[1] <= -UNBOUNDED):
        ff, fv = seg_scan((flag, v), seg_start)
        bf = jnp.take(ff, jnp.clip(b, 0, cap - 1))
        bv = jnp.take(fv, jnp.clip(b, 0, cap - 1))
    elif frame[0] == "rows" and frame[2] >= UNBOUNDED:
        seg_end_flag = jnp.concatenate([seg_start[1:],
                                        jnp.ones(1, dtype=jnp.bool_)])
        rf, rv = seg_scan((flag, v), seg_end_flag, reverse=True)
        bf = jnp.take(rf, jnp.clip(a, 0, cap - 1))
        bv = jnp.take(rv, jnp.clip(a, 0, cap - 1))
    else:
        _r, start, end = frame
        bf = jnp.full(cap, 2 if is_min else -1, dtype=jnp.int32)
        bv = jnp.full(cap, inf if is_min else -inf, dtype=jnp.float64)
        for off in range(start, end + 1):
            src = jnp.clip(iota + jnp.int32(off), 0, cap - 1)
            in_f = (iota + off >= a) & (iota + off <= b)
            cf = jnp.where(in_f, jnp.take(flag, src),
                           jnp.int32(2 if is_min else -1))
            cv = jnp.where(in_f, jnp.take(v, src), inf if is_min else -inf)
            bf, bv = better((bf, bv), (cf, cv))
    out = jnp.where(bf == 1, jnp.float64(np.nan), bv)
    return Column(out.astype(func.dtype.jnp_dtype), n_valid > 0, func.dtype)


def _min_max(func: WindowFunc, c: Column, a, b, iota, seg_start,
             seg_first, seg_last) -> Column:
    cap = iota.shape[0]
    is_min = func.kind == "Min"
    if c.dtype.is_string:
        return _min_max_string(func, c, a, b, iota, seg_first, seg_last)
    from .sort_keys import column_sort_keys
    # encode to order-preserving int64 keys so one scan handles floats with
    # Spark NaN/-0.0 semantics too
    keys = column_sort_keys(c, ascending=True)
    assert len(keys) == 1
    k = keys[0]
    # int64 extremes: NaN's sort key (0x7FF8...) exceeds 2^62, so anything
    # smaller would let nulls beat valid NaNs in a Min
    big = jnp.int64(2 ** 63 - 1) if is_min else jnp.int64(-(2 ** 63))
    k = jnp.where(c.valid, k, big)  # nulls never win
    op = jnp.minimum if is_min else jnp.maximum
    frame = func.frame
    n_valid = _prefix_sum_frame(c.valid.astype(jnp.int64), a, b)
    if frame[0] in ("whole", "range_to_current") or \
            (frame[0] == "rows" and frame[1] <= -UNBOUNDED):
        fwd = _segmented_scan(k, seg_start, op)
        best_k = jnp.take(fwd, jnp.clip(b, 0, cap - 1))
    elif frame[0] == "rows" and frame[2] >= UNBOUNDED:
        seg_end_flag = jnp.concatenate([seg_start[1:],
                                        jnp.ones(1, dtype=jnp.bool_)])
        rev = _segmented_scan(k, seg_end_flag, op, reverse=True)
        best_k = jnp.take(rev, jnp.clip(a, 0, cap - 1))
    else:  # bounded both sides: static stack of shifted gathers
        _r, start, end = frame
        best_k = big
        for off in range(start, end + 1):
            src = jnp.clip(iota + jnp.int32(off), 0, cap - 1)
            in_seg = (iota + off >= a) & (iota + off <= b)
            kk = jnp.where(in_seg, jnp.take(k, src), big)
            best_k = op(best_k, kk)
    # decode: find the row holding best_k is wasteful; instead recompute the
    # value by inverting the key encoding per dtype
    out = _decode_sort_key(best_k, c.dtype)
    return Column(out, n_valid > 0, func.dtype)


def _decode_sort_key(k, dtype: DataType):
    """Invert sort_keys.column_sort_keys for single-key integer dtypes
    (floats take the pair-scan path in _min_max_float)."""
    assert not dtype.is_floating
    if dtype.name == "boolean":
        return k.astype(jnp.uint8)
    return k.astype(dtype.jnp_dtype)


def _min_max_string(func, c: Column, a, b, iota, seg_first, seg_last):
    """Strings: frame gathers with lexicographic reduce via stacked shifted
    compare is costly; support unbounded frames with a segmented scan over
    (row index of current best), comparing byte rows."""
    cap = iota.shape[0]
    is_min = func.kind == "Min"
    from .expressions import string_lt

    def better(i_idx, j_idx):
        ci, cj = c.take(i_idx), c.take(j_idx)
        lt = string_lt(ci, cj)
        i_wins = jnp.where(is_min, lt, ~lt & ~_string_eq_rows(ci, cj))
        # nulls never win
        i_wins = jnp.where(ci.valid & ~cj.valid, True, i_wins)
        i_wins = jnp.where(~ci.valid, False, i_wins)
        return jnp.where(i_wins, i_idx, j_idx)

    if func.frame[0] == "rows" and func.frame[1] > -UNBOUNDED:
        raise WindowUnsupported(
            "min/max over strings with a bounded frame start")
    fwd = _segmented_scan(iota, _seg_start_from_first(seg_first, iota),
                          better)
    best_idx = jnp.take(fwd, jnp.clip(b, 0, cap - 1))
    g = c.take(best_idx)
    n_valid = _prefix_sum_frame(c.valid.astype(jnp.int64), a, b)
    return Column(g.data, n_valid > 0, c.dtype, g.lengths)


def _string_eq_rows(x: Column, y: Column):
    return jnp.all(x.data == y.data, axis=1) & (x.lengths == y.lengths)


def _seg_start_from_first(seg_first, iota):
    return seg_first == iota
