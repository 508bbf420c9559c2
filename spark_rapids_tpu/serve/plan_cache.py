"""Parameterized plan cache: normalize literals out of a query plan.

The warmup killer (ROADMAP item 2): a cold query spends far longer
compiling than executing, and a second user running the SAME
query shape with different literals (a different date cutoff, a
different discount band) pays the whole warmup again, because literal
values are baked into every kernel-cache key (utils/kernel_cache.expr_key
keys Literal by repr(value)).

This module fixes the second user:

  * `extract_parameters(plan)` rewrites a LOGICAL plan, lifting eligible
    literals in row-local positions (Project/Filter/Expand expressions
    under value-safe operators — comparisons, arithmetic, boolean logic,
    CaseWhen/Coalesce/Least/Greatest, or a bare projected literal) into
    `ColumnExpr("param", (slot, dtype, value))` placeholders.  The
    current value rides INLINE, so scan pushdown still prunes row groups
    against concrete bounds and CPU twins evaluate the right constant —
    but the kernel layer resolves the placeholder to an
    `ops.expressions.Parameter`, whose value enters compiled programs as
    a RUNTIME argument on every parameter-threaded dispatch path
    (RowLocalExec, TpuWholeStageExec, the aggregate whole-stage
    absorption, the exchange bucketing fusion).  Result: a literal
    variant of a seen plan produces byte-identical stage keys and
    replays the cached traced+compiled executables — trace AND compile
    are skipped (`kernel_cache.stage_executable` hits).

  * `plan_cache_key(normalized, conf)` fingerprints the normalized tree
    (parameter slots + dtypes, never values) together with the input
    schemas/sources and the session conf, so a hit means "same plan
    shape, same inputs, same planning-relevant configuration".

  * `PlanCache` is the bookkeeping layer the QueryScheduler consults:
    LRU-bounded entries, hit/miss/lifted counters (surfaced as
    planCacheHits/planCacheMisses metrics).
    Execution ALWAYS uses the incoming normalized plan — never a cached
    object — so a fingerprint collision can only miscount, never
    mis-execute, and concurrent submissions share no mutable plan state.

What invalidates a cached plan (docs/tuning-guide.md): any conf change,
a different input table/file set, a schema change, a literal whose
inferred dtype changes (5 vs 2**40), string/null literals, and literals
outside the value-safe positions (aggregate arguments, join conditions,
sort keys, limits) — those stay part of the key.
"""
from __future__ import annotations

import copy
import threading
from collections import OrderedDict
from typing import List, Tuple

import numpy as np

from ..plan.logical import (ColumnExpr, LogicalAggregate, LogicalExpand,
                            LogicalFilter, LogicalJoin, LogicalPlan,
                            LogicalProject, LogicalSort, SortOrder)

# ColumnExpr ops under which a literal child evaluates as a genuine
# columnar value (broadcast scalar flowing through jnp ops) — safe to
# feed from a traced runtime argument.  Ops that consume literals as
# STATIC kernel configuration (Substring lengths, Like patterns, Round
# decimals, In lists, Cast targets) are deliberately absent: their
# literals stay baked and key the cache.
_LIFT_UNDER = frozenset({
    "EqualTo", "LessThan", "GreaterThan", "LessThanOrEqual",
    "GreaterThanOrEqual", "EqualNullSafe",
    "Add", "Subtract", "Multiply", "Divide", "IntegralDivide",
    "Remainder", "Pmod",
    "And", "Or", "Not", "Coalesce", "Least", "Greatest",
    "UnaryMinus", "Abs", "CaseWhen", "NaNvl",
    "__root__",  # a bare projected literal / filter condition
})


def _eligible(v) -> bool:
    """Numeric/bool literal values only: strings have host-side eval
    paths (Column.from_strings) and Nones change null semantics — both
    stay baked Literals and key the plan."""
    return isinstance(v, (bool, int, float, np.integer, np.floating))


def _rewrite_expr(ce, parent_op: str, values: List):
    if isinstance(ce, SortOrder) or not isinstance(ce, ColumnExpr):
        return ce
    if ce.op == "lit":
        v = ce.args[0]
        if parent_op in _LIFT_UNDER and _eligible(v):
            from ..ops.expressions import _infer_literal_type
            slot = len(values)
            values.append(v)
            return ColumnExpr("param", (slot, _infer_literal_type(v), v),
                              alias=ce._alias)
        return ce
    if ce.op == "WindowExpr":
        # window specs carry frame/ordering objects the rewrite has no
        # business descending into; window kernels are not
        # parameter-threaded anyway
        return ce
    new_args, changed = [], False
    for a in ce.args:
        na = _rewrite_arg(a, ce.op, values)
        changed = changed or na is not a
        new_args.append(na)
    if not changed:
        return ce
    return ColumnExpr(ce.op, tuple(new_args), alias=ce._alias)


def _rewrite_arg(a, op: str, values: List):
    if isinstance(a, ColumnExpr):
        return _rewrite_expr(a, op, values)
    if isinstance(a, (list, tuple)):
        out = [_rewrite_arg(x, op, values) for x in a]
        if all(n is o for n, o in zip(out, a)):
            return a
        return type(a)(out)
    return a


def _copy_node(node: LogicalPlan, children, **attrs) -> LogicalPlan:
    """Shallow-copy with new children/attrs (never mutates the input —
    DataFrames share logical nodes, same contract as pushdown._rebuild)."""
    new = copy.copy(node)
    new.children = tuple(children)
    for k, v in attrs.items():
        setattr(new, k, v)
    new.__dict__.pop("_cached_schema", None)
    return new


def extract_parameters(plan: LogicalPlan) -> Tuple[LogicalPlan, List]:
    """(normalized plan, lifted values).  Slots number the lifted
    literals in tree order, so two structurally equal queries assign
    identical slots to corresponding literals.

    Two classes of position:

      * Project/Filter/Expand expressions lift under `"__root__"` — a
        bare projected literal qualifies, and these are the
        parameter-THREADED dispatch paths, so the lifted value enters
        the compiled program as a runtime argument (no recompile).
      * Aggregate, sort and join expressions lift only literals NESTED
        under value-safe operators (`"__guard__"` parent: `sum(price *
        (1 - discount))`'s constants qualify, `count(lit(1))`'s bare
        literal does not — bare literal agg children have count-star
        special-casing in analysis).  These kernels are not
        parameter-threaded: the Parameter evaluates as its baked value
        and keys kernel caches VALUE-INCLUSIVELY (always correct, one
        recompile per distinct value) — but the PLAN key is value-free,
        so literal variants still hit the plan cache and reuse every
        threaded stage around the aggregate."""
    values: List = []

    def guard_list(exprs):
        return [_rewrite_expr(e, "__guard__", values) for e in exprs]

    def walk(node: LogicalPlan) -> LogicalPlan:
        children = [walk(c) for c in node.children]
        kids_changed = any(n is not o
                           for n, o in zip(children, node.children))
        if isinstance(node, LogicalProject):
            exprs = [_rewrite_expr(e, "__root__", values)
                     for e in node.exprs]
            if kids_changed or any(n is not o
                                   for n, o in zip(exprs, node.exprs)):
                return _copy_node(node, children, exprs=exprs)
            return node
        if isinstance(node, LogicalFilter):
            cond = _rewrite_expr(node.condition, "__root__", values)
            if kids_changed or cond is not node.condition:
                return _copy_node(node, children, condition=cond)
            return node
        if isinstance(node, LogicalExpand):
            projections = [[_rewrite_expr(e, "__root__", values)
                            for e in proj] for proj in node.projections]
            changed = any(n is not o
                          for np_, op_ in zip(projections,
                                              node.projections)
                          for n, o in zip(np_, op_))
            if kids_changed or changed:
                return _copy_node(node, children, projections=projections)
            return node
        if isinstance(node, LogicalAggregate):
            grouping = guard_list(node.grouping)
            aggregates = guard_list(node.aggregates)
            changed = any(n is not o for n, o in
                          zip(grouping + aggregates,
                              list(node.grouping) + list(node.aggregates)))
            if kids_changed or changed:
                return _copy_node(node, children, grouping=grouping,
                                  aggregates=aggregates)
            return node
        if isinstance(node, LogicalSort):
            orders = [SortOrder(_rewrite_expr(o.child, "__guard__",
                                              values),
                                o.ascending, o.nulls_first)
                      if isinstance(o, SortOrder) else o
                      for o in node.orders]
            changed = any(isinstance(o, SortOrder)
                          and n.child is not o.child
                          for n, o in zip(orders, node.orders))
            if kids_changed or changed:
                return _copy_node(node, children, orders=orders)
            return node
        if isinstance(node, LogicalJoin) \
                and getattr(node, "condition", None) is not None:
            cond = _rewrite_expr(node.condition, "__guard__", values)
            if kids_changed or cond is not node.condition:
                return _copy_node(node, children, condition=cond)
            return node
        if kids_changed:
            return _copy_node(node, children)
        return node

    return walk(plan), values


# --------------------------------------------------------------------------
# fingerprinting
# --------------------------------------------------------------------------

def _val_fp(v, seen: set):
    if isinstance(v, ColumnExpr):
        if v.op == "param":
            slot, dtype, _value = v.args  # value-free: that is the point
            return ("param", slot, dtype.name, v._alias)
        return ("CE", v.op, v._alias,
                tuple(_val_fp(a, seen) for a in v.args))
    if isinstance(v, SortOrder):
        return ("SO", _val_fp(v.child, seen), v.ascending, v.nulls_first)
    if v is None or isinstance(v, (str, int, float, bool, bytes)):
        return v
    if isinstance(v, (list, tuple)):
        return ("seq", tuple(_val_fp(x, seen) for x in v))
    if isinstance(v, dict):
        return ("map", tuple(sorted((str(k), _val_fp(x, seen))
                                    for k, x in v.items())))
    if isinstance(v, (set, frozenset)):
        return ("set", tuple(sorted(map(repr, v))))
    from ..types import DataType, Schema
    if isinstance(v, DataType):
        return ("dt", v.name)
    if isinstance(v, Schema):
        return ("schema", tuple((f.name, f.dtype.name) for f in v))
    if type(v).__name__ == "Table" and hasattr(v, "column_names"):
        # pyarrow tables are immutable: identity implies content.  The
        # cache holds NO reference to the table (128 retained input
        # tables would be an unbounded-bytes leak in a long-lived
        # server), so a recycled id could in principle alias — shape and
        # schema ride along to make that a counters-only curiosity, and
        # execution always uses the submitted plan, never a cached one.
        return ("table", id(v), v.num_rows,
                tuple(str(t) for t in v.schema.types))
    if id(v) in seen:
        return ("cycle",)
    d = getattr(v, "__dict__", None)
    if d is not None:
        seen = seen | {id(v)}
        return ("obj", type(v).__name__,
                tuple(sorted((k, _val_fp(x, seen)) for k, x in d.items())))
    # last resort: type-only.  A collision here can only miscount a hit
    # (execution always uses the incoming plan), never mis-execute.
    return ("opaque", type(v).__name__)


def _plan_fp(node: LogicalPlan, seen: set) -> tuple:
    attrs = []
    skip = ("children", "_cached_schema")
    if getattr(node, "source_identity", None) is not None:
        # Streaming scans (streaming/source.py) stamp a stable
        # source_identity on the scan node: the source PAYLOAD changes
        # every epoch (an appended table object, a longer file list, a
        # bigger num_rows) while the plan is the same dashboard query —
        # baking the table fingerprint (id/rows) into the key would miss
        # the cache on every epoch and re-compile the stages incremental
        # execution exists to replay.  The identity string (which IS one
        # of the fingerprinted attrs below) plus the scan schema keys the
        # plan instead; offsets/row counts stay out of the key.
        skip = skip + ("source",)
    for k, v in sorted(vars(node).items()):
        if k in skip:
            continue
        attrs.append((k, _val_fp(v, seen)))
    return (type(node).__name__, tuple(attrs),
            tuple(_plan_fp(c, seen) for c in node.children))


def conf_fingerprint(conf) -> tuple:
    """Every explicitly-set key participates: a conf change (a new codec,
    a different batch size, a toggled rule) invalidates cached plans."""
    return tuple(sorted((k, str(v)) for k, v in conf._settings.items()))


def plan_cache_key(normalized: LogicalPlan, conf) -> tuple:
    return (_plan_fp(normalized, set()), conf_fingerprint(conf))


# --------------------------------------------------------------------------
# the cache
# --------------------------------------------------------------------------

class CachedPlan:
    """Bookkeeping only — deliberately NO reference to the plan or its
    input tables (execution always uses the submitted normalized tree,
    and pinning up to maxEntries input tables would leak unbounded
    bytes in a long-lived server)."""

    __slots__ = ("key", "n_params", "param_dtypes", "hits")

    def __init__(self, key, values):
        from ..ops.expressions import _infer_literal_type
        self.key = key
        self.n_params = len(values)
        self.param_dtypes = tuple(_infer_literal_type(v).name
                                  for v in values)
        self.hits = 0


class PlanCache:
    """LRU-bounded normalized-plan registry (one per QueryScheduler)."""

    def __init__(self, max_entries: int = 128):
        self.max_entries = max(1, int(max_entries))
        self._entries: "OrderedDict[tuple, CachedPlan]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.lifted = 0

    def lookup(self, logical: LogicalPlan, conf
               ) -> Tuple[LogicalPlan, List, bool]:
        """Normalize `logical` and account the hit/miss.  Returns
        (normalized plan WITH this submission's values inline, values,
        hit).  The caller plans and executes the returned tree; the
        cached entry is pure bookkeeping."""
        normalized, values = extract_parameters(logical)
        key = plan_cache_key(normalized, conf)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                entry.hits += 1
                self.hits += 1
                hit = True
            else:
                self._entries[key] = CachedPlan(key, values)
                while len(self._entries) > self.max_entries:
                    self._entries.popitem(last=False)
                self.misses += 1
                hit = False
            self.lifted += len(values)
        return normalized, values, hit

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._entries), "hits": self.hits,
                    "misses": self.misses, "params_lifted": self.lifted,
                    "max_entries": self.max_entries}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


