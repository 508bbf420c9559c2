"""`grouping(col)` and `grouping_id()` over a ROLLUP or CUBE.

A rolled-up aggregate is planned as Expand -> Aggregate keyed on the
nullable key copies and `_grouping_id` (engine.py `_expand_rollup`), under
a Project that drops the id.  The id's bits ARE Spark's `grouping_id()`:
bit `n - 1 - i` is set where grouping column `i` is aggregated away in
that row.  So the two functions need no operator and no physical
expression of their own: wherever a DataFrame method builds a node whose
expressions hold one (the aggregate's own output list, a later
`with_column` / `select`, a filter, a sort, a window's partition or order
keys), `resolve_grouping` rewrites it into shifts and masks of
`col("_grouping_id")` and lets the id through every Project between the
node and the aggregate; a node that does not project (Filter, Sort) gets
a Project on top that drops the id again.  Both engines then evaluate
plain expressions (Spark resolves the functions the same way, against its
Expand's `spark_grouping_id`: ResolveGroupingAnalytics).

Outside a rollup or cube the functions are an analysis error, as in Spark.
"""
from __future__ import annotations

from typing import List, Optional

from ..types import ByteType, LongType
from . import logical as L
from .analysis import AnalysisError
from .logical import ColumnExpr, SortOrder, WindowSpec, col, lit

GROUPING_ID = "_grouping_id"
_OPS = ("Grouping", "GroupingID")

# nodes the id passes through on its way up from the aggregate: one child,
# every row of the child kept or dropped whole
_PASS_THROUGH = (L.LogicalProject, L.LogicalFilter, L.LogicalSort,
                 L.LogicalLimit, L.LogicalWindow)


def _map(x, f):
    """`x` with `f` applied to every ColumnExpr at its top level, through
    the containers an expression's `args` hold."""
    if isinstance(x, ColumnExpr):
        return f(x)
    if isinstance(x, SortOrder):
        return SortOrder(f(x.child), x.ascending, x.nulls_first)
    if isinstance(x, WindowSpec):
        return WindowSpec(_map(x.parts, f), _map(x.orders, f), x.frame)
    if isinstance(x, (list, tuple)):
        return type(x)(_map(y, f) for y in x)
    return x


def has_grouping(x) -> bool:
    """Whether a grouping() / grouping_id() stands anywhere in `x` (an
    expression or a container of them).  Every DataFrame method asks this
    of what it builds, so it allocates nothing."""
    if isinstance(x, ColumnExpr):
        return x.op in _OPS or has_grouping(x.args)
    if isinstance(x, SortOrder):
        return has_grouping(x.child)
    if isinstance(x, WindowSpec):
        return has_grouping(x.parts) or has_grouping(x.orders)
    if isinstance(x, (list, tuple)):
        return any(has_grouping(y) for y in x)
    return False


def _node_exprs(node):
    if isinstance(node, L.LogicalProject):
        return node.exprs
    if isinstance(node, L.LogicalFilter):
        return [node.condition]
    if isinstance(node, L.LogicalSort):
        return node.orders
    if isinstance(node, L.LogicalWindow):
        return [node.window_exprs, node.partition_by, node.order_by]
    return []


def _rollup_keys(node) -> Optional[List[str]]:
    """The grouping columns of the rollup or cube `node` sits on, reached
    through pass-through nodes only."""
    while isinstance(node, _PASS_THROUGH):
        node = node.children[0]
    return getattr(node, "rollup_keys", None)


def _rewrite_expr(e: ColumnExpr, keys: List[str]) -> ColumnExpr:
    if e.op == "GroupingID":
        return ColumnExpr("Cast", (col(GROUPING_ID), LongType),
                          alias=e._alias)
    if e.op == "Grouping":
        arg = e.args[0]
        if arg.op != "col" or arg.args[0] not in keys:
            raise AnalysisError(
                f"grouping() takes one of the grouping columns {keys}, "
                f"got {arg!r}")
        shift = len(keys) - 1 - keys.index(arg.args[0])
        bit = ColumnExpr("BitwiseAnd", (
            ColumnExpr("ShiftRight", (col(GROUPING_ID), lit(shift))),
            lit(1)))
        return ColumnExpr("Cast", (bit, ByteType), alias=e._alias)
    return ColumnExpr(e.op, _map(e.args, lambda a: _rewrite_expr(a, keys)),
                      alias=e._alias)


def resolve_grouping(node, conf, want_id: bool = False):
    """`node`, whose own expressions may hold `grouping()` /
    `grouping_id()`, with them resolved (the nodes below it hold none:
    every DataFrame method resolves what it builds).  With `want_id` the
    result also carries `_grouping_id` (what the node above asked for)."""
    uses = has_grouping(_node_exprs(node))
    has_id = (getattr(node, "rollup_keys", None) is not None
              or isinstance(node, L.LogicalProject)
              and any(e.output_name == GROUPING_ID for e in node.exprs))
    if not uses and (not want_id or has_id):
        return node      # nothing to do, or the id is already in the output
    keys = _rollup_keys(node)
    if keys is None or not isinstance(node, _PASS_THROUGH):
        raise AnalysisError(
            "grouping() / grouping_id() can only be used over a rollup or "
            "cube, through projections, filters, sorts, limits and windows")
    child = resolve_grouping(node.children[0], conf, want_id=True)

    def rw(x):
        return _map(x, lambda e: _rewrite_expr(e, keys))
    if isinstance(node, L.LogicalProject):
        exprs = rw(node.exprs) + ([col(GROUPING_ID)]
                                  if want_id and not has_id else [])
        new = L.LogicalProject(exprs, child)
    elif isinstance(node, L.LogicalFilter):
        new = L.LogicalFilter(rw(node.condition), child)
    elif isinstance(node, L.LogicalSort):
        new = L.LogicalSort(rw(node.orders), child)
    elif isinstance(node, L.LogicalLimit):
        new = L.LogicalLimit(node.n, child)
    else:
        new = L.LogicalWindow(rw(node.window_exprs), rw(node.partition_by),
                              rw(node.order_by), child)
    if hasattr(node, "_hints"):
        new._hints = node._hints
    if want_id or isinstance(node, L.LogicalProject):
        return new
    # a node that does not project, and nobody above asked for the id
    from .overrides import plan_schema
    return L.LogicalProject(
        [col(n) for n in plan_schema(new, conf).names if n != GROUPING_ID],
        new)
