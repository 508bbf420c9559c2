"""Logical plan and the unresolved column DSL (the framework's frontend).

The reference plugs into Spark's Catalyst plans; this standalone framework
provides its own DataFrame-style frontend that produces the same *shape* of
physical-planning problem: a logical tree that the overrides pass (see
overrides.py) tags, converts to device operators where supported, and leaves
on the CPU executor where not.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

from ..types import DataType, Schema


# --------------------------------------------------------------------------
# unresolved expression DSL:  col("a") + 1, f.sum(...), etc.
# --------------------------------------------------------------------------

class ColumnExpr:
    """Unresolved expression; analysis resolves it against a child schema."""

    def __init__(self, op: str, args: Tuple = (), alias: Optional[str] = None):
        self.op = op
        self.args = args
        self._alias = alias

    # -- operators ----------------------------------------------------------
    def _bin(self, op, other, flip=False):
        other = _wrap(other)
        return ColumnExpr(op, (other, self) if flip else (self, other))

    def __add__(self, o):
        return self._bin("Add", o)

    def __radd__(self, o):
        return self._bin("Add", o, flip=True)

    def __sub__(self, o):
        return self._bin("Subtract", o)

    def __rsub__(self, o):
        return self._bin("Subtract", o, flip=True)

    def __mul__(self, o):
        return self._bin("Multiply", o)

    def __rmul__(self, o):
        return self._bin("Multiply", o, flip=True)

    def __truediv__(self, o):
        return self._bin("Divide", o)

    def __rtruediv__(self, o):
        return self._bin("Divide", o, flip=True)

    def __mod__(self, o):
        return self._bin("Remainder", o)

    def __neg__(self):
        return ColumnExpr("UnaryMinus", (self,))

    def __eq__(self, o):  # type: ignore[override]
        return self._bin("EqualTo", o)

    def __ne__(self, o):  # type: ignore[override]
        return ColumnExpr("Not", (self._bin("EqualTo", o),))

    def __lt__(self, o):
        return self._bin("LessThan", o)

    def __le__(self, o):
        return self._bin("LessThanOrEqual", o)

    def __gt__(self, o):
        return self._bin("GreaterThan", o)

    def __ge__(self, o):
        return self._bin("GreaterThanOrEqual", o)

    def __and__(self, o):
        return self._bin("And", o)

    def __or__(self, o):
        return self._bin("Or", o)

    def __invert__(self):
        return ColumnExpr("Not", (self,))

    def __hash__(self):
        return id(self)

    # -- methods ------------------------------------------------------------
    def alias(self, name: str) -> "ColumnExpr":
        return ColumnExpr(self.op, self.args, alias=name)

    def cast(self, to) -> "ColumnExpr":
        if isinstance(to, str):  # Spark accepts type names: .cast("BIGINT")
            from ..types import _TYPES_BY_NAME
            name = to.strip().lower()
            name = {"bigint": "long", "integer": "int",
                    "smallint": "short", "tinyint": "byte"}.get(name, name)
            if name not in _TYPES_BY_NAME:
                raise ValueError(
                    f"cast target type {to!r} is not supported "
                    f"(supported: {sorted(_TYPES_BY_NAME)})")
            to = _TYPES_BY_NAME[name]
        return ColumnExpr("Cast", (self, to))

    def isin(self, *items) -> "ColumnExpr":
        vals = items[0] if len(items) == 1 and isinstance(items[0],
                                                          (list, tuple)) \
            else items
        return ColumnExpr("In", (self, list(vals)))

    def is_null(self) -> "ColumnExpr":
        return ColumnExpr("IsNull", (self,))

    def is_not_null(self) -> "ColumnExpr":
        return ColumnExpr("IsNotNull", (self,))

    def between(self, lo, hi) -> "ColumnExpr":
        return (self >= lo) & (self <= hi)

    def asc(self) -> "SortOrder":
        return SortOrder(self, ascending=True)

    def desc(self) -> "SortOrder":
        return SortOrder(self, ascending=False)

    def over(self, spec: "WindowSpec") -> "ColumnExpr":
        """Turn an aggregate/ranking expression into a window expression
        (pyspark's Column.over)."""
        return ColumnExpr("WindowExpr", (self, spec), alias=self._alias)

    def substr(self, pos, length) -> "ColumnExpr":
        return ColumnExpr("Substring", (self, _wrap(pos), _wrap(length)))

    def startswith(self, s) -> "ColumnExpr":
        return ColumnExpr("StartsWith", (self, _wrap(s)))

    def endswith(self, s) -> "ColumnExpr":
        return ColumnExpr("EndsWith", (self, _wrap(s)))

    def contains(self, s) -> "ColumnExpr":
        return ColumnExpr("Contains", (self, _wrap(s)))

    def like(self, pattern: str) -> "ColumnExpr":
        return ColumnExpr("Like", (self, _wrap(pattern)))

    def rlike(self, pattern: str) -> "ColumnExpr":
        return ColumnExpr("RLike", (self, _wrap(pattern)))

    @property
    def output_name(self) -> str:
        if self._alias:
            return self._alias
        if self.op == "col":
            return self.args[0]
        return self.op.lower()

    def __repr__(self):
        if self.op == "col":
            return f"col({self.args[0]!r})"
        if self.op == "lit":
            return f"lit({self.args[0]!r})"
        if self.op == "param":
            slot, dtype, value = self.args
            return f"param({slot}:{dtype.name}={value!r})"
        return f"{self.op}({', '.join(map(repr, self.args))})"

    def __bool__(self):
        raise TypeError("Cannot convert ColumnExpr to bool; use & | ~")


def _wrap(v) -> ColumnExpr:
    if isinstance(v, ColumnExpr):
        return v
    return ColumnExpr("lit", (v,))


def col(name: str) -> ColumnExpr:
    return ColumnExpr("col", (name,))


def lit(v) -> ColumnExpr:
    return ColumnExpr("lit", (v,))


@dataclasses.dataclass
class SortOrder:
    child: ColumnExpr
    ascending: bool = True
    nulls_first: Optional[bool] = None  # default: first if asc, last if desc

    @property
    def effective_nulls_first(self) -> bool:
        return self.ascending if self.nulls_first is None else self.nulls_first


# functions namespace -------------------------------------------------------

class functions:
    """spark.sql.functions equivalent surface."""

    col = staticmethod(col)
    lit = staticmethod(lit)

    @staticmethod
    def _agg(op, e, distinct=False):
        return ColumnExpr(op, (_wrap(e), distinct))

    @staticmethod
    def sum(e):
        return functions._agg("Sum", e)

    @staticmethod
    def percentile(e, p: float):
        """Exact percentile with linear interpolation (Spark's
        `percentile`).  No device rule exists — the aggregate falls back
        to the CPU executors, exactly like the reference (which ships no
        GPU Percentile rule in this era)."""
        return ColumnExpr("Percentile", (_wrap(e), False, float(p)))

    @staticmethod
    def avg(e):
        return functions._agg("Average", e)

    mean = avg

    @staticmethod
    def min(e):
        return functions._agg("Min", e)

    @staticmethod
    def max(e):
        return functions._agg("Max", e)

    @staticmethod
    def count(e):
        return functions._agg("Count", e)

    @staticmethod
    def count_distinct(e):
        return functions._agg("Count", e, distinct=True)

    @staticmethod
    def first(e):
        return functions._agg("First", e)

    @staticmethod
    def last(e):
        return functions._agg("Last", e)

    @staticmethod
    def grouping(c):
        """1 where grouping column `c` of the rollup or cube below is
        aggregated away in the row (a subtotal's NULL), 0 where the row
        groups by it (a NULL there is the data's); a byte, as in Spark.
        Resolved by plan/grouping.py; an analysis error anywhere else."""
        return ColumnExpr("Grouping", (col(c) if isinstance(c, str) else c,))

    @staticmethod
    def grouping_id():
        """The `grouping()` bits of all grouping columns of the rollup or
        cube below, the first column's the highest; a long."""
        return ColumnExpr("GroupingID", ())

    @staticmethod
    def when(cond, value):
        return WhenBuilder([(cond, _wrap(value))])

    @staticmethod
    def input_file_name():
        return ColumnExpr("InputFileName", ())

    @staticmethod
    def input_file_block_start():
        return ColumnExpr("InputFileBlockStart", ())

    @staticmethod
    def input_file_block_length():
        return ColumnExpr("InputFileBlockLength", ())

    @staticmethod
    def asinh(e):
        return ColumnExpr("Asinh", (_wrap(e),))

    @staticmethod
    def acosh(e):
        return ColumnExpr("Acosh", (_wrap(e),))

    @staticmethod
    def atanh(e):
        return ColumnExpr("Atanh", (_wrap(e),))

    @staticmethod
    def coalesce(*exprs):
        return ColumnExpr("Coalesce", tuple(_wrap(e) for e in exprs))

    @staticmethod
    def abs(e):
        return ColumnExpr("Abs", (_wrap(e),))

    @staticmethod
    def sqrt(e):
        return ColumnExpr("Sqrt", (_wrap(e),))

    @staticmethod
    def exp(e):
        return ColumnExpr("Exp", (_wrap(e),))

    @staticmethod
    def log(e):
        return ColumnExpr("Log", (_wrap(e),))

    @staticmethod
    def pow(a, b):
        return ColumnExpr("Pow", (_wrap(a), _wrap(b)))

    @staticmethod
    def floor(e):
        return ColumnExpr("Floor", (_wrap(e),))

    @staticmethod
    def ceil(e):
        return ColumnExpr("Ceil", (_wrap(e),))

    @staticmethod
    def upper(e):
        return ColumnExpr("Upper", (_wrap(e),))

    @staticmethod
    def lower(e):
        return ColumnExpr("Lower", (_wrap(e),))

    @staticmethod
    def length(e):
        return ColumnExpr("Length", (_wrap(e),))

    @staticmethod
    def substring(e, pos, length):
        return ColumnExpr("Substring", (_wrap(e), _wrap(pos), _wrap(length)))

    @staticmethod
    def concat(*exprs):
        return ColumnExpr("Concat", tuple(_wrap(e) for e in exprs))

    @staticmethod
    def year(e):
        return ColumnExpr("Year", (_wrap(e),))

    @staticmethod
    def month(e):
        return ColumnExpr("Month", (_wrap(e),))

    @staticmethod
    def dayofmonth(e):
        return ColumnExpr("DayOfMonth", (_wrap(e),))

    @staticmethod
    def hour(e):
        return ColumnExpr("Hour", (_wrap(e),))

    @staticmethod
    def minute(e):
        return ColumnExpr("Minute", (_wrap(e),))

    @staticmethod
    def second(e):
        return ColumnExpr("Second", (_wrap(e),))

    @staticmethod
    def to_date(e):
        return ColumnExpr("Cast", (_wrap(e), __import__(
            "spark_rapids_tpu.types", fromlist=["DateType"]).DateType))

    @staticmethod
    def date_add(e, days):
        return ColumnExpr("DateAdd", (_wrap(e), _wrap(days)))

    @staticmethod
    def date_sub(e, days):
        return ColumnExpr("DateSub", (_wrap(e), _wrap(days)))

    @staticmethod
    def datediff(end, start):
        return ColumnExpr("DateDiff", (_wrap(end), _wrap(start)))

    @staticmethod
    def isnan(e):
        return ColumnExpr("IsNaN", (_wrap(e),))

    @staticmethod
    def rand(seed=0):
        return ColumnExpr("Rand", (seed,))

    @staticmethod
    def spark_partition_id():
        return ColumnExpr("SparkPartitionID", ())

    @staticmethod
    def monotonically_increasing_id():
        return ColumnExpr("MonotonicallyIncreasingID", ())

    @staticmethod
    def row_number():
        return ColumnExpr("RowNumber", ())

    @staticmethod
    def rank():
        return ColumnExpr("Rank", ())

    @staticmethod
    def dense_rank():
        return ColumnExpr("DenseRank", ())

    @staticmethod
    def lag(e, offset: int = 1, default=None):
        return ColumnExpr("Lag", (_wrap(e), offset, default))

    @staticmethod
    def lead(e, offset: int = 1, default=None):
        return ColumnExpr("Lead", (_wrap(e), offset, default))

    @staticmethod
    def initcap(e):
        return ColumnExpr("InitCap", (_wrap(e),))

    @staticmethod
    def reverse(e):
        return ColumnExpr("Reverse", (_wrap(e),))

    @staticmethod
    def ascii(e):
        return ColumnExpr("Ascii", (_wrap(e),))

    @staticmethod
    def lpad(e, length, pad=" "):
        return ColumnExpr("StringLPad", (_wrap(e), _wrap(length),
                                         _wrap(pad)))

    @staticmethod
    def rpad(e, length, pad=" "):
        return ColumnExpr("StringRPad", (_wrap(e), _wrap(length),
                                         _wrap(pad)))

    @staticmethod
    def repeat(e, n):
        return ColumnExpr("StringRepeat", (_wrap(e), _wrap(n)))

    @staticmethod
    def substring_index(e, delim, count):
        return ColumnExpr("SubstringIndex", (_wrap(e), _wrap(delim),
                                             _wrap(count)))

    @staticmethod
    def regexp_replace(e, pattern, replacement):
        return ColumnExpr("RegExpReplace", (_wrap(e), _wrap(pattern),
                                            _wrap(replacement)))

    @staticmethod
    def round(e, scale=0):
        return ColumnExpr("Round", (_wrap(e), _wrap(scale)))

    @staticmethod
    def bround(e, scale=0):
        return ColumnExpr("BRound", (_wrap(e), _wrap(scale)))

    @staticmethod
    def hypot(a, b):
        return ColumnExpr("Hypot", (_wrap(a), _wrap(b)))

    @staticmethod
    def cot(e):
        return ColumnExpr("Cot", (_wrap(e),))

    @staticmethod
    def log_base(base, e):
        return ColumnExpr("Logarithm", (_wrap(base), _wrap(e)))

    @staticmethod
    def least(*exprs):
        return ColumnExpr("Least", tuple(_wrap(e) for e in exprs))

    @staticmethod
    def greatest(*exprs):
        return ColumnExpr("Greatest", tuple(_wrap(e) for e in exprs))

    @staticmethod
    def hash(*exprs):
        return ColumnExpr("Murmur3Hash", tuple(_wrap(e) for e in exprs))

    @staticmethod
    def add_months(e, n):
        return ColumnExpr("AddMonths", (_wrap(e), _wrap(n)))

    @staticmethod
    def months_between(a, b, round_off=True):
        return ColumnExpr("MonthsBetween", (_wrap(a), _wrap(b),
                                            _wrap(round_off)))

    @staticmethod
    def trunc(e, fmt):
        return ColumnExpr("TruncDate", (_wrap(e), _wrap(fmt)))

    @staticmethod
    def next_day(e, day_of_week):
        return ColumnExpr("NextDay", (_wrap(e), _wrap(day_of_week)))

    @staticmethod
    def explode(values):
        """Explode an array literal: one output row per element per input
        row (reference scope: GpuGenerateExec.scala:101+ supports
        explode/posexplode of array literals)."""
        return ColumnExpr("Explode", (list(values),))

    @staticmethod
    def posexplode(values):
        """Like explode, plus a 0-based position column."""
        return ColumnExpr("PosExplode", (list(values),))


class WindowSpec:
    """partition/order/frame spec (pyspark WindowSpec equivalent; reference:
    rapids/GpuWindowExpression.scala window spec mapping)."""

    def __init__(self, parts=(), orders=(), frame=None):
        self.parts = list(parts)        # partition-by ColumnExprs
        self.orders = list(orders)      # SortOrders
        # frame: None (Spark default) | ("rows", start, end)
        self.frame = frame

    def partition_by(self, *cols) -> "WindowSpec":
        return WindowSpec([c if isinstance(c, ColumnExpr) else col(c)
                           for c in cols], self.orders, self.frame)

    partitionBy = partition_by

    def order_by(self, *orders) -> "WindowSpec":
        os = []
        for o in orders:
            if isinstance(o, SortOrder):
                os.append(o)
            elif isinstance(o, str):
                os.append(SortOrder(col(o)))
            else:
                os.append(SortOrder(o))
        return WindowSpec(self.parts, os, self.frame)

    orderBy = order_by

    def rows_between(self, start: int, end: int) -> "WindowSpec":
        return WindowSpec(self.parts, self.orders,
                          ("rows", int(start), int(end)))

    rowsBetween = rows_between

    def _group_key(self):
        """Specs with the same partition/order can share one window node."""
        return (tuple(repr(c) for c in self.parts),
                tuple((repr(o.child), o.ascending, o.effective_nulls_first)
                      for o in self.orders))


class Window:
    """pyspark.sql.Window-compatible namespace."""

    unboundedPreceding = unbounded_preceding = -(1 << 62)
    unboundedFollowing = unbounded_following = (1 << 62)
    currentRow = current_row = 0

    @staticmethod
    def partition_by(*cols) -> WindowSpec:
        return WindowSpec().partition_by(*cols)

    partitionBy = partition_by

    @staticmethod
    def order_by(*orders) -> WindowSpec:
        return WindowSpec().order_by(*orders)

    orderBy = order_by


class WhenBuilder(ColumnExpr):
    def __init__(self, branches, otherwise=None):
        super().__init__("CaseWhen", (tuple(branches), otherwise))
        self.branches = branches
        self.otherwise_value = otherwise

    def when(self, cond, value):
        return WhenBuilder(self.branches + [(cond, _wrap(value))])

    def otherwise(self, value):
        return WhenBuilder(self.branches, _wrap(value))


# --------------------------------------------------------------------------
# logical plan nodes
# --------------------------------------------------------------------------

class LogicalPlan:
    children: Tuple["LogicalPlan", ...] = ()

    def __repr__(self):
        return type(self).__name__


class LogicalScan(LogicalPlan):
    """A data source: in-memory arrow table or a file scan."""

    def __init__(self, source, schema: Schema, fmt: str,
                 options: Optional[dict] = None):
        self.source = source      # pa.Table | list[str] paths
        self.schema = schema
        self.fmt = fmt            # "memory" | "parquet" | "csv" | "orc"
        self.options = options or {}


class LogicalProject(LogicalPlan):
    def __init__(self, exprs: Sequence[ColumnExpr], child: LogicalPlan):
        self.exprs = list(exprs)
        self.children = (child,)


class LogicalFilter(LogicalPlan):
    def __init__(self, condition: ColumnExpr, child: LogicalPlan):
        self.condition = condition
        self.children = (child,)


class LogicalAggregate(LogicalPlan):
    def __init__(self, grouping: Sequence[ColumnExpr],
                 aggregates: Sequence[ColumnExpr], child: LogicalPlan,
                 rollup_keys: Optional[List[str]] = None):
        self.grouping = list(grouping)
        self.aggregates = list(aggregates)
        self.children = (child,)
        # over a ROLLUP/CUBE Expand: the grouping columns in order, which
        # `_grouping_id` (the last grouping key) holds one bit each for;
        # what grouping() / grouping_id() resolve against (plan/grouping.py)
        self.rollup_keys = rollup_keys


class LogicalJoin(LogicalPlan):
    def __init__(self, left: LogicalPlan, right: LogicalPlan,
                 join_type: str, condition: Optional[ColumnExpr] = None,
                 using: Optional[List[str]] = None):
        self.join_type = join_type  # inner|left|right|left_semi|left_anti|cross|full
        self.condition = condition
        self.using = using
        self.children = (left, right)


class LogicalSort(LogicalPlan):
    def __init__(self, orders: Sequence[SortOrder], child: LogicalPlan):
        self.orders = [o if isinstance(o, SortOrder) else SortOrder(o)
                       for o in orders]
        self.children = (child,)


class LogicalLimit(LogicalPlan):
    def __init__(self, n: int, child: LogicalPlan):
        self.n = n
        self.children = (child,)


class LogicalUnion(LogicalPlan):
    def __init__(self, children: Sequence[LogicalPlan]):
        self.children = tuple(children)


class LogicalDistinct(LogicalPlan):
    def __init__(self, child: LogicalPlan):
        self.children = (child,)


class LogicalRepartition(LogicalPlan):
    def __init__(self, num_partitions: int, keys: Sequence[ColumnExpr],
                 child: LogicalPlan, mode: str = "hash",
                 ascending: Optional[Sequence[bool]] = None,
                 nulls_first: Optional[Sequence[bool]] = None):
        self.num_partitions = num_partitions
        self.keys = list(keys)
        self.mode = mode  # hash | round_robin | range | single
        self.ascending = list(ascending) if ascending is not None \
            else [True] * len(self.keys)
        self.nulls_first = list(nulls_first) if nulls_first is not None \
            else list(self.ascending)
        self.children = (child,)


class LogicalExpand(LogicalPlan):
    """ROLLUP/CUBE fan-out: list of projection lists."""

    def __init__(self, projections: Sequence[Sequence[ColumnExpr]],
                 child: LogicalPlan):
        self.projections = [list(p) for p in projections]
        self.children = (child,)


class LogicalGenerate(LogicalPlan):
    """Generator (explode/posexplode of an array literal) appended to the
    child's columns (Spark GenerateExec shape; reference:
    rapids/GpuGenerateExec.scala)."""

    def __init__(self, generator: ColumnExpr, names, child: LogicalPlan):
        self.generator = generator          # Explode | PosExplode ColumnExpr
        self.names = list(names)            # output column names (1 or 2)
        self.children = (child,)


class LogicalWindow(LogicalPlan):
    def __init__(self, window_exprs, partition_by, order_by, child):
        self.window_exprs = list(window_exprs)
        self.partition_by = list(partition_by)
        self.order_by = list(order_by)
        self.children = (child,)


class LogicalWrite(LogicalPlan):
    def __init__(self, path: str, fmt: str, child: LogicalPlan,
                 options: Optional[dict] = None,
                 partition_by: Optional[List[str]] = None):
        self.path = path
        self.fmt = fmt
        self.options = options or {}
        self.partition_by = partition_by or []
        self.children = (child,)


class LogicalPlaceholder(LogicalPlan):
    """Stage-input marker for SHIPPED plan fragments.

    The multi-process cluster driver (cluster.py) serializes a reduce-side
    fragment with this node where the shuffle feed attaches; the executing
    worker (shuffle/worker.py) swaps in an in-memory scan over the
    partitions it fetched.  The analogue of the shuffle-read RDD boundary
    in a serialized Spark task binary."""

    def __init__(self, schema: "Schema"):
        self.schema = schema
