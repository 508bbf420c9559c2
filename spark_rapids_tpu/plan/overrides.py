"""The overrides pass: tag -> explain -> convert.

Reference behavior being reproduced (structure, not code):
  * GpuOverrides rule tables keyed by operator class, each rule deriving a
    kill-switch conf `spark.rapids.sql.<kind>.<Name>`
    (reference: rapids/GpuOverrides.scala:66-258 rule framework,
     453-1705 rule tables)
  * RapidsMeta tagging tree: every plan/expression node gets a meta wrapper;
    tagging marks `willNotWorkOnTpu(reason)` bottom-up; `explain` prints the
    reasons; conversion swaps supported subtrees to device operators
    (reference: rapids/RapidsMeta.scala:173-196)
  * type gate (reference: GpuOverrides.isSupportedType:375-387)

The planner here goes logical plan -> physical ExecNode tree where each node
is either the Tpu* or Cpu* implementation; transitions.py then inserts
host<->device edges, coalesce nodes and fuses row-local chains.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .. import config as C
from ..config import TpuConf
from ..ops import expressions as E
from ..ops import math as M
from ..ops import strings as S
from ..ops import datetime_exprs as D
from ..ops.aggregates import AggregateExpression
from ..ops.cast import Cast, supported_cast
from ..types import (DataType, NullType, Schema, StructField, StringType,
                     SUPPORTED_TYPES, DoubleType, FloatType)
from . import logical as L
from .analysis import AnalysisError, resolve

# --------------------------------------------------------------------------
# expression rule table — class name -> optional extra tagger
# (the device implementation is the Expression.eval itself)
# --------------------------------------------------------------------------

def _tag_cast(meta: "ExprMeta", conf: TpuConf):
    e: Cast = meta.expr
    src, dst = e.child.dtype, e.to
    if not supported_cast(src, dst):
        meta.will_not_work(f"cast {src.name} to {dst.name} is not supported "
                           "on TPU")
        return
    if src.is_string and dst.is_floating \
            and not conf.get(C.ENABLE_CAST_STRING_TO_FLOAT):
        meta.will_not_work(
            "string to float casts can produce results different from Spark "
            "in corner cases; set "
            f"{C.ENABLE_CAST_STRING_TO_FLOAT.key}=true to enable")
    if src.is_floating and dst.is_string \
            and not conf.get(C.ENABLE_CAST_FLOAT_TO_STRING):
        meta.will_not_work(
            "float to string casts are formatted differently than Spark; set "
            f"{C.ENABLE_CAST_FLOAT_TO_STRING.key}=true to enable")
    if src.is_string and dst.name == "timestamp" \
            and not conf.get(C.ENABLE_CAST_STRING_TO_TIMESTAMP):
        meta.will_not_work(
            "string to timestamp casts only support a subset of formats; set "
            f"{C.ENABLE_CAST_STRING_TO_TIMESTAMP.key}=true to enable")


def _tag_literal_pattern(meta: "ExprMeta", conf: TpuConf):
    e = meta.expr
    pat = getattr(e, "pattern", None) or getattr(e, "search", None)
    if not (isinstance(pat, E.Literal) and isinstance(pat.value, str)):
        meta.will_not_work("only literal patterns are supported on TPU")


def _tag_replace(meta: "ExprMeta", conf: TpuConf):
    e: S.StringReplace = meta.expr
    if not e.device_supported():
        meta.will_not_work("device StringReplace requires equal-length "
                           "literal search/replace strings")


def _tag_agg(meta: "ExprMeta", conf: TpuConf):
    e: AggregateExpression = meta.expr
    if not conf.is_op_enabled(expr_conf_key(e.func)):
        # per-function kill-switch, like the reference's expr rules for
        # Sum/Count/Min/Max/Average/First/Last (GpuOverrides.scala)
        meta.will_not_work(
            f"aggregate {e.func} has been disabled; set "
            f"{expr_conf_key(e.func)}=true to enable")
    if e.distinct and e.func in ("First", "Last"):
        # value depends on arrival order after dedup; Spark itself plans
        # these as non-distinct — reject defensively
        meta.will_not_work(f"distinct {e.func} is not supported on TPU")
    if e.func in ("Min", "Max") and e.child is not None \
            and e.child.dtype.is_string:
        meta.will_not_work("min/max over strings is not supported on TPU "
                           "yet (byte-matrix segment reduction pending)")
    if e.func in ("Sum", "Average") and e.child is not None \
            and e.child.dtype.is_floating \
            and not (conf.get(C.VARIABLE_FLOAT_AGG)
                     or conf.get(C.INCOMPATIBLE_OPS)):
        meta.will_not_work(
            "floating point aggregation reduces in a different order than "
            f"Spark; set {C.VARIABLE_FLOAT_AGG.key}=true to enable")


_EXPR_RULES: Dict[str, Optional[Callable]] = {}
for _n in ("BoundReference Literal Alias Add Subtract Multiply Divide "
           "IntegralDivide Remainder Pmod UnaryMinus UnaryPositive Abs "
           "EqualTo LessThan GreaterThan LessThanOrEqual GreaterThanOrEqual "
           "EqualNullSafe And Or Not IsNull IsNotNull IsNaN Coalesce NaNvl "
           "If CaseWhen In InSet BitwiseAnd BitwiseOr BitwiseXor BitwiseNot "
           "ShiftLeft ShiftRight ShiftRightUnsigned SparkPartitionID "
           "MonotonicallyIncreasingID Rand "
           "Sqrt Cbrt Exp Expm1 Log Log2 Log10 Log1p Sin Cos Tan Asin Acos "
           "Atan Sinh Cosh Tanh ToDegrees ToRadians Signum Floor Ceil Rint "
           "Pow Atan2 "
           "Upper Lower Length StringTrim StringTrimLeft StringTrimRight "
           "Substring Concat "
           "Year Month DayOfMonth DayOfWeek WeekDay DayOfYear Quarter "
           "LastDay Hour Minute Second DateAdd DateSub DateDiff "
           "UnixTimestamp ToUnixTimestamp FromUnixTime TimeAdd").split():
    _EXPR_RULES[_n] = None
# plan-cache parameter (serve/plan_cache.py): evaluates like the Literal
# it replaced (broadcast scalar), device-supported unconditionally
_EXPR_RULES["Parameter"] = None
_EXPR_RULES["Cast"] = _tag_cast
_EXPR_RULES["AnsiCast"] = _tag_cast
_EXPR_RULES["StartsWith"] = _tag_literal_pattern
_EXPR_RULES["EndsWith"] = _tag_literal_pattern
_EXPR_RULES["Contains"] = _tag_literal_pattern
_EXPR_RULES["Like"] = _tag_literal_pattern
_EXPR_RULES["StringLocate"] = None
_EXPR_RULES["StringReplace"] = _tag_replace
_EXPR_RULES["AggregateExpression"] = _tag_agg


def _tag_device_supported(meta: "ExprMeta", conf: TpuConf):
    """Ops whose device kernel needs literal arguments (static shapes /
    compiled patterns) expose device_supported(); tag the rest to CPU."""
    e = meta.expr
    if hasattr(e, "device_supported") and not e.device_supported():
        meta.will_not_work(
            f"{meta.name} arguments are not supported on TPU "
            "(literal arguments with device-supported shapes required)")


for _n in ("InitCap Reverse Ascii Cot Hypot Logarithm Least Greatest "
           "Murmur3Hash AddMonths MonthsBetween "
           "Asinh Acosh Atanh AtLeastNNonNulls TimeSub "
           "NormalizeNaNAndZero KnownFloatingPointNormalized "
           "InputFileName InputFileBlockStart InputFileBlockLength "
           "AttributeReference SortOrder").split():
    _EXPR_RULES[_n] = None
# aggregate functions are registered by name like the reference's expr
# rules for Sum/Count/... (GpuOverrides.scala agg entries); the kill-switch
# conf check runs in _tag_agg against the AggregateExpression's func name
for _n in ("Sum Count Min Max Average First Last").split():
    _EXPR_RULES[_n] = None
# window functions: resolved via ops/windows.resolve_window_func (not the
# Expression tree), but registered here so the per-op kill-switch conf
# surface matches the reference's window rule table (GpuOverrides window
# expressions; the conf check runs in plan/tagging._tag_window)
for _n in ("RowNumber Rank DenseRank Lag Lead WindowExpression "
           "WindowSpecDefinition SpecifiedWindowFrame").split():
    _EXPR_RULES[_n] = None
for _n in ("StringLPad StringRPad StringRepeat SubstringIndex "
           "RegExpReplace Round BRound TruncDate NextDay").split():
    _EXPR_RULES[_n] = _tag_device_supported


def expr_conf_key(name: str) -> str:
    return f"spark.rapids.sql.expr.{name}"


def exec_conf_key(name: str) -> str:
    return f"spark.rapids.sql.exec.{name}"


# --------------------------------------------------------------------------
# meta tree
# --------------------------------------------------------------------------

class MetaBase:
    def __init__(self):
        self._reasons: List[str] = []

    def will_not_work(self, reason: str):
        if reason not in self._reasons:
            self._reasons.append(reason)

    @property
    def can_this_run(self) -> bool:
        return not self._reasons

    @property
    def reasons(self):
        return list(self._reasons)


class ExprMeta(MetaBase):
    def __init__(self, expr: E.Expression, conf: TpuConf):
        super().__init__()
        self.expr = expr
        self.conf = conf
        self.children = [ExprMeta(c, conf) for c in expr.children]

    @property
    def name(self) -> str:
        return type(self.expr).__name__

    def tag(self):
        for c in self.children:
            c.tag()
        name = self.name
        rule = _EXPR_RULES.get(name, "missing")
        if rule == "missing":
            self.will_not_work(f"expression {name} is not supported on TPU")
        else:
            dt = self.expr.dtype
            if dt is not NullType and dt not in SUPPORTED_TYPES:
                self.will_not_work(f"expression {name} produces an "
                                   f"unsupported type {dt.name}")
            if not self.conf.is_op_enabled(expr_conf_key(name)):
                self.will_not_work(
                    f"expression {name} has been disabled; set "
                    f"{expr_conf_key(name)}=true to enable")
            if rule is not None:
                rule(self, self.conf)

    @property
    def can_run_deep(self) -> bool:
        return self.can_this_run and all(c.can_run_deep
                                         for c in self.children)

    def all_reasons(self) -> List[str]:
        out = list(self._reasons)
        for c in self.children:
            out.extend(c.all_reasons())
        return out


class PlanMeta(MetaBase):
    """Meta wrapper for one logical node."""

    def __init__(self, plan: L.LogicalPlan, conf: TpuConf,
                 session=None):
        super().__init__()
        self.plan = plan
        self.conf = conf
        self.session = session
        self.children = [PlanMeta(c, conf, session) for c in plan.children]
        self.expr_metas: List[ExprMeta] = []
        self.resolved = {}     # stashed resolved expressions for conversion
        self.on_tpu = False

    @property
    def name(self) -> str:
        return _exec_name(self.plan)

    def input_schema(self, i=0) -> Schema:
        return plan_schema(self.children[i].plan, self.conf)

    def tag_tree(self):
        for c in self.children:
            c.tag_tree()
        if not self.conf.sql_enabled:
            self.will_not_work("TPU acceleration is disabled "
                               f"({C.SQL_ENABLED.key}=false)")
        if not self.conf.is_op_enabled(exec_conf_key(self.name)):
            self.will_not_work(f"exec {self.name} has been disabled; set "
                               f"{exec_conf_key(self.name)}=true to enable")
        try:
            self._tag_self()
        except AnalysisError as ex:
            raise
        except NotImplementedError as ex:
            self.will_not_work(str(ex))
        for em in self.expr_metas:
            em.tag()
            if not em.can_run_deep:
                for r in em.all_reasons():
                    self.will_not_work(r)
        self.on_tpu = self.can_this_run

    # -- per-node tagging+resolution --------------------------------------
    def _tag_self(self):
        from . import tagging
        tagging.tag_node(self)

    def explain(self, verbose: bool = False, indent: int = 0) -> str:
        mark = "*" if self.on_tpu else "!"
        line = " " * indent + f"{mark}{self.name}"
        if not self.on_tpu:
            why = "; ".join(self._reasons) or "child not on TPU"
            line += f" cannot run on TPU because {why}"
        lines = [line]
        for c in self.children:
            lines.append(c.explain(verbose, indent + 2))
        return "\n".join(lines)


_DISPLAY_NAMES = {
    L.LogicalProject: "ProjectExec",
    L.LogicalFilter: "FilterExec",
    L.LogicalAggregate: "HashAggregateExec",
    L.LogicalSort: "SortExec",
    L.LogicalLimit: "CollectLimitExec",
    L.LogicalUnion: "UnionExec",
    L.LogicalExpand: "ExpandExec",
    L.LogicalWindow: "WindowExec",
    L.LogicalGenerate: "GenerateExec",
    L.LogicalRepartition: "ShuffleExchangeExec",
    L.LogicalWrite: "DataWritingCommandExec",
    L.LogicalDistinct: "HashAggregateExec",
    L.LogicalScan: "FileSourceScanExec",
    L.LogicalJoin: "SortMergeJoinExec",
    # shipped-fragment stage input (cluster.py); swapped for a scan before
    # planning, but tagging/explain must still name it if one leaks through
    L.LogicalPlaceholder: "ShuffleQueryStageExec",
}


def _exec_name(plan: L.LogicalPlan) -> str:
    """Logical node -> reference exec-rule name (so conf keys match the
    reference's per-exec kill-switches)."""
    mapping = _DISPLAY_NAMES
    if isinstance(plan, L.LogicalScan):
        return {"memory": "LocalTableScanExec",
                "parquet": "FileSourceScanExec",
                "csv": "BatchScanExec",
                "orc": "FileSourceScanExec"}.get(plan.fmt,
                                                 "FileSourceScanExec")
    if isinstance(plan, L.LogicalJoin):
        return "SortMergeJoinExec"  # pre-conversion name; see tagging
    return mapping.get(type(plan), type(plan).__name__)


# schema computation --------------------------------------------------------

def plan_schema(plan: L.LogicalPlan, conf: TpuConf) -> Schema:
    s = getattr(plan, "_cached_schema", None)
    if s is None:
        s = _compute_schema(plan, conf)
        plan._cached_schema = s
    return s


def _compute_schema(plan: L.LogicalPlan, conf: TpuConf) -> Schema:
    if isinstance(plan, (L.LogicalScan, L.LogicalPlaceholder)):
        return plan.schema
    if isinstance(plan, L.LogicalProject):
        child = plan_schema(plan.children[0], conf)
        fields = []
        for ce in plan.exprs:
            ex = resolve(ce, child)
            fields.append(StructField(ce.output_name, ex.dtype))
        return Schema(fields)
    if isinstance(plan, L.LogicalAggregate):
        child = plan_schema(plan.children[0], conf)
        fields = []
        for ce in plan.grouping:
            ex = resolve(ce, child)
            fields.append(StructField(ce.output_name, ex.dtype))
        for ce in plan.aggregates:
            ex = resolve(ce, child)
            fields.append(StructField(ce.output_name, ex.dtype))
        return Schema(fields)
    if isinstance(plan, L.LogicalJoin):
        ls = plan_schema(plan.children[0], conf)
        rs = plan_schema(plan.children[1], conf)
        if plan.join_type in ("left_semi", "left_anti"):
            return ls
        if plan.using:
            rfields = [f for f in rs if f.name not in plan.using]
            return Schema(list(ls.fields) + rfields)
        return Schema(list(ls.fields) + list(rs.fields))
    if isinstance(plan, (L.LogicalFilter, L.LogicalSort, L.LogicalLimit,
                         L.LogicalDistinct, L.LogicalRepartition,
                         L.LogicalWrite)):
        return plan_schema(plan.children[0], conf)
    if isinstance(plan, L.LogicalUnion):
        return plan_schema(plan.children[0], conf)
    if isinstance(plan, L.LogicalExpand):
        child = plan_schema(plan.children[0], conf)
        fields = []
        for ce in plan.projections[0]:
            ex = resolve(ce, child)
            fields.append(StructField(ce.output_name, ex.dtype))
        return Schema(fields)
    if isinstance(plan, L.LogicalGenerate):
        from ..types import IntegerType
        from .analysis import _infer_value_dtype
        child = plan_schema(plan.children[0], conf)
        fields = list(child.fields)
        dtype = _infer_value_dtype(plan.generator.args[0]) or StringType
        if plan.generator.op == "PosExplode":
            fields.append(StructField(plan.names[0], IntegerType))
        fields.append(StructField(plan.names[-1], dtype))
        return Schema(fields)
    if isinstance(plan, L.LogicalWindow):
        from ..ops.windows import resolve_window_func
        child = plan_schema(plan.children[0], conf)
        fields = list(child.fields)
        for ce in plan.window_exprs:
            func_ce, spec = ce.args
            wf = resolve_window_func(func_ce, spec, child, resolve,
                                     device=False)
            fields.append(StructField(ce.output_name, wf.dtype))
        return Schema(fields)
    raise NotImplementedError(f"schema of {type(plan).__name__}")


# --------------------------------------------------------------------------
# generated supported-ops documentation
# --------------------------------------------------------------------------

_EXEC_DOC_ROWS = [
    ("ProjectExec", "expression projection; row-local stages fuse into one "
     "compiled kernel"),
    ("FilterExec", "predicates AND into the selection mask (no gather "
     "until a shape-changing op needs one)"),
    ("HashAggregateExec", "sort-based segmented reduction; ROLLUP/CUBE via "
     "ExpandExec; single-distinct; whole-stage vmapped path"),
    ("SortMergeJoinExec", "replaced by the device hash join: "
     "inner/left/right/full outer/left semi/left anti (right runs "
     "side-swapped under a column reorder); conditional joins for "
     "inner/semi/anti (residual evaluated pair-wise in the candidate "
     "walk); broadcast and partitioned (EnsureRequirements) variants; "
     "USING full joins fall back for Spark's coalesced-key "
     "contract"),
    ("SortExec", "order-preserving integer key encoding, one lexsort; "
     "external (partitioned) sort above the in-memory threshold"),
    ("WindowExec", "sort-once segmented-scan windows; external window"),
    ("ExpandExec", "grouping-set projections (ROLLUP/CUBE); "
     "`F.grouping(col)` and `F.grouping_id()` over them are bits of its "
     "grouping-id column, resolved when the DataFrame is built "
     "(plan/grouping.py), an analysis error outside a rollup or cube"),
    ("GenerateExec", "explode/posexplode"),
    ("UnionExec", "batch interleave"),
    ("CollectLimitExec", "device head-N"),
    ("ShuffleExchangeExec", "hash (murmur3 Spark-parity)/range/round-robin/"
     "single partitioners; device-resident shuffle"),
    ("DataWritingCommandExec", "parquet and ORC encode ON DEVICE "
     "(snappy/uncompressed parquet); CSV and dynamic partitions via the "
     "host arrow writer (the reference's GPU write formats are parquet/"
     "ORC only; CSV is read-only there too)"),
    ("FileSourceScanExec", "parquet/ORC device decode (see formats "
     "below); pushdown + schema evolution"),
    ("BatchScanExec", "CSV device parse (native quote-aware tokenizer + "
     "device gather/Horner kernels)"),
    ("LocalTableScanExec", "arrow/pydict ingestion"),
    ("BroadcastExchangeExec", "device broadcast for hash joins under the "
     "size threshold/hint"),
]


def supported_ops_doc() -> str:
    """docs/supported-ops.md content: execs, expression rules, formats —
    generated from the live rule registry (the reference generates its
    docs/supported_ops.md from GpuOverrides the same way)."""
    from ..types import SUPPORTED_TYPES
    lines = [
        "# Supported operators and expressions",
        "",
        "Generated from the rule registry "
        "(`python -m spark_rapids_tpu.plan.overrides`); do not edit.",
        "Counterpart: the reference's generated docs/supported_ops.md.",
        "",
        "## Types",
        "",
        "On-device columns: "
        + ", ".join(sorted(t.name for t in SUPPORTED_TYPES)) + ".",
        "Decimal/binary/calendar-interval/nested types keep the plan on "
        "the CPU executor (the reference's isSupportedType gate).",
        "",
        "## Execs",
        "",
        "Every exec has a kill-switch conf "
        "`spark.rapids.sql.exec.<name>`.",
        "",
        "| Exec | Device support |",
        "|---|---|",
    ]
    for name, note in _EXEC_DOC_ROWS:
        lines.append(f"| {name} | {note} |")
    lines += [
        "",
        "## Expressions",
        "",
        f"{len(_EXPR_RULES)} expression rules.  Every expression has a "
        "kill-switch conf `spark.rapids.sql.expr.<name>`.  Rules marked "
        "*conditional* run on device only for supported argument shapes "
        "(literal patterns, in-range pad widths, ...) and tag the plan "
        "back to CPU otherwise, with the reason shown by explain().",
        "",
        "| Expression | Device support |",
        "|---|---|",
    ]
    for name in sorted(_EXPR_RULES):
        tagger = _EXPR_RULES[name]
        if tagger is None:
            note = "supported"
        else:
            doc = (tagger.__doc__ or "").strip().split("\n")[0]
            note = f"conditional — {doc}" if doc else "conditional"
        lines.append(f"| {name} | {note} |")
    lines += [
        "",
        "## File formats",
        "",
        "| Format | Read | Write |",
        "|---|---|---|",
        "| Parquet | device decode: PLAIN, RLE/PLAIN_DICTIONARY (incl. "
        "strings), DELTA_BINARY_PACKED, DELTA_LENGTH_BYTE_ARRAY, "
        "BYTE_STREAM_SPLIT, PLAIN BYTE_ARRAY strings; page v1/v2; "
        "row-group pruning | device encode (snappy/uncompressed) |",
        "| ORC | device decode: full RLEv2 (SHORT_REPEAT/DIRECT/DELTA/"
        "PATCHED_BASE on device), strings (DIRECT_V2 + DICTIONARY_V2), "
        "timestamps, booleans; stripe pruning from footer statistics | "
        "device encode (uncompressed, RLEv1/DIRECT) |",
        "| CSV | device parse (native tokenizer incl. quoted fields and "
        "CRLF; device gather + Horner numeric kernels) | host arrow "
        "writer (reference parity: GPU CSV is read-only there) |",
        "",
    ]
    return "\n".join(lines)


def write_supported_ops_docs(path: str = None) -> str:
    import os
    if path is None:
        path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "docs", "supported-ops.md")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(supported_ops_doc())
    return path


if __name__ == "__main__":  # python -m spark_rapids_tpu.plan.overrides
    print(write_supported_ops_docs())
