"""tpulint framework core: pass SPI, suppressions, baseline, runner.

Design (mirrors the shape of real project linters — pylint's per-message
ids + inline pragmas + a checked-in known-issues file — scaled down to
exactly what this tree needs):

  * every finding carries a stable rule id (TPU001..TPU007) so it can be
    suppressed PRECISELY, never wholesale;
  * inline suppressions are `# tpulint: disable=TPU006 <reason>` on the
    finding's line (or the line above, or anywhere inside the finding's
    span for multi-line constructs like except handlers).  A suppression
    WITHOUT a reason does not suppress — it is itself reported (TPU000) —
    so every silenced finding documents why;
  * the baseline file (lint/baseline.json) grandfathers pre-existing
    findings per (rule, file) with a count and a mandatory reason.  New
    findings in a baselined file fail (count exceeded); fixing findings
    makes the entry stale, which is reported as a warning nudging the
    entry down.  Counts instead of line numbers keep the baseline stable
    across unrelated edits to the same file;
  * passes are per-file AST visitors plus an optional cross-file
    `finalize` hook (conf-vs-docs drift, lock-graph cycles, sweep-list
    coverage need the whole project).

Exit codes: 0 clean, 1 findings, 2 usage/internal error (__main__).
"""
from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

#: rule id of the meta-pass: malformed suppressions / baseline entries
META_RULE = "TPU000"

_SUPPRESS_RE = re.compile(
    r"#\s*tpulint:\s*disable=([A-Za-z0-9_,]+)[ \t]*(.*?)\s*$")


@dataclass
class Finding:
    rule: str
    path: str          # repo-relative, stable across machines
    line: int
    message: str
    #: last line of the construct (multi-line suppression window);
    #: defaults to `line`
    span_end: int = 0
    #: annotation filled by the runner ("baselined"/"suppressed")
    status: str = ""

    def __post_init__(self):
        if not self.span_end:
            self.span_end = self.line

    def key(self) -> Tuple[str, str]:
        return (self.rule, self.path)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"

    def to_json(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message}


class FileContext:
    """One parsed source file handed to every pass.

    `tree` may be handed in as None (incremental-cache hit): the property
    parses LAZILY on first access, so a warm run whose per-file findings
    all replay from the cache never pays the parse — only files a
    cross-file finalizer actually inspects (the contract files) do."""

    def __init__(self, path: str, rel_path: str, text: str,
                 tree: Optional[ast.Module], scope: str):
        self.path = path
        self.rel_path = rel_path
        self.text = text
        self.lines = text.splitlines()
        self._tree = tree
        #: "package" for spark_rapids_tpu/ sources, "aux" for tests/,
        #: bench and scripts — passes pick the scopes they police
        self.scope = scope
        #: line -> set of rule ids suppressed there ({"all"} allowed)
        self.suppressions: Dict[int, Set[str]] = {}
        #: (line, rule ids) of suppressions missing a reason: honored
        #: NOT — reported instead, naming the nearest rule doc
        self.bad_suppressions: List[Tuple[int, Tuple[str, ...]]] = []
        self._parse_suppressions()

    @property
    def tree(self) -> ast.Module:
        if self._tree is None:
            self._tree = ast.parse(self.text, filename=self.path)
        return self._tree

    def _parse_suppressions(self) -> None:
        for i, line in enumerate(self.lines, start=1):
            if "tpulint" not in line:
                continue
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            reason = m.group(2).strip()
            if not reason:
                self.bad_suppressions.append((i, tuple(sorted(rules))))
                continue
            self.suppressions.setdefault(i, set()).update(rules)

    def is_suppressed(self, finding: Finding) -> bool:
        """A suppression anywhere in [line-1, span_end] window matches —
        comment-above, same-line, and inside-the-block styles all work."""
        for ln in range(finding.line - 1, finding.span_end + 1):
            rules = self.suppressions.get(ln)
            if rules and (finding.rule in rules or "all" in rules):
                return True
        return False


class LintPass:
    """SPI: subclass, set rule_id/name/doc, implement check_file and/or
    finalize.  One instance lives for one lint run, so cross-file state
    accumulated in check_file is readable in finalize.

    Incremental-cache contract (lint/cache.py): a pass marked
    `cacheable` promises its check_file findings are a pure function of
    the file bytes (given the contract files pinned in the cache salt).
    A pass that also accumulates per-file CROSS-file state returns it
    from `file_fragment(ctx)` (picklable) and re-absorbs it on warm runs
    via `absorb_fragment` — so a cache hit skips the AST walk but the
    finalizer still sees every file's contribution.  `needs_model = True`
    asks the runner to link the ProjectModel (lint/model.py) before
    finalize; it is exposed as `project.model`."""

    rule_id: str = "TPU9XX"
    name: str = "unnamed"
    doc: str = ""
    #: which file scopes this pass polices
    scopes: Tuple[str, ...] = ("package",)
    #: check_file findings + file_fragment are content-pure -> cacheable
    cacheable: bool = False
    #: runner must build/link the cross-module ProjectModel
    needs_model: bool = False

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        return ()

    def file_fragment(self, ctx: FileContext):
        """Picklable per-file cross-file state (None = none).  Called
        after check_file on cold files; the cache replays it into
        absorb_fragment on warm runs."""
        return None

    def absorb_fragment(self, rel_path: str, fragment) -> None:
        """Re-absorb a cached file_fragment (no-op default)."""

    def finalize(self, project: "Project") -> Iterable[Finding]:
        return ()


@dataclass
class Project:
    root: str
    files: List[FileContext] = field(default_factory=list)
    #: linked cross-module model (lint/model.py), present when any
    #: active pass sets needs_model
    model: object = None

    def file(self, rel_path: str) -> Optional[FileContext]:
        for ctx in self.files:
            if ctx.rel_path == rel_path:
                return ctx
        return None


class Baseline:
    """Checked-in grandfathered findings: (rule, path) -> (count, reason).
    Every entry MUST carry a reason; a reasonless entry is a TPU000
    finding, not a silent grant."""

    def __init__(self, entries: Sequence[dict], origin: str = "baseline"):
        self.origin = origin
        self.grants: Dict[Tuple[str, str], int] = {}
        self.reasons: Dict[Tuple[str, str], str] = {}
        self.errors: List[Finding] = []
        for i, e in enumerate(entries):
            rule, path = e.get("rule", ""), e.get("path", "")
            count = int(e.get("count", 0))
            reason = str(e.get("reason", "")).strip()
            key = (rule, path)
            if not rule or not path or count <= 0 or not reason:
                self.errors.append(Finding(
                    META_RULE, origin, i + 1,
                    f"baseline entry {i} for {rule or '?'}:{path or '?'} "
                    f"needs rule, path, count>0 and a non-empty reason"))
                continue
            if key in self.grants:
                self.errors.append(Finding(
                    META_RULE, origin, i + 1,
                    f"duplicate baseline entry for {rule}:{path}"))
                continue
            self.grants[key] = count
            self.reasons[key] = reason

    @classmethod
    def load(cls, path: str) -> "Baseline":
        with open(path) as f:
            data = json.load(f)
        rel = os.path.basename(path)
        return cls(data.get("entries", []), origin=rel)

    def apply(self, findings: List[Finding],
              active_rules: Optional[Set[str]] = None,
              present_paths: Optional[Set[str]] = None
              ) -> Tuple[List[Finding], List[Finding], List[str]]:
        """Split into (reported, baselined, stale-entry warnings): the
        first `count` findings per (rule, path) — in line order — are
        grandfathered, the excess is reported.  Staleness is only judged
        for rules in `active_rules` (None = all): a --rules subset run
        must not claim grants for passes that never ran are unused.
        `present_paths` (the full-surface file set, when known) upgrades
        the message for grants whose file is GONE — those entries are
        dead weight and should be pruned outright."""
        by_key: Dict[Tuple[str, str], List[Finding]] = {}
        for f in findings:
            by_key.setdefault(f.key(), []).append(f)
        reported: List[Finding] = []
        baselined: List[Finding] = []
        stale: List[str] = []
        for key, group in by_key.items():
            group.sort(key=lambda f: f.line)
            grant = self.grants.get(key, 0)
            for f in group[:grant]:
                f.status = "baselined"
                baselined.append(f)
            reported.extend(group[grant:])
        for key, grant in self.grants.items():
            if active_rules is not None and key[0] not in active_rules:
                continue
            n = len(by_key.get(key, []))
            if n < grant:
                if present_paths is not None \
                        and key[1] not in present_paths:
                    stale.append(
                        f"{key[1]}: baseline grants {grant} x {key[0]} "
                        "but the file no longer exists — prune the entry")
                else:
                    stale.append(
                        f"{key[1]}: baseline grants {grant} x {key[0]} "
                        f"but only {n} remain — lower the entry")
        return reported, baselined, stale


@dataclass
class LintResult:
    findings: List[Finding]          # unsuppressed, the failure set
    baselined: List[Finding]
    suppressed: List[Finding]
    stale_baseline: List[str]
    files_checked: int
    cache_hits: int = 0
    cache_misses: int = 0
    elapsed_s: float = 0.0

    @property
    def exit_code(self) -> int:
        return 1 if self.findings else 0


def repo_root() -> str:
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.dirname(pkg)


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def _scope_of(rel_path: str) -> str:
    first = rel_path.replace(os.sep, "/").split("/", 1)[0]
    return "package" if first == "spark_rapids_tpu" else "aux"


def discover_files(paths: Sequence[str], root: str) -> List[str]:
    out: List[str] = []
    for p in paths:
        p = os.path.abspath(p)
        if os.path.isfile(p) and p.endswith(".py"):
            out.append(p)
            continue
        for dirpath, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs
                       if d not in ("__pycache__", ".git", "node_modules")]
            for fn in sorted(files):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    # stable order, no duplicates (overlapping path args)
    return sorted(dict.fromkeys(out))


def default_paths(root: str) -> List[str]:
    """The project surface the standing gate covers: the package, the
    test suite, the query packages and scripts."""
    cands = [os.path.join(root, "spark_rapids_tpu"),
             os.path.join(root, "tests"),
             os.path.join(root, "benchmarks"),
             os.path.join(root, "scripts")]
    return [c for c in cands if os.path.exists(c)]


def lint_paths(paths: Optional[Sequence[str]] = None,
               rules: Optional[Sequence[str]] = None,
               baseline: Optional[Baseline] = None,
               baseline_path: Optional[str] = None,
               root: Optional[str] = None,
               passes: Optional[Sequence[LintPass]] = None,
               use_cache: bool = False) -> LintResult:
    """Run the framework: parse every file once (or replay it from the
    incremental cache when `use_cache` and the content hash matches), run
    each pass over it, link the cross-module project model, run the
    cross-file finalizers, then the suppression + baseline filters."""
    import time as _time
    from .passes import ALL_PASSES
    t0 = _time.perf_counter()
    root = root or repo_root()
    if rules is not None:
        known = {cls.rule_id for cls in ALL_PASSES}
        unknown = [r for r in rules if r not in known]
        if unknown:
            # a typo'd --rules filter must ERROR, not run zero passes
            # and report a green no-op gate
            raise ValueError(
                f"unknown tpulint rule(s) {', '.join(unknown)}; "
                f"known: {', '.join(sorted(known))}")
    if passes is None:
        passes = [cls() for cls in ALL_PASSES
                  if rules is None or cls.rule_id in rules]
    if baseline is None:
        bp = baseline_path if baseline_path is not None \
            else default_baseline_path()
        baseline = Baseline.load(bp) if bp and os.path.exists(bp) \
            else Baseline([])
    cache = None
    if use_cache:
        from .cache import LintCache
        cache = LintCache(root, enabled=True)
    want_model = any(getattr(p, "needs_model", False) for p in passes)
    cacheable_rules = {p.rule_id for p in passes
                       if getattr(p, "cacheable", False)}
    project = Project(root=root)
    fragments = []
    raw: List[Finding] = []
    raw.extend(baseline.errors)
    file_list = discover_files(paths or default_paths(root), root)
    for path in file_list:
        rel = os.path.relpath(path, root)
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
        except OSError as e:
            raw.append(Finding(META_RULE, rel, 1, f"cannot parse: {e}"))
            continue
        entry = None
        key = None
        if cache is not None:
            key = cache.key_for(text, rel)
            entry = cache.load(key)
            if entry is not None and not cacheable_rules <= set(
                    entry.get("rules", ())):
                # cached under a different pass subset: treat as a miss
                cache.hits -= 1
                cache.misses += 1
                entry = None
        if entry is not None:
            # warm path: findings + fragments replay; the tree stays
            # unparsed unless a finalizer asks for it
            ctx = FileContext(path, rel, text, None, _scope_of(rel))
            project.files.append(ctx)
            _report_bad_suppressions(ctx, raw)
            for p in passes:
                if ctx.scope not in p.scopes:
                    continue
                rec = entry["rules"].get(p.rule_id) \
                    if getattr(p, "cacheable", False) else None
                if rec is not None:
                    raw.extend(Finding(**d) for d in rec["findings"])
                    if rec["fragment"] is not None:
                        p.absorb_fragment(rel, rec["fragment"])
                else:
                    raw.extend(p.check_file(ctx))
            if want_model:
                fragments.append(entry["model"])
            continue
        try:
            tree = ast.parse(text, filename=path)
        except SyntaxError as e:
            raw.append(Finding(META_RULE, rel, getattr(e, "lineno", 1) or 1,
                               f"cannot parse: {e}"))
            continue
        ctx = FileContext(path, rel, text, tree, _scope_of(rel))
        project.files.append(ctx)
        _report_bad_suppressions(ctx, raw)
        rules_rec: Dict[str, dict] = {}
        for p in passes:
            file_findings: List[Finding] = []
            if ctx.scope in p.scopes:
                file_findings = list(p.check_file(ctx))
                raw.extend(file_findings)
            if getattr(p, "cacheable", False):
                frag = p.file_fragment(ctx) if ctx.scope in p.scopes \
                    else None
                rules_rec[p.rule_id] = {
                    "findings": [dict(rule=f.rule, path=f.path,
                                      line=f.line, message=f.message,
                                      span_end=f.span_end)
                                 for f in file_findings],
                    "fragment": frag}
        model_frag = None
        if want_model or cache is not None:
            from .model import extract_module
            model_frag = extract_module(rel, tree)
        if want_model:
            fragments.append(model_frag)
        if cache is not None and key is not None:
            cache.store(key, {"rules": rules_rec, "model": model_frag})
    if want_model:
        from .model import ProjectModel
        project.model = ProjectModel.link(fragments)
    for p in passes:
        raw.extend(p.finalize(project))
    # suppression filter (line-window pragmas), then baseline filter
    ctx_by_rel = {c.rel_path: c for c in project.files}
    unsuppressed: List[Finding] = []
    suppressed: List[Finding] = []
    for f in raw:
        ctx = ctx_by_rel.get(f.path)
        if f.rule != META_RULE and ctx is not None \
                and ctx.is_suppressed(f):
            f.status = "suppressed"
            suppressed.append(f)
        else:
            unsuppressed.append(f)
    active_rules = {p.rule_id for p in passes} | {META_RULE}
    # only a full-surface run can distinguish "file removed" from "file
    # outside the linted subset"
    present = set(ctx_by_rel) if paths is None else None
    reported, baselined, stale = baseline.apply(unsuppressed,
                                                active_rules=active_rules,
                                                present_paths=present)
    reported.sort(key=lambda f: (f.path, f.line, f.rule))
    elapsed = _time.perf_counter() - t0
    if cache is not None:
        if paths is None:
            # only a full-surface run may prune: a subset run's _live
            # set would otherwise delete every other file's entry
            cache.prune()
        cache.record_run(elapsed, len(project.files))
    return LintResult(findings=reported, baselined=baselined,
                      suppressed=suppressed, stale_baseline=stale,
                      files_checked=len(project.files),
                      cache_hits=cache.hits if cache else 0,
                      cache_misses=cache.misses if cache else 0,
                      elapsed_s=elapsed)


def _report_bad_suppressions(ctx: FileContext, raw: List[Finding]) -> None:
    for ln, rule_ids in ctx.bad_suppressions:
        which = ", ".join(rule_ids) or "TPUxxx"
        # `disable=all` has no rule id to cite: fall back to a real one
        ref = next((r for r in rule_ids if r.startswith("TPU")), "TPU001")
        raw.append(Finding(
            META_RULE, ctx.rel_path, ln,
            f"tpulint suppression of {which} without a reason (write "
            f"`# tpulint: disable={which} <why>`); not honored — rule "
            f"reference: docs/lint.md, or `python -m spark_rapids_tpu"
            f".lint --explain {ref}`"))


# -- rendering ---------------------------------------------------------------

def render_text(result: LintResult, verbose: bool = False) -> str:
    lines = [f.render() for f in result.findings]
    for s in result.stale_baseline:
        lines.append(f"warning: stale baseline: {s}")
    lines.append(
        f"tpulint: {len(result.findings)} finding(s), "
        f"{len(result.baselined)} baselined, "
        f"{len(result.suppressed)} suppressed, "
        f"{result.files_checked} files")
    if verbose:
        for f in result.baselined:
            lines.append(f"baselined: {f.render()}")
        for f in result.suppressed:
            lines.append(f"suppressed: {f.render()}")
    return "\n".join(lines)


def render_json(result: LintResult) -> str:
    return json.dumps({
        "findings": [f.to_json() for f in result.findings],
        "baselined": [f.to_json() for f in result.baselined],
        "suppressed": [f.to_json() for f in result.suppressed],
        "stale_baseline": result.stale_baseline,
        "files_checked": result.files_checked,
        "exit_code": result.exit_code,
    }, indent=2)
