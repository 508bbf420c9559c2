"""tpulint framework + per-rule fixture tests (ISSUE 9).

Three layers:
  * framework mechanics: suppressions need reasons, the baseline grants
    exact counts with mandatory reasons, stale entries warn;
  * per-rule fixtures: every pass TPU001..TPU011 proves one true
    positive AND one clean negative on synthetic project trees (the
    ISSUE-12 cross-module passes get dataflow/call-graph fixtures plus
    a project-model unit tier and incremental-cache replay tests);
  * the self-run: the real repo lints to ZERO unsuppressed findings
    (the acceptance gate every later PR inherits), and the back-compat
    `python -m spark_rapids_tpu.metrics --lint` alias still answers.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from spark_rapids_tpu.config import help_doc
from spark_rapids_tpu.lint.core import (Baseline, Finding, lint_paths,
                                        render_json, render_text,
                                        repo_root)

pytestmark = pytest.mark.lint


def run_fixture(tmp_path, files, rules=None, baseline=None, passes=None):
    """Write a synthetic project and lint it.  Package files go under
    spark_rapids_tpu/ so package-scoped passes see them; a generated
    docs/configs.md keeps TPU003's finalize quiet unless a fixture
    deliberately breaks it."""
    root = str(tmp_path)
    for rel, text in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(textwrap.dedent(text))
    docs = os.path.join(root, "docs", "configs.md")
    if not os.path.exists(docs):
        os.makedirs(os.path.dirname(docs), exist_ok=True)
        with open(docs, "w") as f:
            f.write(help_doc())
    return lint_paths(paths=[root], rules=rules, root=root,
                      baseline=baseline if baseline is not None
                      else Baseline([]), passes=passes)


def rules_of(result):
    return [f.rule for f in result.findings]


# --------------------------------------------------------------------------
# framework mechanics
# --------------------------------------------------------------------------

def test_suppression_with_reason_silences(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        def f(x):
            return x.item()  # tpulint: disable=TPU001 benchmark readback, one per query
    """}, rules=["TPU001"])
    assert res.findings == []
    assert len(res.suppressed) == 1
    assert res.suppressed[0].rule == "TPU001"


def test_suppression_on_line_above_works(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        def f(x):
            # tpulint: disable=TPU001 readback at the result boundary
            return x.item()
    """}, rules=["TPU001"])
    assert res.findings == []
    assert len(res.suppressed) == 1


def test_suppression_without_reason_is_reported_and_ignored(tmp_path):
    # the reasonless pragma is assembled by concatenation so the repo
    # self-run does not see it as a bad suppression of THIS file
    src = ("def f(x):\n"
           "    return x.item()  # tpulint: " "disable=TPU001\n")
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": src},
                      rules=["TPU001"])
    assert sorted(rules_of(res)) == ["TPU000", "TPU001"]


def test_baseline_grants_exact_count(tmp_path):
    files = {"spark_rapids_tpu/m.py": """
        def f(x, y):
            return x.item() + y.item()
    """}
    grant2 = Baseline([{"rule": "TPU001", "path": "spark_rapids_tpu/m.py",
                        "count": 2, "reason": "legacy readbacks"}])
    res = run_fixture(tmp_path, files, rules=["TPU001"], baseline=grant2)
    assert res.findings == [] and len(res.baselined) == 2
    grant1 = Baseline([{"rule": "TPU001", "path": "spark_rapids_tpu/m.py",
                        "count": 1, "reason": "legacy readback"}])
    res = run_fixture(tmp_path, files, rules=["TPU001"], baseline=grant1)
    assert rules_of(res) == ["TPU001"] and len(res.baselined) == 1


def test_baseline_entry_requires_reason():
    b = Baseline([{"rule": "TPU001", "path": "x.py", "count": 1,
                   "reason": ""}])
    assert b.errors and b.errors[0].rule == "TPU000"
    assert b.grants == {}


def test_stale_baseline_entry_warns(tmp_path):
    stale = Baseline([{"rule": "TPU001", "path": "spark_rapids_tpu/m.py",
                       "count": 3, "reason": "was three, one fixed"}])
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        def f(x):
            return x.item()
    """}, rules=["TPU001"], baseline=stale)
    assert res.findings == []
    assert len(res.stale_baseline) == 1
    assert "grants 3" in res.stale_baseline[0]
    assert "stale baseline" in render_text(res)


def test_repo_baseline_file_entries_all_carry_reasons():
    path = os.path.join(repo_root(), "spark_rapids_tpu", "lint",
                        "baseline.json")
    with open(path) as f:
        data = json.load(f)
    assert data["entries"], "repo baseline unexpectedly empty"
    for e in data["entries"]:
        assert e.get("reason", "").strip(), f"reasonless entry: {e}"
    assert not Baseline(data["entries"]).errors


def test_render_json_shape(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        def f(x):
            return x.item()
    """}, rules=["TPU001"])
    payload = json.loads(render_json(res))
    assert payload["exit_code"] == 1
    assert payload["findings"][0]["rule"] == "TPU001"
    assert payload["findings"][0]["path"] == "spark_rapids_tpu/m.py"


# --------------------------------------------------------------------------
# TPU001 — host-sync hazards
# --------------------------------------------------------------------------

def test_tpu001_flags_item_asarray_devget_and_coercion(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def f(x):
            a = x.item()
            b = np.asarray(x)
            c = jax.device_get(x)
            d = int(jnp.sum(x))
            return a, b, c, d
    """}, rules=["TPU001"])
    assert rules_of(res) == ["TPU001"] * 4


def test_tpu001_clean_negative_and_allowlisted_path(tmp_path):
    res = run_fixture(tmp_path, {
        # jnp.asarray is device-side; int() over host values is fine
        "spark_rapids_tpu/m.py": """
            import jax.numpy as jnp

            def f(x, n):
                return jnp.asarray(x) + int(n)
        """,
        # the io/ layer is allowlisted: host decode is its job
        "spark_rapids_tpu/io/reader.py": """
            import numpy as np

            def decode(buf):
                return np.asarray(buf).item()
        """}, rules=["TPU001"])
    assert res.findings == []


# --------------------------------------------------------------------------
# TPU002 — jit purity
# --------------------------------------------------------------------------

def test_tpu002_flags_impure_call_and_traced_branch(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import time
        import jax

        def make():
            def kern(a, b):
                t = time.time()
                if a > 0:
                    return b + t
                return b
            return jax.jit(kern)
    """}, rules=["TPU002"])
    msgs = [f.message for f in res.findings]
    assert any("impure call time.time" in m for m in msgs)
    assert any("branch on traced value 'a'" in m for m in msgs)


def test_tpu002_builder_pattern_and_stage_executable(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import random
        from .kernel_cache import cached_kernel, stage_executable

        def plan(key, args):
            def builder():
                def kern(x):
                    return x * random.random()
                return kern
            cached_kernel(key, builder)
            stage_executable(key, builder, args)
    """}, rules=["TPU002"])
    # the builder-returned kernel is analyzed once per sink resolution
    assert all(f.rule == "TPU002" for f in res.findings)
    assert any("random.random" in f.message for f in res.findings)


def test_tpu002_mixed_static_and_value_branch_still_flags(tmp_path):
    """`if v.ndim == 2 and v:` — the static .ndim subexpression must not
    whitelist the bare traced `v` in the same test."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import jax

        def make():
            def kern(v):
                if v.ndim == 2 and v:
                    return v
                return v
            return jax.jit(kern)
    """}, rules=["TPU002"])
    assert any("branch on traced value 'v'" in f.message
               for f in res.findings)


def test_tpu002_shard_map_body_resolved(tmp_path):
    """ISSUE 14: `shard_map(step, ...)` program bodies are jit sinks —
    collective kernels get linted, not baselined."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import time
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def exchange_step(mesh, axis):
            def step(local, start):
                t = time.time()
                if start > 0:
                    return local + t
                return local
            return shard_map(step, mesh=mesh, in_specs=(P(axis), P()),
                             out_specs=P(axis))
    """}, rules=["TPU002"])
    msgs = [f.message for f in res.findings]
    assert any("impure call time.time" in m for m in msgs)
    assert any("branch on traced value 'start'" in m for m in msgs)


def test_tpu002_clean_shard_map_negative(tmp_path):
    """Closure-variable branches (quota knobs, mode switches) inside a
    shard_map body are static trace-time dispatch, not traced-value
    branching — the real collective programs' shape."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def exchange_step(mesh, axis, use_allgather, pre=None):
            def step(local, start):
                if pre is not None:
                    local = pre(local)
                if use_allgather:
                    return local
                return local + start
            return shard_map(step, mesh=mesh, in_specs=(P(axis), P()),
                             out_specs=P(axis))
    """}, rules=["TPU002"])
    assert res.findings == []


def test_tpu002_clean_negative_shape_branch_ok(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import time
        import jax
        import jax.numpy as jnp

        def host_side():
            return time.time()  # impure, but never traced

        def make():
            def kern(a):
                if a.shape[0] > 4:  # shape polymorphism: static
                    return jnp.sum(a)
                return a
            return jax.jit(kern)
    """}, rules=["TPU002"])
    assert res.findings == []


# --------------------------------------------------------------------------
# TPU003 — conf hygiene
# --------------------------------------------------------------------------

def test_tpu003_flags_unknown_key_everywhere(tmp_path):
    res = run_fixture(tmp_path, {
        "spark_rapids_tpu/m.py": """
            def f(conf):
                return conf.get("spark.rapids.sql.tpu.notAReal.key")
        """,
        "tests/test_x.py": """
            CONF = {"spark.rapids.sql.batchSizeByte": "1"}
        """}, rules=["TPU003"])
    assert rules_of(res) == ["TPU003", "TPU003"]


def test_tpu003_clean_negative_registered_derived_prefix(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        KEYS = ("spark.rapids.sql.enabled",
                "spark.rapids.sql.exec.SortExec",
                "spark.rapids.sql.expr.Add",
                "spark.rapids.sql.tpu.adaptive.skewJoin.")
    """}, rules=["TPU003"])
    assert res.findings == []


def test_tpu003_docs_drift_finalize(tmp_path):
    # a docs/configs.md missing a registered key fails the doc half
    res = run_fixture(tmp_path, {
        "spark_rapids_tpu/m.py": "X = 1\n",
        "docs/configs.md": "# configs\nnothing here\n",
    }, rules=["TPU003"])
    assert res.findings
    assert all(f.path == "docs/configs.md" for f in res.findings)


# --------------------------------------------------------------------------
# TPU004 — metric/journal contracts
# --------------------------------------------------------------------------

def test_tpu004_flags_unregistered_metric_retry_block_and_kind(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .journal import journal_event

        def f(ctx, metrics, run_retryable):
            metrics.add("numOutputRowz", 1)
            run_retryable(ctx, metrics, "notABlock", None, [])
            journal_event("notakind", "x")
    """}, rules=["TPU004"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "numOutputRowz" in msgs
    assert "notABlockRetries" in msgs
    assert "notakind" in msgs


def test_tpu004_clean_negative(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .journal import journal_event

        def f(ctx, metrics, run_retryable, tags):
            metrics.add("numOutputRows", 1)
            with metrics.timer("totalTime"):
                pass  # tpulint: disable=TPU006 fixture body
            run_retryable(ctx, metrics, "sort", None, [])
            journal_event("retry", "x")
            tags.add("not a metric name")  # spaces: not an emission site
    """}, rules=["TPU004"])
    assert res.findings == []


# --------------------------------------------------------------------------
# TPU005 — retry-site sweep coverage
# --------------------------------------------------------------------------

_SWEEP_TEST = """
    OOM_SWEEP_SITES = ({sites})
"""


def test_tpu005_uncovered_site_and_stale_entry(tmp_path):
    res = run_fixture(tmp_path, {
        "spark_rapids_tpu/m.py": """
            def f(rt):
                rt.reserve(10, site="covered.site")
                rt.reserve(10, site="new.site")
        """,
        "tests/test_retry.py": _SWEEP_TEST.format(
            sites='"covered.site", "ghost.site",')},
        rules=["TPU005"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "'new.site' missing from OOM_SWEEP_SITES" in msgs
    assert "'ghost.site' matches no reserve site" in msgs


def test_tpu005_duplicate_label_across_modules(tmp_path):
    res = run_fixture(tmp_path, {
        "spark_rapids_tpu/a.py": """
            def f(rt):
                rt.reserve(10, site="shared")
        """,
        "spark_rapids_tpu/b.py": """
            def g(rt):
                rt.reserve(10, site="shared")
        """,
        "tests/test_retry.py": _SWEEP_TEST.format(sites='"shared",')},
        rules=["TPU005"])
    assert any("multiple modules" in f.message for f in res.findings)


def test_tpu005_clean_negative(tmp_path):
    res = run_fixture(tmp_path, {
        "spark_rapids_tpu/m.py": """
            def f(rt):
                rt.reserve(10, site="only.site")
        """,
        "tests/test_retry.py": _SWEEP_TEST.format(sites='"only.site",')},
        rules=["TPU005"])
    assert res.findings == []


def test_sweep_contract_matches_real_tree():
    """The repo's OOM_SWEEP_SITES equals the reserve sites the package
    actually contains (the TPU005 invariant, asserted directly)."""
    from spark_rapids_tpu.lint.passes.retry_sites import RetrySitesPass
    import tests.test_retry as tr
    p = RetrySitesPass()
    pkg = os.path.join(repo_root(), "spark_rapids_tpu")
    lint_paths(paths=[pkg], root=repo_root(), baseline=Baseline([]),
               passes=[p])
    assert set(p.sites) == set(tr.OOM_SWEEP_SITES)


# --------------------------------------------------------------------------
# TPU006 — exception hygiene
# --------------------------------------------------------------------------

def test_tpu006_flags_silent_pass_and_continue(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        def f(items):
            try:
                open("/nope")
            except OSError:
                pass
            for it in items:
                try:
                    it()
                except Exception:
                    continue
    """}, rules=["TPU006"])
    assert rules_of(res) == ["TPU006", "TPU006"]


def test_tpu006_clean_negative_logged_counted_or_raised(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import logging
        log = logging.getLogger("x")

        def f(counters):
            try:
                open("/nope")
            except OSError as e:
                log.debug("probe failed: %r", e)
                counters.add("numScanPruneStatErrors", 1)
            try:
                open("/nope")
            except ValueError:
                raise
    """}, rules=["TPU006"])
    assert res.findings == []


def test_tpu006_suppression_inside_handler_body(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        def f(q):
            try:
                q.get_nowait()
            except Exception:
                pass  # tpulint: disable=TPU006 drain-loop termination
    """}, rules=["TPU006"])
    assert res.findings == [] and len(res.suppressed) == 1


# --------------------------------------------------------------------------
# TPU007 — lock order
# --------------------------------------------------------------------------

_LOCK_FIXTURE = """
    import threading

    class A:
        def __init__(self):
            self.a_lock = threading.Lock()
            self.b_lock = threading.Lock()

        def fwd(self):
            with self.a_lock:
                with self.b_lock:
                    x = 1

        def rev(self):
            with self.b_lock:
                with self.a_lock:
                    x = 1
"""


def test_tpu007_flags_cycle(tmp_path):
    res = run_fixture(tmp_path,
                      {"spark_rapids_tpu/m.py": _LOCK_FIXTURE},
                      rules=["TPU007"])
    assert any("lock-order cycle" in f.message for f in res.findings)


def test_tpu007_cross_file_cycle(tmp_path):
    res = run_fixture(tmp_path, {
        "spark_rapids_tpu/a.py": """
            class A:
                def f(self, other):
                    with self.m_lock:
                        with other.n_lock:
                            x = 1
        """,
        "spark_rapids_tpu/b.py": """
            class B:
                def g(self, other):
                    with self.n_lock:
                        with other.m_lock:
                            x = 1
        """}, rules=["TPU007"])
    # A.m_lock -> n_lock and B.n_lock -> m_lock: distinct class owners,
    # so no cycle between THOSE labels — but `other.n_lock`/`other.m_lock`
    # resolve to the same receiver-alias labels in both files, closing
    # other.n_lock -> other.m_lock -> ... only when labels coincide.
    # The deterministic cross-file case: module-global locks.
    res2 = run_fixture(tmp_path, {
        "spark_rapids_tpu/c.py": """
            import threading
            c_lock = threading.Lock()
            d_lock = threading.Lock()

            def f():
                with c_lock:
                    with d_lock:
                        x = 1
        """,
        "spark_rapids_tpu/d.py": """
            from .c import c_lock, d_lock

            def g():
                with d_lock:
                    with c_lock:
                        x = 1
        """}, rules=["TPU007"])
    del res
    assert any("lock-order cycle" in f.message for f in res2.findings)


def test_tpu007_self_edge_nonreentrant_flagged_rlock_ok(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import threading

        class A:
            def __init__(self):
                self.p_lock = threading.Lock()
                self.r_lock = threading.RLock()

            def bad(self):
                with self.p_lock:
                    with self.p_lock:
                        x = 1

            def fine(self):
                with self.r_lock:
                    with self.r_lock:
                        x = 1
    """}, rules=["TPU007"])
    assert len(res.findings) == 1
    assert "non-reentrant lock A.p_lock" in res.findings[0].message


def test_tpu007_journal_write_under_store_lock(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .journal import journal_event

        class FooStore:
            def track(self, buf):
                with self._lock:
                    journal_event("mem", "alloc", buffer=buf)
    """}, rules=["TPU007"])
    assert any("journal write" in f.message for f in res.findings)


def test_tpu007_clean_negative_consistent_order(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .journal import journal_event

        class A:
            def f(self):
                with self.a_lock:
                    with self.b_lock:
                        x = 1

        class FooStore:
            def track(self, buf):
                with self._lock:
                    x = 1
                journal_event("mem", "alloc", buffer=buf)
    """}, rules=["TPU007"])
    assert res.findings == []


def test_tpu007_journal_span_in_with_item_under_store_lock(tmp_path):
    """`with self._lock: with journal_span(...)` — the context expression
    evaluates under the held lock; the With-item form must be caught
    like the statement form."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .journal import journal_span

        class FooStore:
            def serve(self, buf):
                with self._lock:
                    with journal_span("serve", "x"):
                        y = 1
    """}, rules=["TPU007"])
    assert any("journal write" in f.message for f in res.findings)


# --------------------------------------------------------------------------
# review-fix regressions: rules validation + scoped staleness
# --------------------------------------------------------------------------

def test_unknown_rule_filter_errors_instead_of_green(tmp_path):
    with pytest.raises(ValueError, match="TPU0006"):
        run_fixture(tmp_path, {"spark_rapids_tpu/m.py": "X = 1\n"},
                    rules=["TPU0006"])


def test_stale_warnings_scoped_to_rules_that_ran(tmp_path):
    """A --rules subset must not call grants stale for passes that never
    ran (following that advice would break the next full run)."""
    grant = Baseline([{"rule": "TPU001", "path": "spark_rapids_tpu/m.py",
                       "count": 2, "reason": "two real syncs"}])
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        def f(x, y):
            return x.item() + y.item()
    """}, rules=["TPU006"], baseline=grant)
    assert res.stale_baseline == []
    res_full = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        def f(x, y):
            return x.item() + y.item()
    """}, rules=["TPU001"], baseline=grant)
    assert res_full.findings == [] and res_full.stale_baseline == []


def test_tpu004_polices_count_swallowed_names(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .registry import count_swallowed

        def f(e):
            count_swallowed("numTypoedCounter", "x", "boom: %r", e)
            count_swallowed("numCleanupErrors", "x", "ok: %r", e)
    """}, rules=["TPU004"])
    msgs = [f.message for f in res.findings]
    assert len(msgs) == 1 and "numTypoedCounter" in msgs[0]


# --------------------------------------------------------------------------
# ENGINE_COUNTERS (the TPU006 fix infrastructure)
# --------------------------------------------------------------------------

def test_engine_counters_roundtrip_and_catalog_gate():
    from spark_rapids_tpu.metrics.registry import (ENGINE_COUNTERS,
                                                   UNREGISTERED_SEEN,
                                                   EngineCounters)
    c = EngineCounters()
    c.add("numScanPruneStatErrors", 1)
    c.add("numScanPruneStatErrors", 2)
    assert c.get("numScanPruneStatErrors") == 3
    assert c.snapshot() == {"numScanPruneStatErrors": 3}
    c.reset()
    assert c.get("numScanPruneStatErrors") == 0
    # a typo'd name is recorded but remembered for the lint tier
    UNREGISTERED_SEEN.discard("numTypoCounter")
    c.add("numTypoCounter", 1)
    assert "numTypoCounter" in UNREGISTERED_SEEN
    UNREGISTERED_SEEN.discard("numTypoCounter")
    assert isinstance(ENGINE_COUNTERS, EngineCounters)


def test_engine_counters_surface_in_observability_exports():
    """The counters are readable, not write-only: session_observability
    carries them and prometheus_dump emits scope=engine samples."""
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.metrics.export import (parse_prometheus,
                                                 session_observability)
    from spark_rapids_tpu.metrics.registry import ENGINE_COUNTERS
    s = TpuSession({})
    df = s.from_pydict({"a": [1, 2, 3]})
    ENGINE_COUNTERS.add("numCleanupErrors", 1)
    try:
        df.collect()
        obs = session_observability(s)
        assert obs["engine_counters"].get("numCleanupErrors", 0) >= 1
        samples = parse_prometheus(s.last_execution.prometheus())
        hits = [k for k in samples
                if k[0] == "spark_rapids_tpu_num_cleanup_errors"
                and ("scope", "engine") in k[1]]
        assert hits, "no scope=engine sample for the hygiene counter"
    finally:
        ENGINE_COUNTERS.reset()


def test_count_swallowed_logs_and_counts(caplog):
    import logging

    from spark_rapids_tpu.metrics.registry import (ENGINE_COUNTERS,
                                                   count_swallowed)
    before = ENGINE_COUNTERS.get("numCleanupErrors")
    with caplog.at_level(logging.DEBUG, logger="spark_rapids_tpu.exec"):
        count_swallowed("numCleanupErrors", "spark_rapids_tpu.exec",
                        "cleanup %r failed", "cb")
    assert ENGINE_COUNTERS.get("numCleanupErrors") == before + 1
    assert any("cleanup 'cb' failed" in r.message for r in caplog.records)
    ENGINE_COUNTERS.reset()


class _BoomDev:
    platform = "cpu"

    def memory_stats(self):
        raise RuntimeError("no stats on this backend")


class _BoomTpu(_BoomDev):
    platform = "tpu"


class _EmptyTpu(_BoomTpu):
    def memory_stats(self):
        return None


def test_hbm_detect_fallback_counts(monkeypatch):
    from spark_rapids_tpu.mem import runtime as rt
    from spark_rapids_tpu.metrics.registry import ENGINE_COUNTERS

    import jax
    before = ENGINE_COUNTERS.get("numHbmDetectFallbacks")
    monkeypatch.setattr(jax, "devices", lambda: [_BoomDev()])
    assert rt._detect_hbm_bytes() == 16 << 30
    assert ENGINE_COUNTERS.get("numHbmDetectFallbacks") == before + 1


@pytest.mark.parametrize("dev", [_BoomTpu, _EmptyTpu])
def test_hbm_detect_on_tpu_raises_instead_of_guessing(monkeypatch, dev):
    """On the tpu platform a pool sized from a guess would hide the
    device: missing memory_stats() is an error there."""
    from spark_rapids_tpu.mem import runtime as rt
    from spark_rapids_tpu.metrics.registry import ENGINE_COUNTERS

    import jax
    before = ENGINE_COUNTERS.get("numHbmDetectFallbacks")
    monkeypatch.setattr(jax, "devices", lambda: [dev()])
    with pytest.raises(RuntimeError):
        rt._detect_hbm_bytes()
    assert ENGINE_COUNTERS.get("numHbmDetectFallbacks") == before


# --------------------------------------------------------------------------
# the acceptance gate: the repo lints clean + the CLI answers
# --------------------------------------------------------------------------

def test_self_run_zero_unsuppressed_findings():
    """The whole tree, all passes, the checked-in baseline: zero
    findings (ISSUE 9 acceptance).  Every suppression and baseline entry
    was already proven to carry a reason above."""
    result = lint_paths()
    assert result.findings == [], \
        "tpulint findings on the tree:\n" + render_text(result)
    # the baseline must not have gone stale silently either
    assert result.stale_baseline == [], result.stale_baseline


@pytest.mark.slow
def test_cli_and_metrics_alias_exit_zero():
    """Subprocess smoke: the module entry point and the back-compat
    metrics --lint alias (scripts/ci.sh calls both).  slow-marked: each
    spawn pays the jax import."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = repo_root()
    out = subprocess.run([sys.executable, "-m", "spark_rapids_tpu.lint",
                          "--json"], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert json.loads(out.stdout)["findings"] == []
    alias = subprocess.run([sys.executable, "-m",
                            "spark_rapids_tpu.metrics", "--lint"],
                           cwd=root, env=env, capture_output=True,
                           text=True, timeout=600)
    assert alias.returncode == 0, alias.stdout + alias.stderr
    assert "tpulint" in alias.stdout
    drift = subprocess.run([sys.executable, "-m", "spark_rapids_tpu.lint",
                            "--check-docs"], cwd=root, env=env,
                           capture_output=True, text=True, timeout=600)
    assert drift.returncode == 0, drift.stdout + drift.stderr


# --------------------------------------------------------------------------
# TPU008 — use-after-donate (ISSUE 12 cross-module dataflow)
# --------------------------------------------------------------------------

def test_tpu008_donated_then_read(tmp_path):
    """The core true positive: a batch dispatched through a donating
    executable and then re-read on a later line."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .kernel_cache import stage_executable
        from .fusion import source_donatable

        def run(key, builder, b, journal):
            if source_donatable(b):
                fn = stage_executable(key, builder, (b,),
                                      donate_argnums=(0,))
                out = fn(b)
                journal(b)  # <- b's buffers were donated at the dispatch
                return out
    """}, rules=["TPU008"])
    assert [f.rule for f in res.findings] == ["TPU008"]
    assert "use-after-donate" in res.findings[0].message
    assert "'b'" in res.findings[0].message


def test_tpu008_defuse_ladder_error_path_read(tmp_path):
    """The PR 11 dispatch-site regression the acceptance criteria names:
    re-introducing a post-donation read at a retry-combinator site (the
    whole-stage de-fuse ladder shape) is caught — the donation flows
    through run_retryable into the nested attempt's donating dispatch,
    and the read sits in the except handler."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .kernel_cache import stage_executable
        from .retryable import run_retryable
        from .retry import RetryExhausted
        from .donation import donatable

        class Stage:
            def execute(self, ctx, batches, key, builder, cpu_apply):
                def attempt(b):
                    don = self.donate_inputs and donatable(b)
                    fn = stage_executable(key, builder, (b,),
                                          donate_argnums=(0,)
                                          if don else ())
                    return fn(b)
                for batch in batches:
                    try:
                        yield run_retryable(ctx, self.metrics, "stage",
                                            attempt, [batch])
                    except RetryExhausted:
                        yield cpu_apply(batch)  # reads the donated batch
    """}, rules=["TPU008"])
    assert [f.rule for f in res.findings] == ["TPU008"]
    assert "'batch'" in res.findings[0].message
    assert "retry combinator" in res.findings[0].message


def test_tpu008_consumed_guard_negative(tmp_path):
    """The blessed error-path shape: a donation.consumed() bail-out that
    dominates the read silences the finding."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .kernel_cache import stage_executable
        from .retryable import run_retryable
        from .retry import RetryExhausted
        from .donation import donatable, consumed

        class Stage:
            def execute(self, ctx, batches, key, builder, cpu_apply):
                def attempt(b):
                    don = self.donate_inputs and donatable(b)
                    fn = stage_executable(key, builder, (b,),
                                          donate_argnums=(0,)
                                          if don else ())
                    return fn(b)
                for batch in batches:
                    try:
                        yield run_retryable(ctx, self.metrics, "stage",
                                            attempt, [batch])
                    except RetryExhausted:
                        if consumed(batch):
                            raise
                        yield cpu_apply(batch)
    """}, rules=["TPU008"])
    assert res.findings == []


def test_tpu008_pin_dominating_donation_negative(tmp_path):
    """A pin() that dominates the donation site disarms it: the registry
    refuses to donate a pinned batch, so later reads are safe."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .kernel_cache import stage_executable
        from .donation import pin, donatable

        def run(key, builder, b, journal):
            pin(b)
            don = donatable(b)
            fn = stage_executable(key, builder, (b,),
                                  donate_argnums=(0,) if don else ())
            out = fn(b)
            journal(b)
            return out
    """}, rules=["TPU008"])
    assert res.findings == []


def test_tpu008_unproven_dispatch_site(tmp_path):
    """A NEW dispatch site that donates without any donatable()/
    source_donatable()/donate_inputs proof in scope is flagged even
    before any read goes wrong."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .kernel_cache import stage_executable

        def run(key, builder, b):
            fn = stage_executable(key, builder, (b,),
                                  donate_argnums=(0,))
            return fn(b)
    """}, rules=["TPU008"])
    assert any("last-consumer proof" in f.message for f in res.findings)


def test_tpu008_plumbing_forward_not_flagged(tmp_path):
    """kernel_cache's own shape — donate_argnums forwarded from the
    function's parameter — is plumbing; the proof sits at the caller."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import jax

        def build(builder, donate_argnums=()):
            return jax.jit(builder(), donate_argnums=donate_argnums)
    """}, rules=["TPU008"])
    assert res.findings == []


def test_tpu008_exclusive_branches_negative(tmp_path):
    """A read in the non-donating sibling arm (after a terminating
    donation arm) can never observe the donation."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .kernel_cache import stage_executable
        from .donation import donatable

        def run(key, builder, b, fused, eager):
            if fused and donatable(b):
                fn = stage_executable(key, builder, (b,),
                                      donate_argnums=(0,))
                return fn(b)
            return eager(b)
    """}, rules=["TPU008"])
    assert res.findings == []


# --------------------------------------------------------------------------
# TPU009 — serving-tier shared-state audit
# --------------------------------------------------------------------------

_TPU009_POS = """
    import threading

    _HITS = {"n": 0}

    class Scheduler:
        def __init__(self):
            self._lock = threading.Lock()
            self.completed = 0
            self._workers = [
                threading.Thread(target=self._worker_loop, daemon=True)]

        def _worker_loop(self):
            while True:
                self._run_one()

        def _run_one(self):
            _HITS["n"] += 1          # global counter without the lock
            self.completed += 1      # instance write without the lock
"""


def test_tpu009_unlocked_writes_from_worker_threads(tmp_path):
    res = run_fixture(tmp_path,
                      {"spark_rapids_tpu/m.py": _TPU009_POS},
                      rules=["TPU009"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "_HITS" in msgs, msgs
    assert "self.completed" in msgs, msgs


def test_tpu009_locked_writes_negative(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import threading

        _HITS = {"n": 0}
        _HITS_LOCK = threading.Lock()

        class Scheduler:
            def __init__(self):
                self._lock = threading.Lock()
                self.completed = 0
                self._workers = [
                    threading.Thread(target=self._worker_loop,
                                     daemon=True)]

            def _worker_loop(self):
                while True:
                    self._run_one()

            def _run_one(self):
                with _HITS_LOCK:
                    _HITS["n"] += 1
                with self._lock:
                    self.completed += 1

            def _untrack_locked(self):
                self.completed -= 1  # convention: caller holds the lock
    """}, rules=["TPU009"])
    assert res.findings == []


def test_tpu009_thread_local_read_without_reinstall(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import threading

        class Verifier:
            def __init__(self):
                self._thread = threading.Thread(target=self._run,
                                                daemon=True)

            def _run(self):
                while True:
                    self._verify_one()

            def _verify_one(self):
                from .journal import journal_event
                journal_event("spill", "verified")
    """}, rules=["TPU009"])
    assert any("thread boundary" in f.message for f in res.findings)


def test_tpu009_thread_local_reinstall_negative(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import threading

        class Worker:
            def __init__(self):
                self._thread = threading.Thread(target=self._run,
                                                daemon=True)

            def _run(self):
                from .journal import journal_event, trace_context
                with trace_context(query="q1"):
                    journal_event("spill", "verified")
    """}, rules=["TPU009"])
    assert res.findings == []


# --------------------------------------------------------------------------
# TPU010 — pallas kernel contracts
# --------------------------------------------------------------------------

def test_tpu010_int64_in_kernel_and_bad_tile(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _kern(x_ref, o_ref):
            o_ref[:] = x_ref[:].astype(jnp.int64)

        def wide_cumsum(x):
            return pl.pallas_call(
                _kern,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                in_specs=[pl.BlockSpec((7, 100), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            )(x)
    """}, rules=["TPU010"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "64-bit dtype int64" in msgs
    assert "(7, 100)" in msgs
    # the congruent out_spec is NOT flagged
    assert "(8, 128) is not congruent" not in msgs


def test_tpu010_host_sync_in_kernel(tmp_path):
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from jax.experimental import pallas as pl

        def _kern(x_ref, o_ref):
            n = x_ref[0].item()
            print(n)
            o_ref[:] = x_ref[:]

        def bad(x, shape):
            return pl.pallas_call(_kern, out_shape=shape)(x)
    """}, rules=["TPU010"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "host-sync call item()" in msgs
    assert "impure call print()" in msgs


def test_tpu010_clean_kernel_negative(tmp_path):
    """The real kernels' shape: int32 iota, (8,128) tiles via module
    constants, is_count widening exempt."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        _SUBLANES = 8
        _LANES = 128

        def _make_kern(is_count):
            def kern(x_ref, o_ref):
                v = jnp.cumsum(x_ref[:], axis=1)
                if is_count:
                    v = v.astype(jnp.int64)  # blessed widening shape
                o_ref[:] = v
            return kern

        def good(x, shape, ops):
            spec = pl.BlockSpec((_SUBLANES, _LANES), lambda i: (i, 0))
            return pl.pallas_call(
                _make_kern(True), out_shape=shape,
                in_specs=[spec], out_specs=spec)(x)
    """}, rules=["TPU010"])
    assert res.findings == []


def test_tpu010_shard_map_body_sync_flagged_64bit_exempt(tmp_path):
    """ISSUE 14: shard_map collective bodies get the host-sync/impure
    half of the kernel checks; the 64-bit and tile rules stay
    Mosaic-only (collectives legitimately compute in int64/float64)."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        import numpy as np
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def bad_step(mesh, axis):
            def step(local):
                key = local.astype(jnp.int64)  # fine in a collective
                counts = np.asarray(key)       # host sync: flagged
                print(counts)                  # impure: flagged
                return key
            return shard_map(step, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis))
    """}, rules=["TPU010"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "host-sync call asarray() inside shard_map program" in msgs
    assert "impure call print() inside shard_map program" in msgs
    assert "int64" not in msgs


def test_tpu010_untested_kernel_wrapper(tmp_path):
    """The registry half: a public wrapper with no reference from
    tests/test_pallas.py is flagged; a referenced one is not."""
    res = run_fixture(tmp_path, {
        "spark_rapids_tpu/m.py": """
            from jax.experimental import pallas as pl

            def _kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]

            def tested_kernel(x, shape):
                return pl.pallas_call(_kern, out_shape=shape)(x)

            def untested_kernel(x, shape):
                return pl.pallas_call(_kern, out_shape=shape)(x)
        """,
        "tests/test_pallas.py": """
            from spark_rapids_tpu.m import tested_kernel

            def test_tested_kernel_interpret():
                assert tested_kernel is not None
        """}, rules=["TPU010"])
    names = " | ".join(f.message for f in res.findings)
    assert "untested_kernel" in names
    assert names.count("has no interpret-mode test") == 1


# --------------------------------------------------------------------------
# TPU011 — metric/journal flow coverage
# --------------------------------------------------------------------------

def test_tpu011_dead_metric_and_live_negative(tmp_path):
    res = run_fixture(tmp_path, {
        "spark_rapids_tpu/metrics/names.py": """
            def register_metric(name, kind, level, doc):
                return name

            LIVE = register_metric("liveMetric", "counter", 1, "used")
            DEAD = register_metric("deadMetric", "counter", 1, "unused")
        """,
        "spark_rapids_tpu/m.py": """
            def execute(metrics):
                metrics.add("liveMetric", 1)
        """}, rules=["TPU011"])
    msgs = [f.message for f in res.findings]
    assert any("'deadMetric' is registered but" in m for m in msgs), msgs
    assert not any("liveMetric" in m for m in msgs)


def test_tpu011_orphan_kind_and_unreachable_emission(tmp_path):
    res = run_fixture(tmp_path, {
        "spark_rapids_tpu/metrics/journal.py": """
            EVENT_KINDS = ("spill", "ghostkind")
        """,
        "spark_rapids_tpu/m.py": """
            from .journal import journal_event

            def execute(metrics):
                journal_event("spill", "x")

            def _forgotten(metrics):
                metrics.add("numOutputRows", 1)
        """}, rules=["TPU011"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "'ghostkind'" in msgs
    assert "_forgotten" in msgs and "unreachable" in msgs


def test_tpu011_retry_block_and_constant_emissions_credit(tmp_path):
    """Derived {block}Retries/Splits names and MN.CONSTANT references
    count as emissions — the real tree's idioms must not read as dead."""
    res = run_fixture(tmp_path, {
        "spark_rapids_tpu/metrics/names.py": """
            def register_metric(name, kind, level, doc):
                return name

            QUEUE_TIME = register_metric("queueTime", "timer", 1, "t")
            RETRY_BLOCKS = ("sort",)
            for _b in RETRY_BLOCKS:
                register_metric(f"{_b}Retries", "counter", 1, "r")
                register_metric(f"{_b}Splits", "counter", 1, "s")
        """,
        "spark_rapids_tpu/m.py": """
            from .metrics import names as MN

            def execute(ctx, metrics, run_retryable):
                metrics.add(MN.QUEUE_TIME, 1.0)
                run_retryable(ctx, metrics, "sort", None, [])
        """}, rules=["TPU011"])
    assert res.findings == []


# --------------------------------------------------------------------------
# the project model: call-graph resolution unit tier
# --------------------------------------------------------------------------

def _linked_model(tmp_path, files):
    import ast as _ast
    from spark_rapids_tpu.lint.model import ProjectModel, extract_module
    frags = []
    for rel, text in files.items():
        frags.append(extract_module(rel, _ast.parse(
            textwrap.dedent(text))))
    return ProjectModel.link(frags)


def test_model_resolves_attribute_calls_through_hierarchy(tmp_path):
    """`self.batch_fn()` in a base-class method resolves to every
    override in the class family — the RowLocalExec shape."""
    pm = _linked_model(tmp_path, {
        "spark_rapids_tpu/base.py": """
            class RowLocalExec:
                def execute(self):
                    return self.batch_fn()

                def batch_fn(self):
                    raise NotImplementedError
        """,
        "spark_rapids_tpu/filt.py": """
            from .base import RowLocalExec

            class TpuFilterExec(RowLocalExec):
                def batch_fn(self):
                    return 1
        """})
    execute = pm.funcs["spark_rapids_tpu/base.py::RowLocalExec.execute"]
    targets = pm.resolve_call(execute, "self.batch_fn")
    assert "spark_rapids_tpu/filt.py::TpuFilterExec.batch_fn" in targets
    assert "spark_rapids_tpu/base.py::RowLocalExec.batch_fn" in targets


def test_model_reachability_through_stores_and_imports(tmp_path):
    """Function-level imports and subclass dispatch (the BufferStore
    shape) both resolve; unreached helpers stay unreached."""
    pm = _linked_model(tmp_path, {
        "spark_rapids_tpu/stores.py": """
            class BufferStore:
                def spill(self):
                    self.evict_one()

                def evict_one(self):
                    raise NotImplementedError

            class DeviceMemoryStore(BufferStore):
                def evict_one(self):
                    from .ledger import on_spill
                    on_spill()
        """,
        "spark_rapids_tpu/ledger.py": """
            def on_spill():
                pass

            def _never_called():
                pass
        """})
    reach = pm.reachable(
        ["spark_rapids_tpu/stores.py::BufferStore.spill"])
    assert "spark_rapids_tpu/ledger.py::on_spill" in reach
    assert "spark_rapids_tpu/ledger.py::_never_called" not in reach


def test_model_class_family_and_lock_ownership(tmp_path):
    pm = _linked_model(tmp_path, {
        "spark_rapids_tpu/m.py": """
            import threading

            class Base:
                pass

            class Mid(Base):
                def __init__(self):
                    self._lock = threading.Lock()

            class Leaf(Mid):
                pass
        """})
    fam = pm.class_family("Mid")
    assert fam == {"Base", "Mid", "Leaf"}
    assert pm.owns_lock("Mid")
    assert not pm.owns_lock("Base")


# --------------------------------------------------------------------------
# incremental cache (ISSUE 12 satellite)
# --------------------------------------------------------------------------

def test_cache_warm_run_replays_findings_and_fragments(tmp_path):
    """A warm run must reproduce the cold run bit-for-bit: per-file
    findings (TPU001), cross-file fragment state (TPU005's sweep
    contract), everything."""
    files = {
        "spark_rapids_tpu/m.py": """
            def f(x, rt):
                rt.reserve(10, site="fixture.site")
                return x.item()
        """,
        "tests/test_retry.py": "OOM_SWEEP_SITES = (\"other.site\",)\n",
    }
    root = str(tmp_path)
    for rel, text in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(textwrap.dedent(text))
    docs = os.path.join(root, "docs", "configs.md")
    os.makedirs(os.path.dirname(docs), exist_ok=True)
    with open(docs, "w") as f:
        f.write(help_doc())
    from spark_rapids_tpu.lint.core import lint_paths as lp
    cold = lp(paths=None, root=root, baseline=Baseline([]),
              use_cache=True)
    warm = lp(paths=None, root=root, baseline=Baseline([]),
              use_cache=True)
    assert cold.cache_misses > 0 and warm.cache_misses == 0
    assert warm.cache_hits == warm.files_checked
    assert ([f.to_json() for f in cold.findings]
            == [f.to_json() for f in warm.findings])
    # the TPU005 cross-file contract findings survived the cache replay
    rules = {f.rule for f in warm.findings}
    assert "TPU001" in rules and "TPU005" in rules
    # editing a file invalidates ONLY it
    with open(os.path.join(root, "spark_rapids_tpu", "m.py"), "a") as f:
        f.write("\nX = 1\n")
    third = lp(paths=None, root=root, baseline=Baseline([]),
               use_cache=True)
    assert third.cache_misses == 1
    assert {f.rule for f in third.findings} == rules


def test_cache_entries_prune_for_removed_files(tmp_path):
    root = str(tmp_path)
    target = os.path.join(root, "spark_rapids_tpu", "gone.py")
    os.makedirs(os.path.dirname(target), exist_ok=True)
    with open(target, "w") as f:
        f.write("X = 1\n")
    docs = os.path.join(root, "docs", "configs.md")
    os.makedirs(os.path.dirname(docs), exist_ok=True)
    with open(docs, "w") as f:
        f.write(help_doc())
    from spark_rapids_tpu.lint.cache import CACHE_DIR_NAME
    from spark_rapids_tpu.lint.core import lint_paths as lp
    lp(paths=None, root=root, baseline=Baseline([]), use_cache=True)
    cache_dir = os.path.join(root, CACHE_DIR_NAME)
    before = {f for f in os.listdir(cache_dir) if f.endswith(".pkl")}
    os.unlink(target)
    lp(paths=None, root=root, baseline=Baseline([]), use_cache=True)
    after = {f for f in os.listdir(cache_dir) if f.endswith(".pkl")}
    assert len(after) < len(before)


def test_baseline_entry_for_removed_file_says_prune(tmp_path):
    grant = Baseline([{"rule": "TPU001",
                       "path": "spark_rapids_tpu/removed.py",
                       "count": 2, "reason": "legacy syncs"}])
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": "X = 1\n"},
                      rules=["TPU001"], baseline=grant)
    # fixture runs pass explicit paths, so removal cannot be claimed
    assert all("no longer exists" not in s for s in res.stale_baseline)
    from spark_rapids_tpu.lint.core import lint_paths as lp
    res2 = lp(paths=None, root=str(tmp_path), baseline=grant)
    assert any("no longer exists" in s and "prune" in s
               for s in res2.stale_baseline), res2.stale_baseline


# --------------------------------------------------------------------------
# --explain and the TPU000 rule-doc pointer
# --------------------------------------------------------------------------

def test_explain_prints_rule_section(capsys):
    from spark_rapids_tpu.lint.__main__ import explain_rule
    assert explain_rule(repo_root(), "TPU008") == 0
    out = capsys.readouterr().out
    assert "TPU008" in out and "donat" in out
    assert explain_rule(repo_root(), "TPU999") == 2


def test_tpu000_names_rule_reference(tmp_path):
    src = ("def f(x):\n"
           "    return x.item()  # tpulint: " "disable=TPU001\n")
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": src},
                      rules=["TPU001"])
    meta = [f for f in res.findings if f.rule == "TPU000"]
    assert meta and "--explain TPU001" in meta[0].message


def test_cache_distinguishes_identical_files(tmp_path):
    """Review fix: two byte-identical files must NOT share a cache entry
    — findings and model fragments carry the file's path, so sharing
    would double-report under one path and blind the project model to
    the other."""
    src = "def f(x):\n    return x.item()\n"
    root = str(tmp_path)
    for rel in ("spark_rapids_tpu/a.py", "spark_rapids_tpu/b.py"):
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(src)
    docs = os.path.join(root, "docs", "configs.md")
    os.makedirs(os.path.dirname(docs), exist_ok=True)
    with open(docs, "w") as f:
        f.write(help_doc())
    from spark_rapids_tpu.lint.core import lint_paths as lp
    for _ in range(2):  # cold, then warm replay
        res = lp(paths=None, root=root, baseline=Baseline([]),
                 use_cache=True)
        tpu001 = sorted(f.path for f in res.findings
                        if f.rule == "TPU001")
        assert tpu001 == ["spark_rapids_tpu/a.py",
                          "spark_rapids_tpu/b.py"], tpu001


def test_cache_subset_run_does_not_prune_full_surface(tmp_path):
    """Review fix: a library caller linting a SUBSET with the cache on
    must not delete the rest of the tree's entries."""
    root = str(tmp_path)
    for rel in ("spark_rapids_tpu/a.py", "spark_rapids_tpu/b.py"):
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(f"X_{rel[-4]} = 1\n")
    docs = os.path.join(root, "docs", "configs.md")
    os.makedirs(os.path.dirname(docs), exist_ok=True)
    with open(docs, "w") as f:
        f.write(help_doc())
    from spark_rapids_tpu.lint.cache import CACHE_DIR_NAME
    from spark_rapids_tpu.lint.core import lint_paths as lp
    lp(paths=None, root=root, baseline=Baseline([]), use_cache=True)
    cache_dir = os.path.join(root, CACHE_DIR_NAME)
    full = {f for f in os.listdir(cache_dir) if f.endswith(".pkl")}
    lp(paths=[os.path.join(root, "spark_rapids_tpu", "a.py")],
       root=root, baseline=Baseline([]), use_cache=True)
    kept = {f for f in os.listdir(cache_dir) if f.endswith(".pkl")}
    assert full <= kept, "subset run pruned full-surface entries"


def test_tpu008_fallthrough_handler_read_after_try(tmp_path):
    """Review fix: a try body that RETURNS still reaches the code after
    the Try when an except handler falls through — the donation-then-
    `except: pass`-then-read shape must flag."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .kernel_cache import stage_executable
        from .donation import donatable

        def run(key, builder, b, cpu_apply):
            don = donatable(b)
            try:
                fn = stage_executable(key, builder, (b,),
                                      donate_argnums=(0,) if don else ())
                return fn(b)
            except MemoryError:
                pass  # tpulint: disable=TPU006 fixture fallthrough
            return cpu_apply(b)
    """}, rules=["TPU008"])
    assert any("use-after-donate" in f.message for f in res.findings)


def test_tpu008_terminating_handlers_still_negative(tmp_path):
    """Control: when the try body returns AND every handler terminates,
    code after the Try really is unreachable post-donation."""
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": """
        from .kernel_cache import stage_executable
        from .donation import donatable

        def run(key, builder, b, cpu_apply):
            don = donatable(b)
            try:
                fn = stage_executable(key, builder, (b,),
                                      donate_argnums=(0,) if don else ())
                return fn(b)
            except MemoryError:
                raise
            return cpu_apply(b)
    """}, rules=["TPU008"])
    assert res.findings == []


def test_tpu000_disable_all_cites_a_real_rule(tmp_path):
    src = ("def f(x):\n"
           "    return x.item()  # tpulint: " "disable=all\n")
    res = run_fixture(tmp_path, {"spark_rapids_tpu/m.py": src},
                      rules=["TPU001"])
    meta = [f for f in res.findings if f.rule == "TPU000"]
    assert meta and "--explain all" not in meta[0].message
    assert "--explain TPU001" in meta[0].message
