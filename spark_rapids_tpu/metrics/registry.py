"""Typed, level-gated operator metrics.

The `Metrics` class here replaces the original ad-hoc dict in exec/base.py
(which re-exports it for compatibility).  Three things changed:

  * **Level gating** (reference: GpuMetric + MetricsLevel,
    spark.rapids.sql.metrics.level): every name in names.METRICS carries a
    level; `add`/`add_lazy`/`timer` become no-ops for metrics above the
    session level, so DEBUG-only diagnostics cost nothing at ESSENTIAL.
  * **Batched lazy fold**: deferred device scalars (row counts accumulated
    with `add_lazy` inside streaming hot loops) used to resolve with one
    `int(x)` host round trip per pending scalar; they now fold through one
    device reduction per name stacked into a single array and ONE host
    transfer for the whole Metrics object.
  * **Sync accounting**: `add_sync` is the DEBUG-only eager path (the thunk
    may block on the device); every execution increments the module
    DEVICE_SYNCS counter so tests can assert the ESSENTIAL/MODERATE paths
    never force a per-batch device sync.

Unregistered names are recorded anyway (robustness beats a lost counter)
but remembered in UNREGISTERED_SEEN, which the lint tier asserts is empty.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from . import names as N

# names emitted through a Metrics object but absent from the catalog; the
# lint-style test (tests/test_metrics.py) asserts this stays empty after
# driving a representative query slice
UNREGISTERED_SEEN: set = set()


class _SyncCounter:
    """Process-wide count of metric reads that blocked on the device (the
    'injected-sync counter' of the acceptance tests)."""

    def __init__(self):
        self._n = 0
        self._lock = threading.Lock()

    def bump(self) -> None:
        with self._lock:
            self._n += 1

    @property
    def count(self) -> int:
        return self._n

    def reset(self) -> None:
        with self._lock:
            self._n = 0


DEVICE_SYNCS = _SyncCounter()


class EngineCounters:
    """Process-wide named counters for engine-internal events that happen
    OUTSIDE any operator's Metrics object — teardown paths, detection
    fallbacks, swallowed-failure sites the exception-hygiene lint
    (TPU006, docs/lint.md) requires to be counted.  Names go through the
    same catalog as operator metrics, so a typo'd key fails TPU004 /
    `python -m spark_rapids_tpu.metrics --lint` like any other emission
    site."""

    def __init__(self):
        self._lock = threading.Lock()
        self._values: Dict[str, float] = {}

    def add(self, name: str, v: float = 1) -> None:
        if not N.is_registered(name):
            UNREGISTERED_SEEN.add(name)
        with self._lock:
            self._values[name] = self._values.get(name, 0) + v

    def get(self, name: str) -> float:
        with self._lock:
            return self._values.get(name, 0)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._values)

    def reset(self) -> None:
        with self._lock:
            self._values.clear()


#: the process-wide instance every hygiene site bumps
ENGINE_COUNTERS = EngineCounters()


def count_swallowed(name: str, logger_name: str, msg: str, *args,
                    warn: bool = False) -> None:
    """The canonical TPU006 fix shape in one call: a module-log line plus
    a registered process counter (docs/lint.md).  `warn=True` for
    downgrades an operator should act on (mis-sized pools, leaked
    cleanups); the default debug level for teardown/fallback noise.
    Counters are process-local — worker-side bumps surface in worker
    logs, not the driver's scrape."""
    import logging
    log = logging.getLogger(logger_name)
    (log.warning if warn else log.debug)(msg, *args)
    ENGINE_COUNTERS.add(name, 1)


def parse_level(value) -> int:
    s = str(value).strip().upper()
    for lvl, name in N.LEVEL_NAMES.items():
        if s == name:
            return lvl
    raise ValueError(
        f"unknown metrics level {value!r}; expected one of "
        f"{'/'.join(N.LEVEL_NAMES.values())}")


class _NoopTimer:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        pass


_NOOP_TIMER = _NoopTimer()


class _Timer:
    def __init__(self, m: "Metrics", name: str):
        self.m, self.name = m, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.m.add(self.name, time.perf_counter() - self.t0)


class Metrics:
    """SQLMetric set for one operator (reference: GpuExec.scala:24-41).

    Constructed ungated at the session default; `configure()` (called by
    QueryExecution before the query runs) pins the per-query level and the
    journal/node identity used by the observability layer."""

    DEFAULT_LEVEL = N.MODERATE

    def __init__(self, level: Optional[int] = None):
        self._values: Dict[str, float] = {}
        self._lazy: Dict[str, list] = {}
        self._level = self.DEFAULT_LEVEL if level is None else level
        self._lock = threading.Lock()

    # -- configuration -------------------------------------------------------

    def configure(self, level: int) -> "Metrics":
        self._level = int(level)
        return self

    @property
    def level(self) -> int:
        return self._level

    @property
    def debug_active(self) -> bool:
        return self._level >= N.DEBUG

    def enabled(self, name: str) -> bool:
        """Is `name` recorded at this Metrics object's level?"""
        return N.metric_level(name) <= self._level

    def _gate(self, name: str) -> bool:
        spec = N.METRICS.get(name)
        if spec is None:
            UNREGISTERED_SEEN.add(name)
            return True  # record anyway; the lint tier catches the typo
        return spec.level <= self._level

    # -- recording -----------------------------------------------------------

    def add(self, name: str, v: float) -> None:
        if not self._gate(name):
            return
        with self._lock:
            self._values[name] = self._values.get(name, 0) + v

    def set_max(self, name: str, v: float) -> None:
        """Gauge semantics: keep the high-water mark (peakDevMemory)."""
        if not self._gate(name):
            return
        with self._lock:
            if v > self._values.get(name, float("-inf")):
                self._values[name] = v

    def add_lazy(self, name: str, traced_scalar) -> None:
        """Accumulate a DEVICE scalar without syncing: row counts inside
        streaming hot loops are data-dependent, and a host read per batch
        is a device round trip (a host-link RTT on chip).  Deferred scalars
        resolve in one batched sweep when the metrics are read."""
        if not self._gate(name):
            return
        with self._lock:
            self._lazy.setdefault(name, []).append(traced_scalar)

    def add_sync(self, name: str, thunk) -> None:
        """DEBUG-only eager metric whose thunk may BLOCK on the device
        (e.g. an exact per-batch row count).  Below DEBUG this is a no-op
        that never calls the thunk; at DEBUG each call counts against the
        process-wide DEVICE_SYNCS counter."""
        if self._level < N.DEBUG:
            return
        DEVICE_SYNCS.bump()
        self.add(name, float(thunk()))

    def timer(self, name: str):
        if not self._gate(name):
            return _NOOP_TIMER
        return _Timer(self, name)

    # -- reading -------------------------------------------------------------

    def _fold_lazy_locked(self) -> None:
        """Resolve every deferred device scalar with one device reduction
        per (name, placement) and ONE batched host transfer for the lot
        (the fold syncs; readers are reporting paths, never hot loops).
        Grouped by placement because after a mesh exchange partition i's
        row count lives on device i, and scalars on different devices do
        not stack."""
        pending = [(name, pend) for name, pend in self._lazy.items() if pend]
        if not pending:
            return
        import jax
        import jax.numpy as jnp
        from ..utils.tracing import named_range
        with named_range("metrics_fold"):
            groups: Dict[tuple, list] = {}
            for name, pend in pending:
                for x in pend:
                    x = jnp.asarray(x)
                    groups.setdefault((name, x.sharding), []).append(x)
            sums = [jnp.sum(jnp.stack(xs).astype(jnp.float64))
                    for xs in groups.values()]
            host = jax.device_get(sums)  # tpulint: disable=TPU001 THE designed single device->host transfer of the lazy-metric fold; reporting paths sync once, hot loops never
        for (name, _placement), v in zip(groups, host):
            self._values[name] = self._values.get(name, 0) + float(v)
        for _name, pend in pending:
            pend.clear()

    @property
    def values(self) -> Dict[str, float]:
        """Metric dict with every deferred device scalar folded in."""
        with self._lock:
            self._fold_lazy_locked()
            return self._values

    def snapshot(self) -> Dict[str, float]:
        """Folded copy, safe to hold across further mutation."""
        with self._lock:
            self._fold_lazy_locked()
            return dict(self._values)

    def __repr__(self):
        return repr(self.values)
