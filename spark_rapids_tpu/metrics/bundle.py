"""Post-mortem diagnostic bundles: first-failure artifacts.

`dump_diagnostics()` assembles ONE self-contained directory from
everything the observability stack already knows at the moment of
failure:

  manifest.json        reason, trigger error, wall time, per-section
                       status (a section that failed to assemble is
                       recorded, never fatal)
  config.json          the session's effective conf settings
  explain.txt          EXPLAIN with per-node metrics of the failing
                       query
  progress.json        session/cluster progress() at dump time
  observability.json   metrics.export.session_observability
  slo.json             serving-tier scheduler stats + SLO report
  timeline.json        merged cluster timeline analysis (critical path,
                       stragglers, flow links)
  memledger.txt        memory-ledger replay over the drained shards
  samples.json         the driver gauge sampler's retained time series
  ring-driver.jsonl    the driver flight-recorder ring (metrics/ring.py)
  ring-<exec>.jsonl    each worker's ring, fetched over a DEDICATED
                       control rpc with a timeout — a dead worker costs
                       one missing file, not the bundle

`PostmortemManager` owns the automatic triggers (query failure, hung-task
watchdog, retry-budget exhaustion, SIGUSR1), rate-limited by
`telemetry.postmortem.minIntervalMs` so a failure storm cannot fill the
disk.  `python -m spark_rapids_tpu.metrics postmortem <bundle>` renders a
bundle back into the human report (metrics/__main__.py).
"""
from __future__ import annotations

import json
import logging
import os
import signal
import threading
import time
from typing import Callable, Dict, List, Optional

from .registry import ENGINE_COUNTERS, count_swallowed

log = logging.getLogger("spark_rapids_tpu.metrics.bundle")

MANIFEST = "manifest.json"


def _write(path: str, body: str) -> None:
    with open(path, "w") as f:
        f.write(body)


def _section(bundle_dir: str, sections: Dict[str, str], name: str,
             fname: str, fn: Callable[[], Optional[str]]) -> None:
    """Assemble one bundle file; a failure is recorded in the manifest
    (and counted) instead of aborting the dump — a bundle missing its
    timeline is still worth having for its rings."""
    try:
        body = fn()
        if body is None:
            sections[name] = "skipped"
            return
        _write(os.path.join(bundle_dir, fname), body)
        sections[name] = "ok"
    except Exception as e:  # noqa: BLE001 — partial bundle beats none
        count_swallowed("numPostmortemErrors", __name__,
                        "bundle section %s failed (%r)", name, e)
        sections[name] = f"error: {e!r}"


def _jsonable(obj):
    return json.dumps(obj, indent=2, default=str, sort_keys=True) + "\n"


def dump_diagnostics(bundle_dir: str, session=None, cluster=None,
                     qe=None, reason: str = "manual", error=None,
                     rpc_timeout: float = 2.0) -> str:
    """Write a post-mortem bundle into `bundle_dir` (created; must not
    already contain a manifest) and return the directory path.  Every
    argument is optional — the bundle holds whatever the caller's
    process can see, and each section degrades independently."""
    os.makedirs(bundle_dir, exist_ok=True)
    sections: Dict[str, str] = {}
    if cluster is None and session is not None:
        cluster = getattr(session, "_proc_cluster", None)
    if qe is None and session is not None:
        qe = getattr(session, "_last_qe", None)

    if session is not None:
        _section(bundle_dir, sections, "config", "config.json",
                 lambda: _jsonable(dict(session.conf._settings)))
        from .export import session_observability
        _section(bundle_dir, sections, "observability",
                 "observability.json",
                 lambda: _jsonable(session_observability(session)))
        _section(bundle_dir, sections, "progress", "progress.json",
                 lambda: _jsonable(session.progress()))
        sched = getattr(session, "_scheduler", None)
        if sched is not None:
            _section(bundle_dir, sections, "slo", "slo.json",
                     lambda: _jsonable(sched.stats()))
    elif cluster is not None:
        _section(bundle_dir, sections, "progress", "progress.json",
                 lambda: _jsonable(cluster.progress()))

    if qe is not None:
        _section(bundle_dir, sections, "explain", "explain.txt",
                 lambda: qe.explain_with_metrics() + "\n")

    if cluster is not None:
        _section(bundle_dir, sections, "timeline", "timeline.json",
                 lambda: _jsonable(cluster.timeline_report()))

        def memledger_body():
            # timeline_report above already drained; the accumulated
            # shards compose across drains, so this replays EVERYTHING
            # the cluster has ever heard
            from . import memledger as ML
            shards = [dict(rec) for rec in cluster._drained.values()]
            return ML.render(ML.analyze_shards(shards)) + "\n"
        _section(bundle_dir, sections, "memledger", "memledger.txt",
                 memledger_body)

        def ring_of(w):
            from ..shuffle.net import SocketClient
            client = SocketClient(cluster._transport, tuple(w.address),
                                  inject_faults=False,
                                  connect_timeout=rpc_timeout)
            try:
                rec = client.rpc("ring_dump", _rpc_timeout=rpc_timeout)
            finally:
                client.close()
            return "\n".join(rec.get("lines") or []) + "\n"
        for w in list(getattr(cluster, "workers", []) or []):
            _section(bundle_dir, sections, f"ring-{w.executor_id}",
                     f"ring-{w.executor_id}.jsonl",
                     lambda w=w: ring_of(w))

    from . import ring as R
    telemetry = R.get_telemetry()
    if telemetry is not None:
        _section(bundle_dir, sections, "ring-driver", "ring-driver.jsonl",
                 telemetry.recorder.dump_jsonl)
        _section(bundle_dir, sections, "samples", "samples.json",
                 lambda: _jsonable(telemetry.sampler.series_snapshot()))

        def policy_tail():
            # the last data-movement policy decisions still in the ring
            # (victims/unspills/backpressure/codec) — what the engine
            # chose right before the failure, without needing journal
            # shards on disk
            import json as _json
            snap = telemetry.recorder.snapshot()
            recs = [r for r in snap.get("events") or []
                    if r.get("kind") == "policy"][-200:]
            return "".join(_json.dumps(r, default=str) + "\n"
                           for r in recs)
        _section(bundle_dir, sections, "policy-tail", "policy-tail.jsonl",
                 policy_tail)

    manifest = {
        "version": 1,
        "reason": reason,
        "error": repr(error) if error is not None else None,
        "query_id": getattr(qe, "query_id", None),
        "pid": os.getpid(),
        "wall_time_s": time.time(),
        "sections": sections,
    }
    _write(os.path.join(bundle_dir, MANIFEST), _jsonable(manifest))
    ENGINE_COUNTERS.add("numPostmortemDumps", 1)
    log.warning("post-mortem bundle dumped: %s (reason=%s, %d sections)",
                bundle_dir, reason, len(sections))
    return bundle_dir


class PostmortemManager:
    """Automatic post-mortem triggers with rate limiting.

    One per driver session (armed only when telemetry.postmortem.dir is
    set).  `trigger()` is safe from any thread: dumps run either inline
    (query-failure path — the caller is already failing) or on a
    one-shot thread (watchdog / SIGUSR1 — those callers must not block
    behind a multi-second rpc sweep)."""

    def __init__(self, session, base_dir: str,
                 min_interval_ms: int = 30000):
        self.session = session
        self.base_dir = base_dir
        self.min_interval_s = max(0.0, min_interval_ms / 1000.0)
        self._lock = threading.Lock()
        self._last_dump_mono: Optional[float] = None
        self._seq = 0
        self._in_flight = False
        self.bundles: List[str] = []  # dumped paths, oldest first

    def _reserve(self, reason: str) -> Optional[str]:
        """Rate-limit + dedup gate; returns the bundle dir to write, or
        None when this trigger is suppressed."""
        now = time.monotonic()
        with self._lock:
            if self._in_flight:
                count_swallowed("numPostmortemSuppressed", __name__,
                                "postmortem trigger %s suppressed: a "
                                "dump is already in flight", reason)
                return None
            if self._last_dump_mono is not None and \
                    now - self._last_dump_mono < self.min_interval_s:
                count_swallowed("numPostmortemSuppressed", __name__,
                                "postmortem trigger %s suppressed by "
                                "the minIntervalMs rate limit", reason)
                return None
            self._in_flight = True
            self._seq += 1
            return os.path.join(
                self.base_dir,
                f"postmortem-{self._seq:03d}-{reason}-{os.getpid()}")

    def trigger(self, reason: str, qe=None, error=None,
                asynchronous: bool = False) -> Optional[str]:
        """Fire one automatic dump.  Returns the bundle path (inline
        mode), or None when suppressed / asynchronous."""
        bundle_dir = self._reserve(reason)
        if bundle_dir is None:
            return None

        def run():
            try:
                dump_diagnostics(bundle_dir, session=self.session,
                                 qe=qe, reason=reason, error=error)
                with self._lock:
                    self.bundles.append(bundle_dir)
            except Exception as e:  # noqa: BLE001 — triggers fire from
                # failure paths; the dump must never add a second error
                count_swallowed("numPostmortemErrors", __name__,
                                "postmortem dump %s failed (%r)",
                                reason, e)
            finally:
                with self._lock:
                    self._in_flight = False
                    self._last_dump_mono = time.monotonic()
        if asynchronous:
            threading.Thread(target=run, name="postmortem-dump",
                             daemon=True).start()
            return None
        run()
        return bundle_dir


def install_sigusr1(manager: PostmortemManager) -> bool:
    """SIGUSR1 -> asynchronous diagnostic dump (the 'what is my wedged
    driver doing' signal).  Installs only from the main thread of the
    driver process; returns whether the handler was installed."""
    if threading.current_thread() is not threading.main_thread():
        return False

    def handler(signum, frame):
        manager.trigger("sigusr1", asynchronous=True)

    try:
        signal.signal(signal.SIGUSR1, handler)
        return True
    except (ValueError, OSError, AttributeError) as e:
        # non-main interpreter contexts / platforms without SIGUSR1
        count_swallowed("numPostmortemErrors", __name__,
                        "SIGUSR1 handler install failed (%r)", e)
        return False


# -- renderer (python -m spark_rapids_tpu.metrics postmortem <bundle>) --------

def load_bundle(bundle_dir: str) -> dict:
    """Parse every file of a bundle back into one dict: the manifest,
    each JSON section, and each ring as parsed journal records.  Raises
    on a missing/malformed manifest (the renderer's contract: a bundle
    either loads completely or names what is broken)."""
    with open(os.path.join(bundle_dir, MANIFEST)) as f:
        manifest = json.load(f)
    out = {"manifest": manifest, "rings": {}, "texts": {}, "json": {}}
    for fname in sorted(os.listdir(bundle_dir)):
        path = os.path.join(bundle_dir, fname)
        if fname == MANIFEST or not os.path.isfile(path):
            continue
        if fname.startswith("ring-") and fname.endswith(".jsonl"):
            proc = fname[len("ring-"):-len(".jsonl")]
            events = []
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        events.append(json.loads(line))
            out["rings"][proc] = events
        elif fname.endswith(".json"):
            with open(path) as f:
                out["json"][fname[:-len(".json")]] = json.load(f)
        else:
            with open(path) as f:
                out["texts"][fname] = f.read()
    return out


def render_bundle(bundle_dir: str) -> str:
    """The human report of one bundle (the postmortem CLI body)."""
    b = load_bundle(bundle_dir)
    m = b["manifest"]
    when = time.strftime("%Y-%m-%d %H:%M:%S UTC",
                         time.gmtime(m.get("wall_time_s", 0)))
    lines = [f"== post-mortem bundle {os.path.basename(bundle_dir)} ==",
             f"  reason: {m.get('reason')}   pid: {m.get('pid')}   "
             f"at: {when}"]
    if m.get("error"):
        lines.append(f"  error: {m['error']}")
    if m.get("query_id") is not None:
        lines.append(f"  query: {m['query_id']}")
    lines.append("  sections:")
    for name, status in sorted((m.get("sections") or {}).items()):
        lines.append(f"    {name:<24} {status}")
    for proc in sorted(b["rings"]):
        events = b["rings"][proc]
        kinds: Dict[str, int] = {}
        for ev in events:
            if ev.get("ev") in ("B", "I"):
                kinds[ev.get("kind", "?")] = \
                    kinds.get(ev.get("kind", "?"), 0) + 1
        tss = [e["ts"] for e in events
               if isinstance(e.get("ts"), (int, float))]
        span_ns = (max(tss) - min(tss)) if len(tss) >= 2 else 0
        kind_str = ", ".join(f"{k}={n}" for k, n in sorted(kinds.items()))
        lines.append(f"  ring {proc}: {len(events)} events over "
                     f"{span_ns / 1e9:.2f}s ({kind_str})")
    prog = b["json"].get("progress")
    if prog:
        lines.append(
            "  progress: score=%s tasks_completed=%s hung=%s "
            "lag=%.2fs" % (prog.get("score"), prog.get("tasks_completed"),
                           prog.get("hung_tasks"),
                           float(prog.get("heartbeat_lag_s", 0.0))))
    tl = b["json"].get("timeline")
    if tl and isinstance(tl.get("metrics"), dict):
        tm = tl["metrics"]
        interesting = {k: v for k, v in sorted(tm.items())
                       if isinstance(v, (int, float)) and v}
        if interesting:
            lines.append("  timeline metrics: " + ", ".join(
                f"{k}={v:g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in interesting.items()))
    if "policy-tail.jsonl" in b["texts"]:
        tail = [ln for ln in b["texts"]["policy-tail.jsonl"].splitlines()
                if ln.strip()]
        recs: Dict[str, int] = {}
        for ln in tail:
            try:
                name = json.loads(ln).get("name", "?")
            except ValueError:  # tpulint: disable=TPU006 rendering a post-mortem artifact: a torn tail line is display-only and skipped by design
                continue
            recs[name] = recs.get(name, 0) + 1
        rec_str = ", ".join(f"{k}={n}" for k, n in sorted(recs.items()))
        lines.append(f"  policy tail: {len(tail)} decisions"
                     + (f" ({rec_str})" if rec_str else ""))
    if "explain.txt" in b["texts"]:
        lines.append("")
        lines.append(b["texts"]["explain.txt"].rstrip("\n"))
    if "memledger.txt" in b["texts"]:
        lines.append("")
        lines.append(b["texts"]["memledger.txt"].rstrip("\n"))
    return "\n".join(lines) + "\n"
