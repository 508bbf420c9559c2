"""`python -m spark_rapids_tpu.metrics` — regenerate docs/monitoring.md.
`python -m spark_rapids_tpu.metrics --lint` — metric-name lint: BACK-COMPAT
ALIAS for the TPU004 pass of the tpulint framework (`python -m
spark_rapids_tpu.lint --rules TPU004`, docs/lint.md): every metric
emission site must use a catalog name and every journal kind literal
must be a member of EVENT_KINDS.  Kept because scripts/ci.sh, docs and
muscle memory call this entry point; it delegates.
`python -m spark_rapids_tpu.metrics --timeline <journal-dir> [--chrome
out.json] [--straggler-factor K]` — merge the directory's worker trace
shards (shard-*.jsonl) and driver query journals (query-*.jsonl) into
one wall-clock-aligned cluster timeline and print the analysis report
(critical path, per-task fetch/compute/decompress/idle overlap,
straggler flags, fetch<->serve flow links); --chrome additionally writes
the multi-pid Chrome trace (one lane per worker, flow arrows).
`python -m spark_rapids_tpu.metrics --memory <journal-dir>
[--retouch-window K]` — replay the directory's memory-ledger records
(journal kind `mem`) and print the pressure analysis: per-query/per-site
peak attribution, spill cascade chains (triggering reservation site +
victim buffer ids), churn ratio, victim quality, and the headroom
estimate (metrics/memledger.py).
`python -m spark_rapids_tpu.metrics postmortem <bundle-dir> [--json]` —
render a post-mortem diagnostics bundle (metrics/bundle.py:
dump_diagnostics output, auto-dumped on query failure / hung-task
watchdog / retry-budget exhaustion / SIGUSR1): manifest, per-section
status, per-process flight-recorder ring summaries, progress, timeline
metrics, EXPLAIN and memory-ledger text."""
from __future__ import annotations

import os
import sys

from . import names as N

_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO_ROOT = os.path.dirname(_PKG_ROOT)


def lint() -> int:
    """Back-compat alias: delegate to the tpulint framework's TPU004 pass
    (AST-based, so multi-line calls and journal kinds are covered too —
    strictly stronger than the regex scan this replaced)."""
    from ..lint.core import lint_paths, render_text
    result = lint_paths(paths=[_PKG_ROOT], rules=["TPU004"],
                        root=_REPO_ROOT)
    if result.findings:
        print(render_text(result), file=sys.stderr)
        return 1
    print(f"metric-name lint OK via tpulint TPU004 "
          f"({result.files_checked} files, {len(N.METRICS)} registered "
          f"names); full gate: python -m spark_rapids_tpu.lint")
    return 0


def monitoring_doc() -> str:
    lines = [
        "# Monitoring and observability",
        "",
        "Generated by `python -m spark_rapids_tpu.metrics`; the metric",
        "catalog below comes from the live registry in",
        "`spark_rapids_tpu/metrics/names.py` — do not edit by hand.",
        "",
        "## Metric levels",
        "",
        "`spark.rapids.sql.tpu.metrics.level` (default `MODERATE`) gates",
        "what operators record, mirroring the reference's",
        "`spark.rapids.sql.metrics.level`:",
        "",
        "* `ESSENTIAL` — free host-side counters only (row/batch/fallback",
        "  counts).  No timers, no per-batch device work.",
        "* `MODERATE` — adds wall-clock timers and lazily folded device",
        "  row counts (at most one extra device op per batch, never a",
        "  device sync).",
        "* `DEBUG` — adds metrics that force a per-batch device sync",
        "  (eager exact row counts) and peak-memory sampling",
        "  (`peakDevMemory`).  Expect measurable overhead.",
        "",
        "Deferred (`lazy`) device scalars fold when metrics are read: one",
        "device reduction per name batched into a single host transfer per",
        "operator — reporting paths sync once, hot loops never do.",
        "",
        "## Reading metrics",
        "",
        "* `spark.rapids.sql.explain=METRICS` prints the executed plan",
        "  tree with per-node metrics to stderr after every query",
        "  (EXPLAIN-with-metrics — the Spark SQL UI analogue).",
        "* `session.last_execution.explain_with_metrics()` returns the",
        "  same rendering; `.node_metrics()` / `.aggregate()` give",
        "  structured access; `.prometheus()` returns a Prometheus",
        "  text-format dump of the query.",
        "* `session.query_metrics_total` accumulates counters across every",
        "  query the session ran (the benchmark's per-query counters read",
        "  it).",
        "",
        "## Event journal",
        "",
        "Set `spark.rapids.sql.tpu.metrics.journal.dir=/path` to write one",
        "JSON-lines journal per query (`query-<id>.jsonl`); at `DEBUG`",
        "level an in-memory journal is kept even without a dir (read it",
        "via `session.last_execution.journal.events()`).  Every record:",
        "",
        "```json",
        '{"ts": <monotonic ns>, "ev": "B|E|I", "kind":',
        ' "query|stage|operator|retry|spill|fetch|metric|fallback|replan",',
        ' "name": "...", "id": <int>, "parent": <int|null>, ...}',
        "```",
        "",
        "* `B`/`E` bracket spans (`E` carries `span`: the opening id);",
        "  `I` is an instant event.  Operator spans parent to the",
        "  enclosing operator's span, mirroring the plan tree.",
        "* Retry blocks append `retry` events (attempt/split), the spill",
        "  cascade appends `spill` events with byte counts, the shuffle",
        "  transport appends `fetch` events per remote block, and query",
        "  finish appends one `metric` event per plan node carrying its",
        "  final metric values.",
        "* Adaptive execution appends one `stage` event per materialized",
        "  shuffle map stage (observed bytes/rows) and one `replan` event",
        "  per decision: `coalescePartitions`, `skewSplit`,",
        "  `promoteToBroadcast`, `demoteBroadcastJoin`",
        "  (docs/tuning-guide.md, Adaptive query execution).",
        "* The memory ledger (mem/ledger.py) appends `mem` records for",
        "  every allocation-boundary event — alloc/free/spill/unspill",
        "  with buffer ids and byte counts, `oomSpill` rounds carrying",
        "  the triggering reservation site + `cause` id + victim buffer",
        "  ids, and rate-limited per-tier `pressure` samples (the Chrome",
        "  trace's per-worker memory counter lane).  Replay them offline",
        "  with `python -m spark_rapids_tpu.metrics --memory",
        "  <journal-dir>`: peak attribution, spill cascades, churn,",
        "  victim quality, headroom (docs/tuning-guide.md, Memory",
        "  observability and pressure analysis).",
        "* The serving tier (serve/scheduler.py) appends one `sched`",
        "  `admitted` instant per submitted query — queue seconds,",
        "  planning seconds, priority, declared memory need, and the",
        "  plan-cache outcome (`hit`/`miss`/`off`) — into that query's",
        "  OWN journal under its trace context, so concurrent queries'",
        "  scheduling stories never interleave (docs/tuning-guide.md,",
        "  Concurrent serving and plan caching).",
        "* The distributed task scheduler (cluster.py) appends `spec`",
        "  instants for every recovery decision: `speculativeLaunch` /",
        "  `speculationWin` (straggler re-execution races, with stage,",
        "  task index, attempt id and executor), `taskAbandoned` (an",
        "  attempt cut off at its deadline), `workerEvicted` (a",
        "  wedged-but-alive worker replaced like a dead one) and",
        "  `clusterShrunk` (graceful degradation after the replacement",
        "  budget) — how a chaos-soak round's recovery story reads back",
        "  (docs/tuning-guide.md, Fault tolerance, speculation, and",
        "  chaos testing).",
        "* Mesh-lowered shuffle exchanges bracket every compiled",
        "  all-to-all dispatch in a `collective` span (attrs `shuffle`,",
        "  `map`, `devices`, `quota`) — the mesh tier's twin of the",
        "  socket tier's `fetch`/`serve` spans, visible on the PR-7",
        "  timeline; the `stage` mapStage event carries `tier=ici` or",
        "  `tier=socket` so a trace says which tier served each exchange",
        "  (docs/tuning-guide.md, Mesh-native ICI shuffle).",
        "* The data-movement policy engine (policy/) appends `policy`",
        "  instants for every decision it takes: `victim` (next-use",
        "  spill victim picks, with the baseline victim and whether the",
        "  policy overrode it), `unspill` (proactive unspills charged to",
        "  the owning query), `backpressure` (serve/fetch stalls against",
        "  the reduce-rate window) and `codec` (runtime wire codec",
        "  re-selection).  The `--memory` replay renders them as a",
        "  'policy decisions' section from the journal shards alone",
        "  (docs/tuning-guide.md, Data-movement policy).",
        "* `spark_rapids_tpu.metrics.read_journal(path)` parses a file;",
        "  `journal.validate_events(events)` checks the schema.",
        "",
        "## Data-movement counters",
        "",
        "At `metrics.level` MODERATE and above the operators count the",
        "bytes they move off HBM, from batch metadata (capacities x",
        "dtype widths, never a device read): `h2dBytes` (scan uploads,",
        "host->device transitions, socket-tier shuffle reads), `d2hBytes`",
        "(results, broadcast collects, exchange map writes), `wireBytes`",
        "(socket shuffle wire and broadcast payloads) and `iciBytesMoved`",
        "(mesh-exchange collectives and the SPMD aggregate, join and",
        "sort's row exchanges).  The benchmark reads the first two and",
        "the last per query (`chipbench/layer_metrics/h2d_mb_per_query`,",
        "`d2h_mb_per_query`, `ici_mb_per_query`); the codec advisor",
        "(policy/codec.py) judges wire bytes against its wire peak,",
        "`spark.rapids.sql.tpu.roofline.peakWireGBs`.",
        "",
        "## Serving SLO histograms",
        "",
        "The query scheduler keeps one latency histogram per",
        "(phase, priority class): `queue` (submit->admission), `plan`,",
        "`compile` (stageCompileTime — node-recorded, so per-query),",
        "`execute`, `spill` (attributed via the query's OWN memory",
        "scope — the shared runtime spillTime gauge cannot say which",
        "concurrent query paid a cascade), and `total` (metrics/slo.py;",
        "log-spaced buckets 0.5ms..~1000s).",
        "`scheduler.stats()['slo']` reports p50/p95/p99 per phase per",
        "priority; `scheduler.prometheus()` (or",
        "`export.prometheus_serve_dump`) renders standard Prometheus",
        "`_bucket`/`_sum`/`_count` histogram samples, plus per-priority",
        "fair-share gauges: `serve_queue_depth`, `serve_admitted_total`,",
        "`serve_admission_rejections_total`",
        "(`export.cluster_snapshot(cluster, scheduler=...)` carries the",
        "same block as `_serve`).",
        "",
        "## Flight recorder",
        "",
        "Every process (driver AND each executor worker) keeps an",
        "always-on bounded ring of the last",
        "`spark.rapids.sql.tpu.telemetry.ring.maxEvents` journal records",
        "it emitted, fed by a journal tap (metrics/ring.FlightRecorder)",
        "— so the final seconds of a crashing or wedged process are",
        "recoverable even for journals that were never file-backed or",
        "were already drained.  Alongside it a gauge-sampler daemon",
        "thread snapshots registered gauge sources (pool stats,",
        "transport counters, in-flight tasks, scheduler queue depths)",
        "every `telemetry.sampleIntervalMs` into bounded time series;",
        "each tick also journals ONE `metric`-kind `gaugeSample`",
        "instant, so the series drain with the trace shards and render",
        "offline as Chrome COUNTER lanes (one track per worker for",
        "`device_used`, `in_flight_tasks`, `spill_bytes` via",
        "`--timeline --chrome`).  Disable the whole plane with",
        "`spark.rapids.sql.tpu.telemetry.enabled=false`, or just the",
        "sampler with `telemetry.sampleIntervalMs=0`; overhead is gated",
        "in CI at <=2% wall time (scripts/obs_overhead.py,",
        "BENCH_OBS.json).",
        "",
        "## Live endpoints",
        "",
        "With `spark.rapids.sql.tpu.telemetry.http.enabled` (default on",
        "when telemetry is on), the driver and every worker serve a tiny",
        "stdlib HTTP endpoint on 127.0.0.1 (driver port:",
        "`telemetry.http.port`, 0 = ephemeral; workers always ephemeral,",
        "advertised in their ready line):",
        "",
        "* `/metrics` — Prometheus text format of the sampler's newest",
        "  values plus engine hygiene counters (same naming rules as",
        "  `export.py`; `parse_prometheus` round-trips it).",
        "* `/healthz` — JSON liveness verdict (HTTP 503 when unhealthy:",
        "  hung tasks / heartbeat lag on the driver, shutdown on a",
        "  worker).",
        "* `/debug/observability` — JSON debug snapshot: ring/sampler",
        "  stats plus `session_observability` and progress on the",
        "  driver, task counters on a worker.",
        "",
        "## Post-mortem bundles",
        "",
        "Set `spark.rapids.sql.tpu.telemetry.postmortem.dir=/path` to arm",
        "automatic first-failure diagnostics: on query failure, hung-task",
        "watchdog fire, retry-budget exhaustion, or SIGUSR1, the driver",
        "assembles one self-contained bundle directory",
        "(`postmortem-<seq>-<reason>-<pid>/`) holding manifest.json,",
        "config.json, explain.txt (EXPLAIN with per-node metrics),",
        "progress/observability/slo JSON, timeline.json (merged cluster",
        "timeline), memledger.txt, samples.json (sampler series),",
        "ring-driver.jsonl and one ring-<executor>.jsonl per worker",
        "(fetched over a dedicated control RPC with a timeout — a dead",
        "worker costs one missing file, not the bundle).  Dumps are",
        "rate-limited by `telemetry.postmortem.minIntervalMs`.",
        "`session.dump_diagnostics()` takes one on demand;",
        "`python -m spark_rapids_tpu.metrics postmortem <bundle-dir>`",
        "renders any bundle back into a human report.",
        "",
        "## Prometheus export",
        "",
        "`QueryExecution.prometheus()` renders one query;",
        "`metrics.export.prometheus_cluster_dump(cluster)` renders the",
        "cluster-wide rollup (per-worker `transport_counters` +",
        "`pool_stats`, pulled over a DEDICATED control dial with a",
        "per-worker timeout for `ProcCluster` — a dead or wedged worker",
        "yields a partial snapshot labeled `stale=\"true\"` and",
        "`executor_up 0`, never an exception; pass `scheduler=` to",
        "append the serving-tier fairness gauges and SLO histograms).",
        "Write either string where your Prometheus textfile collector",
        "looks.  Names are prefixed `spark_rapids_tpu_` and snake_cased;",
        "timers gain `_seconds`.  `parse_prometheus` is the exact",
        "inverse (histogram buckets, label-less samples and escaped",
        "label values included) — the round-trip the tests rely on.",
        "",
        "## Trace viewer",
        "",
        "`utils.tracing.write_chrome_trace(events, path)` converts journal",
        "events to the Chrome trace-event format, loadable in the XLA/JAX",
        "trace viewer (TensorBoard profile plugin / Perfetto) next to the",
        "device timeline `utils.tracing.profile_trace` captures; pass",
        "`journal=` to `profile_trace` to emit both into one log dir.",
        "",
        "## Profiler spans and program names",
        "",
        "The profiler's trace (`.xplane.pb`) is where the program's spans",
        "and the device's ops share one clock.  Capture one with",
        "`with utils.tracing.profile_trace(log_dir): df.collect()` and",
        "read it with `python chipbench/xplane.py <file.xplane.pb>` (or",
        "TensorBoard's profile plugin / Perfetto).",
        "",
        "`utils.tracing.named_range` is the way the program opens a span",
        "by hand: a `jax.profiler.TraceAnnotation` named `srt:<name>` and",
        "nothing else (no `jax.named_scope`: a scope marks only ops traced",
        "inside it and nothing reads it), optionally feeding a timer metric",
        "(`named_range(name, metrics, MN.X)`: span and timer are one",
        "site).  `^srt:` separates the program's spans from JAX's runtime",
        "spans.  A span never reads or syncs the device; host-known counts",
        "ride as annotation arguments (`q=<query id>`, the journal's, on",
        "the phase spans; `rows=`, `bytes=`).  The span that caused a span",
        "is the one it nests in on its thread.  Every operator's PULL span",
        "is opened for it, at one site (`exec/base.py",
        "ExecNode.__init_subclass__`): each `next()` on what an operator's",
        "`execute`, `execute_cpu` or `execute_partitions` returns runs",
        "inside `srt:op:<ClassName>@<node id>`, so the innermost open",
        "`srt:op:` span of a thread is the operator whose Python is",
        "running, a launch's owner is the span its host call",
        "(`PjitFunction(<name>)`) was made in, and an operator's self time",
        "is its spans less what its children's cover.  One span a pull of",
        "a NODE: an entry point reached from inside the node's own pull (a",
        "subclass's `super().execute(ctx)`, an `execute` that drains",
        "`self.execute_partitions`) opens no twin.",
        "",
        "Span | Where | Covers",
        "-----|-------|-------",
        "`srt:op:<ClassName>@<node id>` | `exec/base.py`, around every "
        "pull of every operator's iterator (the bare annotation, `q=<query "
        "id>` where the context knows it; no `@<id>` before "
        "`QueryExecution` has numbered the node; not `#`, which ends a "
        "name that carries arguments) | one `next()`: the "
        "operator's own Python, its launches, and nested inside it its "
        "children's pulls and its hand-placed spans below",
        "`srt:plan` | `DataFrame.to_arrow` / `to_device_batches`, the "
        "scheduler's worker | `session.plan` (pushdown, tagging, overrides, "
        "transitions, fusion) and the output schema; the query id does not "
        "exist yet",
        "`srt:begin` | `TpuSession._collect_physical` / "
        "`to_device_batches` | adaptive wrap, `QueryExecution.__init__` "
        "(node ids, metric levels, runtime snapshot, journal open), the "
        "`ExecContext`",
        "`srt:execute` | `_collect_physical` | the drain of "
        "`physical.execute_cpu(ctx)`: every operator span nests inside it",
        "`srt:semaphore` | inside `srt:execute` | the wait for the device "
        "slot",
        "`srt:d2h` | `DeviceToHostExec` | `batch.to_arrow()`: the wait for "
        "the device and the copy to the host (timer `d2hTime`)",
        "`srt:finish` | `TpuSession._finish_execution` | `qe.finish`, "
        "`qe.aggregate`, the session's counter fold",
        "`srt:metrics_fold` | `Metrics._fold_lazy_locked`, only when "
        "something is pending | the eager `stack/astype/sum/device_get` of "
        "the lazy row counts",
        "`srt:rows` | `DataFrame.collect` | arrow to Python tuples",
        "`srt:scan_decode` | `io/scan.py` device-decode paths (Parquet per "
        "row-group chunk, ORC per column, CSV per chunk), on the prefetch "
        "thread when prefetch is on | footer and page parsing, "
        "decompression, H2D enqueue, decode dispatches (timer `scanTime`)",
        "`srt:scan_column` | `io/scan.py _decode_field`, one a column and "
        "row-group chunk, on the Parquet column pool's threads (on the "
        "prefetch thread where a chunk has one column) | that column's "
        "header walk, decompression and every launch it makes "
        "(`jit_scan.pq_*`, the dictionary gathers' eager `jnp.take`): the "
        "span of the thread that launches (`rows=`, `column=`)",
        "`srt:agg_shrink` | `exec/aggregate.py`, the GROUPED streaming "
        "loop only (its update sorts at capacity), one a batch of capacity "
        "8,192 or more, before its `srt:agg_update` | the read of the "
        "batch's live-row count (the wait for the program before it; "
        "counted in `aggHostSyncs`) and `maybe_shrink`'s eager compaction; "
        "an aggregate with no grouping keys has none: its loop is one "
        "`srt:agg_update` a batch around one `jit_agg.stream_step` "
        "(`aggSyncFreeBatches`)",
        "`srt:agg_fold` | `exec/aggregate.py`, the GROUPED streaming "
        "loop's `fold`, one a fold of two or more partial states: every "
        "`agg.mergeFanIn` batches and once on the tail | one read of the "
        "parts' live-row counts (`jit_agg.part_rows`, counted once in "
        "`aggHostSyncs`) and the launch of `jit_agg.fold`, which places "
        "every part's live prefix and merges in one program (counted in "
        "`aggFusedFolds`); no eager op; `srt:agg_merge` nests in it "
        "around the launch (`parts=` in the annotation)",
        "`srt:agg_whole_stage_bail` | `exec/aggregate.py "
        "_try_whole_stage`, zero-length | why the input takes the "
        "streaming loop (`reason=bytes`: past half of "
        "`spark.rapids.sql.batchSizeBytes`; `shapes`; `names`) and after "
        "how many `batches` the probe knew; `aggStreamedBatches` counts "
        "the batches the loop then takes",
        "`srt:broadcast_collect` | `exec/broadcast.py "
        "TpuBroadcastExchangeExec._collect`, once an exchange (the plan "
        "is made anew for every query, so once a query and build side) | "
        "the build side's child executed, its batches concatenated, "
        "`batch_to_host`: the wait for the device and the copy of the "
        "batch AT ITS CAPACITY to the host (timers `collectTime`, "
        "`buildTime`; counters `broadcastBytes`, `broadcastRows`)",
        "`srt:broadcast_upload` | `TpuBroadcastExchangeExec"
        ".broadcast_batch` | `host_to_batch`: the host form back to the "
        "device for the join (`bytes=` in the annotation)",
        "`srt:window` | `exec/window.py TpuWindowExec._run`, one a launch "
        "of the window kernel (one a query unless the external path "
        "partitions the input), AFTER the child has been drained | the "
        "coalesce of the drained batches to one and the enqueue of "
        "`jit_sort.window` (`rows=` at capacity and `batches=` in the "
        "annotation; timer `windowTime`, counters `windowRows`, "
        "`windowBatches`)",
        "`srt:<operator>` | `exec/` | `srt:agg_whole_stage`, "
        "`srt:agg_whole_stage_bucket`, `srt:agg_update`, `srt:agg_merge`, "
        "`srt:whole_stage_<id>`, `srt:join_build`, `srt:join_stream`, "
        "`srt:join_full_tail`, `srt:dist_agg`, `srt:dist_join`, "
        "`srt:dist_sort`, and a row-local operator's own name.  No span "
        "stays open across a `yield`: `srt:dist_join` and its timer "
        "`distributedJoinTime` are one a probe chunk and leave the "
        "consumer's time out since PR 35 (held across the yields before "
        "it: earlier readings of either are not comparable)",
        "",
        "Every executable the program compiles goes through",
        "`utils/kernel_cache.named_jit` (`cached_kernel`,",
        "`stage_executable`, the SPMD steps) and is named",
        "`<layer>.<role>`: the trace's `XLA Modules` line and JAX's",
        "`PjitFunction(...)` host spans read `jit_agg.whole_stage`,",
        "`jit_scan.pq_sdict`, `jit_join.hashjoin_probe`,",
        "`jit_dist.join_probe`, `jit_mem.contig_pack`,",
        "`jit_stage.wholeStage-3`.  `<layer>` comes from the module the",
        "program's builder lives in (`kernel_cache._LAYER_OF_MODULE`: `scan`,",
        "`agg`, `join`, `sort`, `stage`, `dist`, `mem`, `expr`; an unmapped",
        "module raises).  `<role>` is the cache key's string head (an",
        "operator's class name shortened: `TpuHashJoinExec` -> `hashjoin`)",
        "plus the word the call site appended after the key's last tuple",
        "(`probe`, `merge`, `bucket`; a join with a residual condition",
        "launches its probe and recount as `hashjoin_probe_residual` and",
        "`hashjoin_count_residual`, a semi or anti join's walk of its",
        "undecided rows as `hashjoin_rest`), or `stage_executable`'s",
        "`name`.  A",
        "launch that carries no such name is an eager `jnp` op outside any",
        "compiled program.",
        "",
        "The benchmark (`chipbench/`) reads these names and counters.  Two",
        "of its per-layer metrics, by example: `join_semi_batches_per_query`",
        "is the counter `joinSemiBatches` a query (a left semi or anti",
        "join's stream batches through its membership mask, the program",
        "`jit_join.hashjoin_semi`); `agg_whole_stage_roofline_share` is the",
        "grouped whole-stage SORT program's share of its HBM roofline: the",
        "bytes it must move over 819 GB/s, over the device time of the",
        "launches named `jit_agg.whole_stage` (not",
        "`jit_agg.whole_stage_bucket`).  `chipbench/README.md` and",
        "`PERF.md` list the rest.",
        "",
        "## Metric catalog",
        "",
        "Name | Kind | Level | Description",
        "-----|------|-------|------------",
    ]
    for name, kind, level, doc in N.catalog_rows():
        lines.append(f"{name}|{kind}|{level}|{doc}")
    lines += [
        "",
        "See also: docs/tuning-guide.md (how the knobs move these",
        "numbers), docs/configs.md (the conf registry).",
        "",
    ]
    return "\n".join(lines)


def write_monitoring_docs(path: str = None) -> str:
    if path is None:
        path = os.path.join(_REPO_ROOT, "docs", "monitoring.md")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(monitoring_doc())
    return path


def timeline_main(argv) -> int:
    """--timeline driver: merge a journal dir offline and report."""
    from ..config import TRACE_STRAGGLER_FACTOR
    from .timeline import load_journal_dir, merge_shards
    usage = ("usage: python -m spark_rapids_tpu.metrics --timeline "
             "<journal-dir> [--chrome out.json] [--straggler-factor K]")
    factor = float(TRACE_STRAGGLER_FACTOR.default)
    chrome = None
    jdir = None
    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg in ("--chrome", "--straggler-factor"):
            # flag VALUES are consumed here, never mistaken for the
            # journal-dir positional; a missing value is a usage error,
            # not an IndexError
            if i + 1 >= len(argv):
                print(usage, file=sys.stderr)
                return 2
            if arg == "--chrome":
                chrome = argv[i + 1]
            else:
                try:
                    factor = float(argv[i + 1])
                except ValueError:
                    print(usage, file=sys.stderr)
                    return 2
            i += 2
        elif arg.startswith("--") or jdir is not None:
            print(usage, file=sys.stderr)
            return 2
        else:
            jdir = arg
            i += 1
    if jdir is None:
        print(usage, file=sys.stderr)
        return 2
    shards = load_journal_dir(jdir)
    if not shards:
        print(f"no shard-*.jsonl / query-*.jsonl journals in {jdir}",
              file=sys.stderr)
        return 1
    tl = merge_shards(shards)
    print(tl.render(factor))
    if chrome is not None:
        from ..utils.tracing import write_cluster_chrome_trace
        print(f"chrome trace: {write_cluster_chrome_trace(tl, chrome)}")
    return 0


def memory_main(argv) -> int:
    """--memory driver: replay a journal dir's memory-ledger records."""
    from .memledger import DEFAULT_RETOUCH_WINDOW, analyze_shards, render
    from .timeline import load_journal_dir
    usage = ("usage: python -m spark_rapids_tpu.metrics --memory "
             "<journal-dir> [--retouch-window K]")
    window = DEFAULT_RETOUCH_WINDOW
    jdir = None
    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg == "--retouch-window":
            if i + 1 >= len(argv):
                print(usage, file=sys.stderr)
                return 2
            try:
                window = int(argv[i + 1])
            except ValueError:
                print(usage, file=sys.stderr)
                return 2
            i += 2
        elif arg.startswith("--") or jdir is not None:
            print(usage, file=sys.stderr)
            return 2
        else:
            jdir = arg
            i += 1
    if jdir is None:
        print(usage, file=sys.stderr)
        return 2
    shards = load_journal_dir(jdir)
    if not shards:
        print(f"no shard-*.jsonl / query-*.jsonl journals in {jdir}",
              file=sys.stderr)
        return 1
    rep = analyze_shards(shards, retouch_window=window)
    if rep["totals"]["events"] == 0:
        print(f"journals in {jdir} carry no memory-ledger records "
              "(is spark.rapids.sql.tpu.memory.ledger.enabled on?)",
              file=sys.stderr)
        return 1
    print(render(rep))
    return 0


def postmortem_main(argv) -> int:
    """`postmortem` driver: render a diagnostics bundle directory
    (metrics/bundle.dump_diagnostics output) as the human report, or
    dump the parsed bundle as JSON with --json."""
    import json as _json

    from .bundle import MANIFEST, load_bundle, render_bundle
    usage = ("usage: python -m spark_rapids_tpu.metrics postmortem "
             "<bundle-dir> [--json]")
    as_json = False
    bdir = None
    for arg in argv:
        if arg == "--json":
            as_json = True
        elif arg.startswith("--") or bdir is not None:
            print(usage, file=sys.stderr)
            return 2
        else:
            bdir = arg
    if bdir is None:
        print(usage, file=sys.stderr)
        return 2
    if not os.path.isfile(os.path.join(bdir, MANIFEST)):
        print(f"{bdir} holds no {MANIFEST} — not a post-mortem bundle",
              file=sys.stderr)
        return 1
    if as_json:
        print(_json.dumps(load_bundle(bdir), indent=1, default=str,
                          sort_keys=True))
    else:
        print(render_bundle(bdir), end="")
    return 0


if __name__ == "__main__":
    if "--lint" in sys.argv:
        sys.exit(lint())
    if "--timeline" in sys.argv:
        idx = sys.argv.index("--timeline")
        sys.exit(timeline_main(sys.argv[idx + 1:]))
    if "--memory" in sys.argv:
        idx = sys.argv.index("--memory")
        sys.exit(memory_main(sys.argv[idx + 1:]))
    if "postmortem" in sys.argv[1:2]:
        sys.exit(postmortem_main(sys.argv[2:]))
    print(write_monitoring_docs())
