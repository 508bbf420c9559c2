"""Central metric-name catalog.

Reference analogue: the metric-name constants and metric-level machinery in
GpuExec.scala (NUM_OUTPUT_ROWS/NUM_OUTPUT_BATCHES/TOTAL_TIME/... plus
MetricsLevel gating via spark.rapids.sql.metrics.level) — every operator
emits only names registered here, and each name carries a level so
expensive diagnostics can be compiled out of the hot path.

Levels (ordered): ESSENTIAL < MODERATE < DEBUG.  A metric is recorded when
its registered level is <= the session's configured level
(`spark.rapids.sql.tpu.metrics.level`):

  * ESSENTIAL — correctness-adjacent counts that are free to maintain
    (host-side increments only; the Spark UI always shows these);
  * MODERATE  — wall-clock timers and lazily folded device row counts (one
    extra device op per batch at most, never a sync);
  * DEBUG     — anything that forces a per-batch device sync or other
    measurable overhead (eager row counts, peak-memory sampling).

The lint tier (tests/test_metrics.py + `python -m spark_rapids_tpu.metrics
--lint`) asserts every `metrics.add/add_lazy/timer` call site in the tree
uses a registered name, so a typo'd key (`numOutputRow`) fails CI instead
of silently splitting a counter.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

ESSENTIAL = 1
MODERATE = 2
DEBUG = 3

LEVEL_NAMES = {ESSENTIAL: "ESSENTIAL", MODERATE: "MODERATE", DEBUG: "DEBUG"}

# metric kinds (drive the Prometheus TYPE line and the journal/export
# formatting; timers are seconds)
COUNTER = "counter"
GAUGE = "gauge"
TIMER = "timer"


class MetricSpec(NamedTuple):
    name: str
    kind: str
    level: int
    doc: str


METRICS: Dict[str, MetricSpec] = {}


def register_metric(name: str, kind: str, level: int, doc: str) -> str:
    """Register a metric name; returns the name so constants read cleanly."""
    if name in METRICS:
        raise ValueError(f"duplicate metric name {name}")
    if kind not in (COUNTER, GAUGE, TIMER):
        raise ValueError(f"unknown metric kind {kind!r}")
    if level not in LEVEL_NAMES:
        raise ValueError(f"unknown metric level {level!r}")
    METRICS[name] = MetricSpec(name, kind, level, doc)
    return name


def is_registered(name: str) -> bool:
    return name in METRICS


def metric_level(name: str) -> int:
    """Level gate for a name; unregistered names are treated as ESSENTIAL
    (always recorded) but remembered by the registry for the lint tier."""
    spec = METRICS.get(name)
    return spec.level if spec is not None else ESSENTIAL


# --- standard per-operator metrics (GpuExec.scala:24-41 analogues) ----------
NUM_OUTPUT_ROWS = register_metric(
    "numOutputRows", COUNTER, ESSENTIAL, "rows produced by the operator")
NUM_OUTPUT_BATCHES = register_metric(
    "numOutputBatches", COUNTER, ESSENTIAL,
    "columnar batches produced by the operator")
NUM_OUTPUT_BYTES = register_metric(
    "numOutputBytes", COUNTER, ESSENTIAL, "bytes written by a write command")
NUM_FILES = register_metric(
    "numFiles", COUNTER, ESSENTIAL, "files read by a scan / written by a write")
NUM_PARTS = register_metric(
    "numParts", COUNTER, ESSENTIAL, "partitions produced by an exchange")
DATA_SIZE = register_metric(
    "dataSize", COUNTER, ESSENTIAL, "bytes of a broadcast/exchanged payload")
NUM_CPU_FALLBACKS = register_metric(
    "numCpuFallbacks", COUNTER, ESSENTIAL,
    "times an exhausted device operator re-executed on its CPU twin")
NUM_PARTITIONS_WRITTEN = register_metric(
    "numPartitionsWritten", COUNTER, ESSENTIAL,
    "shuffle partition sub-batches written by the map side")

TOTAL_TIME = register_metric(
    "totalTime", TIMER, MODERATE, "operator wall-clock time")
SCAN_TIME = register_metric(
    "scanTime", TIMER, MODERATE,
    "scan decode + H2D time: host decode plus the H2D copy on the host "
    "path; on the device-decode paths (Parquet, ORC, CSV) the host time "
    "inside `srt:scan_decode` (page parsing, decompression, H2D enqueue, "
    "decode dispatches), summed over the decode threads")
SCAN_CACHE_HIT_BATCHES = register_metric(
    "scanCacheHitBatches", COUNTER, ESSENTIAL,
    "batches an in-memory scan served from the device scan cache "
    "(`utils/scan_cache.py`) instead of uploading them, added once an "
    "execute when the scan ends, not a batch, so the hit loop stays as "
    "it was; 0 where the scan uploaded, so a table that is not "
    "resident (over `resident_bound`, or evicted) reads 0 and not absent; "
    "a host integer")
CONCAT_TIME = register_metric(
    "concatTime", TIMER, MODERATE, "batch coalesce/concat time")
SORT_TIME = register_metric(
    "sortTime", TIMER, MODERATE, "device sort time")
JOIN_TIME = register_metric(
    "joinTime", TIMER, MODERATE, "join probe/stream time")
BUILD_TIME = register_metric(
    "buildTime", TIMER, MODERATE, "join build-side time")
COMPUTE_AGG_TIME = register_metric(
    "computeAggTime", TIMER, MODERATE, "per-batch partial aggregation time")
MERGE_AGG_TIME = register_metric(
    "mergeAggTime", TIMER, MODERATE, "partial-aggregate merge time")
WINDOW_TIME = register_metric(
    "windowTime", TIMER, MODERATE, "window function time")
WINDOW_ROWS = register_metric(
    "windowRows", COUNTER, ESSENTIAL,
    "rows AT CAPACITY the window kernel ran over (TpuWindowExec: one sort "
    "by partition and order keys, the functions as segmented scans, the "
    "inverse permutation): what its cost follows, live or not; a host "
    "integer from the batch's shape, never a sync")
WINDOW_BATCHES = register_metric(
    "windowBatches", COUNTER, ESSENTIAL,
    "launches of the window kernel: 1 where the input coalesced to one "
    "batch, one a hash partition on the external path; a host integer")
EXPAND_OUTPUT_ROWS = register_metric(
    "expandOutputRows", COUNTER, ESSENTIAL,
    "rows AT CAPACITY an Expand (ROLLUP/CUBE fan-out) emitted: its input "
    "batch's capacity times its projections, summed over the batches; "
    "added on the host by whoever ran the Expand's program (the operator, "
    "the whole stage it is fused in, or the aggregate that absorbed that "
    "stage), on the stage's plan node; never a sync")
EXPAND_BATCHES = register_metric(
    "expandBatches", COUNTER, ESSENTIAL,
    "input batches an Expand fanned out; a host integer")
GENERATE_TIME = register_metric(
    "generateTime", TIMER, MODERATE, "generator (explode) time")
COLLECT_TIME = register_metric(
    "collectTime", TIMER, MODERATE, "broadcast build-side collect time")
WRITE_TIME = register_metric(
    "writeTime", TIMER, MODERATE, "file write/encode time")
SHUFFLE_READ_TIME = register_metric(
    "shuffleReadTime", TIMER, MODERATE, "shuffle fetch/read time")
SHUFFLE_WRITE_TIME = register_metric(
    "shuffleWriteTime", TIMER, MODERATE, "shuffle partition/write time")
H2D_TIME = register_metric(
    "h2dTime", TIMER, MODERATE, "host->device adoption time")
D2H_TIME = register_metric(
    "d2hTime", TIMER, MODERATE, "device->host materialization time")
DISTRIBUTED_AGG_TIME = register_metric(
    "distributedAggTime", TIMER, MODERATE, "SPMD distributed aggregate time")
DISTRIBUTED_JOIN_TIME = register_metric(
    "distributedJoinTime", TIMER, MODERATE,
    "SPMD distributed join time: the join's own work, one reading a probe "
    "chunk (the consumer's time between chunks is not in it)")
DISTRIBUTED_SORT_TIME = register_metric(
    "distributedSortTime", TIMER, MODERATE, "SPMD distributed sort time")
NUM_ICI_EXCHANGES = register_metric(
    "numIciExchanges", COUNTER, ESSENTIAL,
    "generic shuffle exchanges lowered into jitted ICI collectives over "
    "the device mesh (shuffle/mesh_exchange.py): chain + partition-id "
    "compute + all-to-all as one compiled program, data never leaving "
    "HBM.  The socket tier's exchanges do not count here")
COLLECTIVE_TIME = register_metric(
    "collectiveTime", TIMER, MODERATE,
    "wall-clock time inside mesh-exchange collective dispatches (the "
    "compiled shard_map all-to-all programs, overflow retries included)")
SEMAPHORE_WAIT_TIME = register_metric(
    "semaphoreWaitTime", TIMER, MODERATE,
    "time blocked acquiring the device task semaphore")

# --- scan/write internals ---------------------------------------------------
NUM_STRIPES = register_metric(
    "numStripes", COUNTER, MODERATE, "ORC stripes read")
NUM_STRIPES_SKIPPED = register_metric(
    "numStripesSkipped", COUNTER, MODERATE,
    "ORC stripes pruned by footer statistics")
NUM_ROW_GROUPS = register_metric(
    "numRowGroups", COUNTER, MODERATE, "parquet row groups read")
NUM_ROW_GROUPS_SKIPPED = register_metric(
    "numRowGroupsSkipped", COUNTER, MODERATE,
    "parquet row groups pruned by predicate pushdown")
NUM_DEVICE_DECODED_COLUMNS = register_metric(
    "numDeviceDecodedColumns", COUNTER, MODERATE,
    "columns decoded by device kernels (vs host fallback)")
SCAN_PAGE_COPIES = register_metric(
    "scanPageCopies", COUNTER, MODERATE,
    "masked range copies over a whole chunk buffer (`jit_scan.pq_copy_*`) "
    "the Parquet device decode made to place ONE page or ONE group of "
    "pages: 0 where every chunk assembled in whole-chunk launches "
    "(strings always; numbers unless page encodings mix or are "
    "DELTA_BINARY_PACKED / BYTE_STREAM_SPLIT / boolean PLAIN); grows with "
    "the file's page count where it is not 0; present and 0 for a scan "
    "that decoded a column on the device; a host integer")
NUM_DEVICE_DECODE_ERRORS = register_metric(
    "numDeviceDecodeErrors", COUNTER, MODERATE,
    "columns that fell back to the host reader after a device decode error")
NUM_DEVICE_ENCODED_FILES = register_metric(
    "numDeviceEncodedFiles", COUNTER, MODERATE,
    "files encoded by device write kernels")

# --- memory / retry (mem/runtime.py + mem/retry.py) -------------------------
OOM_SPILL_RETRIES = register_metric(
    "oomSpillRetries", COUNTER, ESSENTIAL,
    "allocation attempts retried behind a synchronous spill")
OOM_SPILL_BYTES = register_metric(
    "oomSpillBytes", COUNTER, ESSENTIAL,
    "bytes spilled out of the device store by the OOM cascade")
OOM_ALLOC_FAILURES = register_metric(
    "oomAllocFailures", COUNTER, ESSENTIAL,
    "reserve() calls that raised RetryOOM after the spill cascade")
PEAK_DEV_MEMORY = register_metric(
    "peakDevMemory", GAUGE, DEBUG,
    "high-water mark of accounted device-store bytes sampled per batch")

# --- memory ledger (mem/ledger.py + metrics/memledger.py) --------------------
MEM_LEDGER_EVENTS = register_metric(
    "memLedgerEvents", COUNTER, MODERATE,
    "records the memory-pressure ledger journaled (alloc/free/spill/"
    "unspill/oomSpill/oomFail, journal kind 'mem'); the raw material of "
    "python -m spark_rapids_tpu.metrics --memory")
NUM_BUFFER_RESPILLS = register_metric(
    "numBufferRespills", COUNTER, ESSENTIAL,
    "device buffers spilled AGAIN after an earlier spill+unspill round "
    "trip — spill churn (thrash): the victim-selection quality signal "
    "the data-movement scheduler is judged against")

# --- data integrity (mem/integrity.py + shuffle fetch/spill verify) ---------
NUM_CHECKSUM_MISMATCHES = register_metric(
    "numChecksumMismatches", COUNTER, ESSENTIAL,
    "buffer leaves whose checksum verification failed (wire fetch, "
    "spill/unspill, disk read, or verified local read)")
NUM_CORRUPTION_REFETCHES = register_metric(
    "numCorruptionRefetches", COUNTER, ESSENTIAL,
    "shuffle buffer refetches issued after a checksum mismatch "
    "classified as transient (wire/reader-side corruption)")
NUM_LOST_MAP_OUTPUTS = register_metric(
    "numLostMapOutputs", COUNTER, ESSENTIAL,
    "map outputs declared lost after persistent corruption, a vanished "
    "buffer, or a dead peer (FetchFailed -> map-fragment recompute)")
CHECKSUM_TIME = register_metric(
    "checksumTime", TIMER, MODERATE,
    "time spent computing and verifying shuffle/spill checksums")

# --- shuffle/spill compression (compress/) -----------------------------------
COMPRESSED_SHUFFLE_BYTES_WRITTEN = register_metric(
    "compressedShuffleBytesWritten", COUNTER, ESSENTIAL,
    "physical (compressed) bytes of shuffle buffers served to peers; "
    "compare with bytes_sent for the wire-level view — AQE map statistics "
    "deliberately keep LOGICAL (uncompressed) sizes so re-planning is "
    "codec-invariant")
COMPRESSED_SHUFFLE_BYTES_READ = register_metric(
    "compressedShuffleBytesRead", COUNTER, ESSENTIAL,
    "physical (compressed) bytes of shuffle buffers fetched from peers "
    "before decompression")
COMPRESSED_SPILL_BYTES_WRITTEN = register_metric(
    "compressedSpillBytesWritten", COUNTER, ESSENTIAL,
    "physical (compressed) bytes written to disk by the spill tier")
COMPRESSED_SPILL_BYTES_READ = register_metric(
    "compressedSpillBytesRead", COUNTER, ESSENTIAL,
    "physical (compressed) bytes read back from compressed spill files")
NUM_COMPRESSION_FALLBACKS = register_metric(
    "numCompressionFallbacks", COUNTER, ESSENTIAL,
    "fetches that negotiated DOWN to the raw wire format because the "
    "peer could not serve the requested codec")
COMPRESSION_TIME = register_metric(
    "compressionTime", TIMER, MODERATE,
    "time spent compressing shuffle/spill leaves into framed chunks")
DECOMPRESSION_TIME = register_metric(
    "decompressionTime", TIMER, MODERATE,
    "time spent decompressing framed shuffle/spill leaves")
COMPRESSION_RATIO = register_metric(
    "compressionRatio", GAUGE, MODERATE,
    "best observed raw:compressed ratio of a compressed buffer "
    "(high-water gauge, like peakDevMemory)")

# --- whole-stage fusion (plan/fusion.py + exec/whole_stage.py) ---------------
NUM_FUSED_STAGES = register_metric(
    "numFusedStages", COUNTER, ESSENTIAL,
    "whole-stage fused blocks executed as a single jitted XLA program "
    "(TpuWholeStageExec runs, exchange bucketing fused into its child "
    "stage, aggregate whole-stage absorptions)")
NUM_STAGE_COMPILES = register_metric(
    "numStageCompiles", COUNTER, ESSENTIAL,
    "distinct (stage, batch-shape) XLA programs traced+compiled for "
    "whole-stage fusion; shapes are bucketed to powers of two so this "
    "stays bounded under split-and-retry")
STAGE_COMPILE_TIME = register_metric(
    "stageCompileTime", TIMER, MODERATE,
    "wall-clock time spent tracing and compiling whole-stage programs "
    "(the warmup cost fusion amortizes across batches and queries)")
NUM_FUSION_FALLBACKS = register_metric(
    "numFusionFallbacks", COUNTER, ESSENTIAL,
    "fused stages that exhausted stage-level OOM retries and fell back "
    "to executing their constituent operators one at a time")
NUM_DONATED_BUFFERS = register_metric(
    "numDonatedBuffers", COUNTER, ESSENTIAL,
    "input column buffers donated to compiled stage programs "
    "(donate_argnums input/output aliasing): each one is an HBM "
    "allocation + copy a warm per-batch dispatch did NOT pay; zero "
    "with spark.rapids.sql.tpu.donation.enabled=false or when every "
    "input batch is pinned (scan cache, spillable registration, retry "
    "checkpoint)")

# --- operator kernels (packed-key sort, aggregate bucket update) ------------
NUM_PACKED_SORTS = register_metric(
    "numPackedSorts", COUNTER, ESSENTIAL,
    "sort dispatches that took the packed-key path (sort keys fused "
    "into 64-bit words + embedded row ids, single-operand sort passes) "
    "instead of the N-pass variadic lexsort")
JOIN_MERGED_WINDOW_BATCHES = register_metric(
    "joinMergedWindowBatches", COUNTER, ESSENTIAL,
    "stream batches (mesh: stream chunks) whose candidate windows in the "
    "hash-sorted build side came from one merge of both sides' hashes "
    "(utils/packed_sort.merge_windows: three single-operand sorts and two "
    "prefix scans, no gather) and not from a binary search per row; a "
    "host integer, never a sync")
JOIN_OUTPUT_SPACE_BATCHES = register_metric(
    "joinOutputSpaceBatches", COUNTER, ESSENTIAL,
    "stream batches (mesh: finished stream chunks) of an inner, left or "
    "full join whose pairs were placed from the output's side "
    "(exec/join.py _gather_kernel: the count walk's verified-candidate "
    "bits, one scatter and prefix scans; no key compared twice, no loop "
    "over the stream batch); counts only the batches _gather_kernel "
    "answered, not those joinPassThroughBatches counts (the two together "
    "equal joinMergedWindowBatches for those join types); stays 0 for "
    "semi and anti joins; a host integer")
JOIN_PASS_THROUGH_BATCHES = register_metric(
    "joinPassThroughBatches", COUNTER, ESSENTIAL,
    "stream batches of a one-chip inner, left or full join passed through "
    "under a mask (exec/join.py _passthrough_kernel, "
    "jit_join.hashjoin_passthrough): no live stream row had more than one "
    "candidate and the output's capacity bucket was the stream batch's, "
    "so the output is the stream batch's own column arrays beside the "
    "build rows taken at stream capacity; no stream column gathered; "
    "added on the host after the launch; a host integer")
JOIN_SEMI_BATCHES = register_metric(
    "joinSemiBatches", COUNTER, ESSENTIAL,
    "stream batches (mesh: finished stream chunks) of a left semi or left "
    "anti join answered by the membership mask (exec/join.py "
    "_semi_kernel, jit_join.hashjoin_semi: the stream batch with its "
    "selection cut to the rows the count walk matched, or did not; no "
    "gather); added on the host where the mask's program is launched; "
    "with joinOutputSpaceBatches and joinPassThroughBatches it sums to "
    "joinMergedWindowBatches; a host integer")
JOIN_WALK_STEPS = register_metric(
    "joinWalkSteps", COUNTER, ESSENTIAL,
    "static step counts of the count walks the join launched, summed: "
    "the probe's speculative duplication bucket a stream batch, a "
    "recount's bucket, the mesh drivers' max_dup a chunk and a retry; "
    "each step gathers the build side's keys over the whole stream "
    "batch, so against joinMergedWindowBatches it says how wide the "
    "walks ran; a host integer")
JOIN_HOST_SYNCS = register_metric(
    "joinHostSyncs", COUNTER, ESSENTIAL,
    "host reads of a device value the join made (exec/join.py): the "
    "probe's two scalars a stream batch (the duplication bucket and the "
    "output row count), a recount's total where the bucket grew, the "
    "build side's live-row count before its shrink, a full join's tail "
    "count; the join's twin of aggHostSyncs; a host integer counted where "
    "the read is made")
BROADCAST_BYTES = register_metric(
    "broadcastBytes", COUNTER, ESSENTIAL,
    "bytes of the host form a broadcast exchange collected "
    "(TpuBroadcastExchangeExec._collect: the build side's batch at its "
    "CAPACITY, data, validity and selection, device to host) and uploads "
    "again for the join; what dataSize gets from the exchange; a host "
    "integer")
BROADCAST_ROWS = register_metric(
    "broadcastRows", COUNTER, ESSENTIAL,
    "live rows of the host form a broadcast exchange collected, counted "
    "on the host from the selection it already holds (no device read): "
    "against broadcastBytes, how much of what was shipped was alive")
AGG_DENSE_BATCHES = register_metric(
    "aggDenseBatches", COUNTER, ESSENTIAL,
    "input batches whose grouped-aggregate bucket update finished in one "
    "dense pass (at most 32 groups in the batch: masked reductions "
    "against each group's representative, nothing scattered or gathered "
    "per row; a batch of more takes further passes and counts in "
    "aggBucketBatches alone); read from the same device value as the "
    "batch's clean check, never a sync of its own")
AGG_BUCKET_BATCHES = register_metric(
    "aggBucketBatches", COUNTER, ESSENTIAL,
    "input batches whose grouped-aggregate bucket update was TAKEN, in "
    "any number of dense passes (the batch held at most 1,024 groups, 32 "
    "a pass, chosen by the rows' own 31-bit hash ids, and every id stood "
    "for one distinct key): all batches of a whole-stage bucket program, "
    "or each streaming-loop batch whose `took` read clean; added on the "
    "host where that integer is read; with aggSortPathBatches, which "
    "road the batches of a grouped aggregate took")
AGG_SORT_PATH_BATCHES = register_metric(
    "aggSortPathBatches", COUNTER, ESSENTIAL,
    "input batches whose grouped-aggregate update ran the SORT-based "
    "program (`_update_kernel`: group ids from one sort of the keys, then "
    "segmented reductions): every batch of a whole-stage program that was "
    "not the bucket program, and in the streaming loop every batch the "
    "bucket update did not take (a dirty batch: more than 1,024 groups, "
    "or two keys of one hash id; a latched-dirty shape; an aggregate "
    "that is not bucketable); the grouped loop adds 0 once, so it reads "
    "0 and not absent where the bucket update took every batch; with "
    "aggBucketBatches, which road the batches of a grouped aggregate "
    "took; a host integer, never a sync")
AGG_STREAMED_BATCHES = register_metric(
    "aggStreamedBatches", COUNTER, ESSENTIAL,
    "input batches that went through the aggregate's streaming loop (what "
    "an input past half of batchSizeBytes, or of unequal batch shapes, "
    "takes): the grouped loop's per-batch update, or the keyless loop's "
    "step program; 0 where the whole-stage program answered; a host "
    "integer, never a sync")
AGG_SYNC_FREE_BATCHES = register_metric(
    "aggSyncFreeBatches", COUNTER, ESSENTIAL,
    "input batches the streaming loop of an aggregate with no grouping "
    "keys took through its step program (filter, update and merge into "
    "the running 1-row state in one launch) with no host read, no shrink "
    "and no concat; equals aggStreamedBatches for such a query, 0 for a "
    "grouped one; a host integer")
AGG_HOST_SYNCS = register_metric(
    "aggHostSyncs", COUNTER, ESSENTIAL,
    "host reads of a device value the aggregate made: the bucket "
    "update's clean check and, in the GROUPED streaming loop only, a "
    "batch's live-row count before the shrink and one read a fold of "
    "the parts' live-row counts; present and 0 for a keyless aggregate "
    "through the streaming loop, which reads nothing; a host integer "
    "counted where the read is made")
AGG_FUSED_FOLDS = register_metric(
    "aggFusedFolds", COUNTER, ESSENTIAL,
    "folds of the GROUPED streaming loop answered by the one program "
    "`jit_agg.fold` (the running state and the pending partial states "
    "placed by their live prefixes and merged in one launch, after one "
    "read of their row counts): every agg.mergeFanIn batches and once on "
    "the tail; added on the host after the launch; a host integer, "
    "never a sync")
SEG_AGG_TIME = register_metric(
    "segAggTime", TIMER, MODERATE,
    "segmented-aggregation kernel time inside grouped-aggregate "
    "update/merge dispatches (the per-batch partial-state compute the "
    "fused single-pass segmented reducers accelerate)")

# --- distributed tracing / heartbeats (metrics/timeline.py, cluster.py) ------
HEARTBEAT_LAG = register_metric(
    "heartbeatLag", GAUGE, ESSENTIAL,
    "seconds since the driver's heartbeat monitor last heard from the "
    "slowest worker (high-water over the monitor's lifetime); a growing "
    "lag means a worker stopped answering its dedicated control "
    "connection")
NUM_STRAGGLERS = register_metric(
    "numStragglers", COUNTER, ESSENTIAL,
    "tasks the merged-timeline analysis flagged as stragglers (duration "
    "> spark.rapids.sql.tpu.trace.stragglerFactor x the stage median)")
TRACED_FETCH_LINKS = register_metric(
    "tracedFetchLinks", COUNTER, ESSENTIAL,
    "reducer fetch spans flow-linked to the serving mapper's serve "
    "record in the merged timeline (the cross-worker trace propagation "
    "working end to end)")
NUM_HUNG_TASKS = register_metric(
    "numHungTasks", COUNTER, ESSENTIAL,
    "tasks the hung-task watchdog saw active past "
    "spark.rapids.sql.tpu.trace.hungTaskTimeoutMs in a worker's "
    "heartbeat snapshots (each task is counted once)")
NUM_MISSED_HEARTBEATS = register_metric(
    "numMissedHeartbeats", COUNTER, ESSENTIAL,
    "heartbeat polls that failed or timed out on a worker's dedicated "
    "control connection")

# --- speculative execution / task deadlines (cluster.py) ---------------------
NUM_SPECULATIVE_TASKS = register_metric(
    "numSpeculativeTasks", COUNTER, ESSENTIAL,
    "speculative task copies launched on another worker after the "
    "straggler detector (task > stragglerFactor x stage median, or the "
    "hung-task watchdog bound) flagged the original attempt")
NUM_SPECULATION_WINS = register_metric(
    "numSpeculationWins", COUNTER, ESSENTIAL,
    "speculative races the COPY won (the copy's result was stored and "
    "the original attempt was cancelled/ignored); wins minus launches "
    "says how often speculation paid for itself")
NUM_EVICTED_WORKERS = register_metric(
    "numEvictedWorkers", COUNTER, ESSENTIAL,
    "workers evicted while their process was still ALIVE — wedged past "
    "the task deadline (health probe answered but the task never "
    "returned) or holding a speculation loser's side effects — and "
    "replaced exactly like a dead worker, map fragments recomputed from "
    "the lineage")
NUM_ABANDONED_TASKS = register_metric(
    "numAbandonedTasks", COUNTER, ESSENTIAL,
    "task attempts abandoned past their deadline "
    "(spark.rapids.sql.tpu.task.timeoutMs, derived from "
    "trace.hungTaskTimeoutMs when unset): the rpc was cut off and the "
    "task re-ran elsewhere instead of stalling the wave forever")

# --- serving tier (serve/: scheduler, admission, plan cache) -----------------
QUEUE_TIME = register_metric(
    "queueTime", TIMER, ESSENTIAL,
    "time submitted queries spent waiting in the scheduler's priority "
    "queue before admission (host-side wall clock; free to maintain, so "
    "ESSENTIAL unlike device timers)")
NUM_ADMITTED = register_metric(
    "numAdmitted", COUNTER, ESSENTIAL,
    "queries the scheduler admitted for execution")
NUM_QUEUED_QUERIES = register_metric(
    "numQueuedQueries", GAUGE, ESSENTIAL,
    "high-water mark of queries waiting in the scheduler queue (set_max "
    "gauge, like peakDevMemory; the instantaneous depth is in "
    "scheduler.stats()['queued'])")
NUM_ADMISSION_REJECTIONS = register_metric(
    "numAdmissionRejections", COUNTER, ESSENTIAL,
    "submissions rejected because the scheduler queue was at "
    "spark.rapids.sql.tpu.serve.queue.capacity — the serving tier's "
    "backpressure signal")
PLAN_CACHE_HITS = register_metric(
    "planCacheHits", COUNTER, ESSENTIAL,
    "scheduler submissions whose normalized (literal-lifted) plan was "
    "already cached — these replay compiled whole-stage executables "
    "instead of re-tracing and re-compiling")
PLAN_CACHE_MISSES = register_metric(
    "planCacheMisses", COUNTER, ESSENTIAL,
    "scheduler submissions that created a new plan-cache entry (first "
    "sighting of this plan shape under this conf)")
NUM_BUDGET_OOMS = register_metric(
    "numBudgetOoms", COUNTER, ESSENTIAL,
    "reservations that exceeded a query's serve.queryBudgetBytes after "
    "spilling the query's own buffers — the RetryOOM then drives that "
    "query's (and only that query's) retry/split/CPU-fallback ladder")
NUM_CANCELLED_QUERIES = register_metric(
    "numCancelledQueries", COUNTER, ESSENTIAL,
    "scheduler-run queries terminated by QueryFuture.cancel() or a "
    "token-routed shutdown — dequeued for free while queued, stopped at "
    "the next lifecycle checkpoint while running, then owner-confined "
    "cleanup freed their remaining device/host/disk buffers and shuffle "
    "outputs (serve/lifecycle.py)")
NUM_DEADLINE_SHEDS = register_metric(
    "numDeadlineSheds", COUNTER, ESSENTIAL,
    "queries rejected AT ADMISSION because their remaining deadline "
    "could not cover the estimated plan+compile cost "
    "(serve.deadline.shedSafetyFactor x the scheduler's EWMA) — shed "
    "with a typed QueryDeadlineExceeded instead of admitted doomed")
NUM_DEADLINE_EXCEEDED = register_metric(
    "numDeadlineExceeded", COUNTER, ESSENTIAL,
    "admitted queries that ran past their submit(deadline_ms=) deadline "
    "and were terminated at a lifecycle checkpoint with "
    "QueryDeadlineExceeded — always the late query's OWN failure path, "
    "never a neighbor's")
NUM_PREEMPTIONS = register_metric(
    "numPreemptions", COUNTER, ESSENTIAL,
    "running queries that suspended at a stage boundary to yield the "
    "admission share/device gate to a higher-priority arrival: device "
    "buffers parked as spillable state charged to the victim's budget, "
    "semaphore + admission share released (serve.preemption.enabled)")
NUM_PREEMPTION_RESUMES = register_metric(
    "numPreemptionResumes", COUNTER, ESSENTIAL,
    "preempted queries granted a FIFO-within-priority resume (or "
    "force-resumed at preemption.resumeTimeoutSeconds): they re-took "
    "their admission share and semaphore slots and continued in place, "
    "bit-for-bit with the unpreempted run; suspend-to-resume latency "
    "lands in the SLO 'preempt' phase histograms")

# --- streaming micro-batch engine (streaming/, ISSUE 20) ---------------------
NUM_EPOCHS = register_metric(
    "numEpochs", COUNTER, ESSENTIAL,
    "streaming micro-batch epochs committed: each epoch sliced unread "
    "source rows, ran the partial-aggregate delta query through the "
    "scheduler (replaying compiled stages via the plan cache), folded "
    "the delta into the device-resident state with the aggregate merge "
    "kernel, and atomically committed offsets + state snapshot")
EPOCH_TIME = register_metric(
    "epochTime", TIMER, ESSENTIAL,
    "wall seconds per committed streaming epoch (delta query + state "
    "fold + checkpoint commit); the per-priority distribution lands in "
    "the SLO 'epoch' phase histograms")
STREAM_STATE_BYTES = register_metric(
    "streamStateBytes", GAUGE, ESSENTIAL,
    "device bytes of streaming aggregation state resident in HBM "
    "between epochs — owner-stamped spillable buffers, so per-query "
    "budgets, policy victim selection and the memory ledger all see "
    "them; released by StreamingQuery.stop()")
NUM_STATE_RECOVERIES = register_metric(
    "numStateRecoveries", COUNTER, ESSENTIAL,
    "streaming queries that restored state + source offsets from the "
    "last committed checkpoint epoch instead of a cold full recompute "
    "(streaming/checkpoint.py recovery path)")

# --- data movement (exec/base.record_movement) -------------------------------
# Bytes an operator moved across the host<->device link, the socket
# shuffle wire and the chips' interconnect.  Free host-side increments
# computed from batch METADATA (capacity/dtype sizes — never a device
# sync), gated MODERATE like the timers they sit beside.
H2D_BYTES = register_metric(
    "h2dBytes", COUNTER, MODERATE,
    "bytes moved host->device over the link (scan adoption, shuffle "
    "read materialization, H2D transitions)")
D2H_BYTES = register_metric(
    "d2hBytes", COUNTER, MODERATE,
    "bytes moved device->host over the link (result materialization, "
    "CPU-fallback bridges)")
WIRE_BYTES = register_metric(
    "wireBytes", COUNTER, MODERATE,
    "bytes this operator put on (or pulled off) the socket shuffle "
    "wire — exchange map writes, shuffle reads, broadcast payloads")
ICI_BYTES_MOVED = register_metric(
    "iciBytesMoved", COUNTER, MODERATE,
    "LOGICAL bytes routed through mesh-exchange collectives — the same "
    "codec-invariant figure the AQE map statistics carry, so the mesh "
    "and socket tiers declare comparable data movement for the same "
    "exchange; the SPMD aggregate, join and sort declare the bytes "
    "their row exchanges' collectives move, from metadata (rows per "
    "peer x row width x n x (n-1): the quota block of the all-to-all, "
    "the whole shard of the all-gather variant)")
SPILL_TIME = register_metric(
    "spillTime", TIMER, MODERATE,
    "wall-clock time spent inside synchronous spill cascades (the "
    "device->host->disk victim migrations an over-budget reservation "
    "forces) — the 'spill' phase of the serving SLO histograms")

# --- adaptive query execution (adaptive/) -----------------------------------
NUM_COALESCED_PARTITIONS = register_metric(
    "numCoalescedPartitions", COUNTER, ESSENTIAL,
    "shuffle partitions merged away by the adaptive coalesce rule")
NUM_SKEW_SPLITS = register_metric(
    "numSkewSplits", COUNTER, ESSENTIAL,
    "extra stream-side slices created by the adaptive skew-join split rule")
NUM_JOIN_STRATEGY_CHANGES = register_metric(
    "numJoinStrategyChanges", COUNTER, ESSENTIAL,
    "joins whose strategy adaptive execution changed from the static plan "
    "(broadcast promotions + demotions)")
MAP_OUTPUT_BYTES = register_metric(
    "mapOutputBytes", COUNTER, ESSENTIAL,
    "observed map-output bytes of materialized shuffle stages")
REPLAN_TIME = register_metric(
    "replanTime", TIMER, MODERATE,
    "time spent applying adaptive re-planning rules between stages "
    "(excludes the map-stage writes themselves)")

# --- data-movement policy decision counters (policy/) -----------------------
# Every policy decision is also journaled under kind 'policy'; these count
# them live so session_observability / /metrics show the engine acting.
NUM_POLICY_VICTIM_PICKS = register_metric(
    "numPolicyVictimPicks", COUNTER, ESSENTIAL,
    "spill victims chosen while next-use scoring was active (every "
    "scored pick, whether or not it changed the baseline order)")
NUM_POLICY_VICTIM_OVERRIDES = register_metric(
    "numPolicyVictimOverrides", COUNTER, ESSENTIAL,
    "spill victims where the next-use score OVERRODE the baseline "
    "(priority, id) choice — the decisions the policy engine actually "
    "changed; zero with scoring active means it never disagreed")
NUM_POLICY_EARLY_RELEASES = register_metric(
    "numPolicyEarlyReleases", COUNTER, ESSENTIAL,
    "shuffle partition buffers freed at their FINAL planned "
    "consumption (single-consumer local reads) — bytes returned to the "
    "pool with no spill write that the baseline would have re-spilled "
    "under pressure")
NUM_PROACTIVE_UNSPILLS = register_metric(
    "numProactiveUnspills", COUNTER, ESSENTIAL,
    "spilled buffers the policy thread re-materialized ahead of their "
    "declared next use (charged to the owning query's ledger scope)")
NUM_PREFETCH_HITS = register_metric(
    "numPrefetchHits", COUNTER, ESSENTIAL,
    "proactively unspilled buffers that were then actually read from "
    "the device tier — the prefetch paid off")
NUM_PREFETCH_WASTED = register_metric(
    "numPrefetchWasted", COUNTER, ESSENTIAL,
    "proactively unspilled buffers evicted or released before any "
    "read — device bytes the policy thread moved for nothing; if this "
    "rivals numPrefetchHits, raise policy.unspill.headroomFraction or "
    "disable the thread")
NUM_BACKPRESSURE_STALLS = register_metric(
    "numBackpressureStalls", COUNTER, ESSENTIAL,
    "flow-control admission stalls (map-side serve staging + reduce-"
    "side fetch admission) where in-flight bytes exceeded the reduce-"
    "rate-driven window — each one is host memory NOT ballooned behind "
    "a slow consumer")
NUM_CODEC_RESELECTIONS = register_metric(
    "numCodecReselections", COUNTER, ESSENTIAL,
    "exchanges whose runtime-observed read throughput proved them "
    "wire-bound and triggered codec re-selection through the shuffle "
    "compression negotiation path")

# --- exception-hygiene counters (metrics/registry.py ENGINE_COUNTERS) -------
# Process-wide counters for swallowed-failure sites that have no operator
# Metrics object in scope; every TPU006 fix pairs a log line with one of
# these so the silence is observable (docs/lint.md).
NUM_NATIVE_TEARDOWN_ERRORS = register_metric(
    "numNativeTeardownErrors", COUNTER, ESSENTIAL,
    "native address-space allocator handles whose destroy failed at "
    "finalization (native.py) — a leak of native tracking state")
NUM_WORKER_STDOUT_NOISE = register_metric(
    "numWorkerStdoutNoise", COUNTER, ESSENTIAL,
    "non-JSON lines a worker printed on stdout before its ready "
    "announcement (library banners are normal; a flood means the worker "
    "is crashing before announcing)")
NUM_HBM_DETECT_FALLBACKS = register_metric(
    "numHbmDetectFallbacks", COUNTER, ESSENTIAL,
    "runtimes on a non-tpu backend whose memory_stats() raised and that "
    "sized the accounted pool from the nominal 16GiB (mem/runtime.py); "
    "on the tpu platform missing stats raise instead")
NUM_SCAN_PRUNE_STAT_ERRORS = register_metric(
    "numScanPruneStatErrors", COUNTER, ESSENTIAL,
    "predicate-pushdown stat computations that raised, keeping the row "
    "group/stripe conservatively (io/scan.py); correctness is unaffected "
    "but pruning silently degrades to a full scan")
NUM_CLEANUP_ERRORS = register_metric(
    "numCleanupErrors", COUNTER, ESSENTIAL,
    "execution-context cleanup callbacks that raised during teardown "
    "(exec/base.py run_cleanups) — each one is a potential buffer/file "
    "handle leak")
NUM_EXPORT_SCRAPE_ERRORS = register_metric(
    "numExportScrapeErrors", COUNTER, ESSENTIAL,
    "cluster observability scrapes that raised and reported zero wire "
    "bytes instead (metrics/export.py) — dashboards silently flatline "
    "when this moves")
NUM_TELEMETRY_TAP_ERRORS = register_metric(
    "numTelemetryTapErrors", COUNTER, ESSENTIAL,
    "flight-recorder journal taps that raised while observing an "
    "emitted record (metrics/journal.py) — the ring may be missing "
    "events a post-mortem bundle would have wanted")
NUM_TELEMETRY_SAMPLE_ERRORS = register_metric(
    "numTelemetrySampleErrors", COUNTER, ESSENTIAL,
    "gauge-sampler source callbacks that raised during a sampling tick "
    "(metrics/ring.py) — that series silently stops advancing")
NUM_TELEMETRY_HTTP_ERRORS = register_metric(
    "numTelemetryHttpErrors", COUNTER, ESSENTIAL,
    "telemetry HTTP endpoint requests that raised and answered 500 "
    "(metrics/http.py) — a scraper sees gaps where samples should be")
NUM_POSTMORTEM_DUMPS = register_metric(
    "numPostmortemDumps", COUNTER, ESSENTIAL,
    "post-mortem diagnostic bundles written (metrics/bundle.py), "
    "automatic or explicit — each one is a first-failure artifact "
    "waiting in telemetry.postmortem.dir")
NUM_POSTMORTEM_SUPPRESSED = register_metric(
    "numPostmortemSuppressed", COUNTER, ESSENTIAL,
    "automatic post-mortem triggers suppressed by the "
    "telemetry.postmortem.minIntervalMs rate limit or a duplicate "
    "in-flight dump — the failure storm a bundle was NOT written for")
NUM_POSTMORTEM_ERRORS = register_metric(
    "numPostmortemErrors", COUNTER, ESSENTIAL,
    "post-mortem bundle sections or whole dumps that raised while being "
    "assembled (metrics/bundle.py) — the bundle (or section) is missing "
    "exactly when it was wanted most")
NUM_POLICY_TICK_ERRORS = register_metric(
    "numPolicyTickErrors", COUNTER, ESSENTIAL,
    "proactive-unspill policy ticks that raised and were swallowed "
    "(policy/engine.py) — the engine stays up but prefetch silently "
    "stops helping while this moves")

# retry-block counters: each `run_retryable(ctx, metrics, <block>)` call
# site emits `<block>Retries` / `<block>Splits` (mem/retry.py with_retry)
RETRY_BLOCKS = ("sort", "aggUpdate", "aggMerge", "joinBuild", "joinProbe",
                "exchangePartition", "exchangeWrite", "exchangeFetch",
                "exchangeCollective", "wholeStage", "wholeStageOp",
                "streamFold", "streamRestore", "retryBlock")
for _b in RETRY_BLOCKS:
    register_metric(f"{_b}Retries", COUNTER, ESSENTIAL,
                    f"same-size OOM retries of the {_b} retryable block")
    register_metric(f"{_b}Splits", COUNTER, ESSENTIAL,
                    f"split-and-retry escalations of the {_b} retryable block")


def retry_metric_names(block: str) -> tuple:
    return (f"{block}Retries", f"{block}Splits")


# --- shuffle transport wire counters (shuffle/net.py count()) ---------------
# Not SQLMetrics — a separate snake_case namespace owned by the transport —
# but registered here so the Prometheus exporter and the cluster aggregation
# share one catalog of everything observable.
TRANSPORT_COUNTERS = {
    "bytes_sent": "payload bytes written to peer sockets",
    "bytes_received": "payload bytes read from peer sockets",
    "metadata_fetched": "shuffle metadata round trips issued",
    "metadata_served": "shuffle metadata round trips answered",
    "net_op_retries": "socket operations retried after a transient error",
    "net_op_failures": "socket operations that exhausted their retries",
    "peer_disconnects": "peer connections dropped mid-stream",
    "accept_errors": "transient server accept() errors survived",
    "rpc_errors": "control-plane RPC failures",
    "shm_fills": "local-partition reads served via shared memory",
    "shm_unavailable": "shared-memory reads that fell back to the stream",
    "peer_publish_failures":
        "set_peers broadcasts a worker failed to acknowledge (a survivor "
        "that never learned a replacement's address)",
    "buffer_gone": "typed buffer-gone frames served for fetches that "
                   "raced a shuffle removal",
    "checksum_mismatches": "fetched buffers whose checksum verification "
                           "failed at this transport's clients",
    "corruption_diagnoses": "writer-side re-hash diagnosis round trips "
                            "served after a reader checksum mismatch",
    "compressed_bytes_sent": "payload bytes sent that rode a negotiated "
                             "compression codec (physical, post-codec)",
    "compressed_bytes_received": "payload bytes received that rode a "
                                 "negotiated compression codec (physical, "
                                 "pre-decompress)",
    "compression_fallbacks": "fetches the peer answered RAW after this "
                             "side requested a codec it could not serve",
    "ici_exchanges": "shuffle exchanges served by the mesh tier (jitted "
                     "ICI collectives; no bytes touched this transport's "
                     "wire for them)",
    "socket_fallbacks": "mesh-eligible exchanges de-lowered to the "
                        "socket tier (collective retry ladder exhausted; "
                        "results identical, movement paid on the wire)",
    # driver-side task-recovery accounting (cluster._run_tasks_with_retry;
    # per-CAUSE so one flaky worker's retries are distinguishable from an
    # unrelated late failure's — the per-task retry-budget satellite)
    "task_retries_dead": "task re-runs caused by a dead worker process "
                         "(replaced, lineage recomputed)",
    "task_retries_timeout": "task re-runs caused by an attempt crossing "
                            "its deadline (worker health-probed, wedged "
                            "workers evicted)",
    "task_retries_fetch_failed": "task re-runs caused by a typed "
                                 "FetchFailed naming a peer whose map "
                                 "output was lost",
    "task_retries_speculation": "speculative task copies launched by the "
                                "straggler detector (also "
                                "numSpeculativeTasks)",
    "task_retries_other": "task re-runs after an error that named no "
                          "dead worker, deadline, or peer (transient rpc "
                          "faults; re-run on the same worker)",
    "worker_shrinks": "worker slots removed by graceful degradation: the "
                      "replacement budget was exhausted (or the spawn "
                      "itself failed) and the cluster re-balanced onto "
                      "the survivors instead of failing the query",
}

# --- gauge-sampler series (metrics/ring.py GaugeSampler) ---------------------
# Sampled at telemetry.sampleIntervalMs into bounded in-memory time series;
# served live by /metrics and replayed as Chrome-trace counter lanes.  Pool
# and transport series reuse the POOL_GAUGES / TRANSPORT_COUNTERS names
# above; these are the sampler-only additions.
TELEMETRY_GAUGES = {
    "in_flight_tasks": "distributed tasks currently executing in this "
                       "process (worker run_map/run_reduce in flight; "
                       "driver: scheduler running count)",
    "spill_bytes": "host + disk spill-store bytes currently tracked "
                   "(host_used + disk_used at the sample instant)",
    "queued_queries": "queries waiting in the serving-tier scheduler "
                      "queue (driver only; 0 without a scheduler)",
    "ring_events": "journal records currently held by this process's "
                   "flight-recorder ring",
    "ring_dropped": "journal records evicted from the flight-recorder "
                    "ring since process start",
    "cluster_device_used": "device-store bytes summed over an in-process "
                           "TpuCluster's executor pools (plugin.py)",
    "cluster_spill_bytes": "host + disk spill bytes summed over an "
                           "in-process TpuCluster's executor pools",
    "policy_tracked_buffers": "device-resident shuffle buffers the "
                              "data-movement policy engine is tracking "
                              "next-use state for",
    "policy_prefetch_pending": "proactively unspilled buffers not yet "
                               "read back (each resolves into a "
                               "prefetch hit or a wasted prefetch)",
    "policy_flow_window_bytes": "current reduce-rate-driven flow-"
                                "control admission window (floor: "
                                "policy.flow.minWindowBytes)",
}

# --- runtime pool gauges (mem/runtime.py pool_stats()) ----------------------
POOL_GAUGES = {
    "pool_limit": "accounted HBM pool budget in bytes",
    "device_used": "bytes currently tracked in the device store",
    "host_used": "bytes currently tracked in the host spill store",
    "disk_used": "bytes currently tracked in the disk spill store",
    "device_peak": "high-water bytes ever tracked in the device store "
                   "(reset-aware: TpuRuntime.reset_peaks() rebases to "
                   "current usage)",
    "host_peak": "high-water bytes ever tracked in the host spill store",
    "disk_peak": "high-water bytes ever tracked in the disk spill store",
}


def catalog_rows():
    """(name, kind, level, doc) rows for docs/monitoring.md generation."""
    rows = [(s.name, s.kind, LEVEL_NAMES[s.level], s.doc)
            for s in sorted(METRICS.values())]
    rows += [(k, COUNTER, "ESSENTIAL", v + " (transport counter)")
             for k, v in sorted(TRANSPORT_COUNTERS.items())]
    rows += [(k, GAUGE, "ESSENTIAL", v + " (runtime pool gauge)")
             for k, v in sorted(POOL_GAUGES.items())]
    rows += [(k, GAUGE, "ESSENTIAL", v + " (gauge-sampler series)")
             for k, v in sorted(TELEMETRY_GAUGES.items())]
    return rows
