"""Typed configuration registry.

Mirrors the reference's conf system (reference: sql-plugin/.../rapids/
RapidsConf.scala:96-220 for the builder machinery, :221-590 for the key list,
:600-689 for doc generation): every entry has a key, a typed default, a doc
string, and an `internal` flag; docs/configs.md is *generated* from this
registry; every operator/expression additionally gets an auto-derived
kill-switch key (see plan/overrides.py).

Key namespace keeps the reference's `spark.rapids.` prefix so users of the
reference find the same knobs, with `tpu` substituted where the reference says
`gpu`.
"""
from __future__ import annotations

import os
import re
from typing import Any, Callable, Dict, List, Optional

_REGISTRY: "Dict[str, ConfEntry]" = {}


def _to_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in ("true", "1", "yes"):
        return True
    if s in ("false", "0", "no"):
        return False
    raise ValueError(f"not a boolean: {v!r}")


_BYTE_SUFFIXES = {
    "b": 1, "k": 1 << 10, "kb": 1 << 10, "m": 1 << 20, "mb": 1 << 20,
    "g": 1 << 30, "gb": 1 << 30, "t": 1 << 40, "tb": 1 << 40,
}


def to_bytes(v) -> int:
    """Parse '2g', '512m', '1024' -> bytes (reference: byte converters in
    TypedConfBuilder, RapidsConf.scala:141-150)."""
    if isinstance(v, (int, float)):
        return int(v)
    m = re.fullmatch(r"\s*(\d+(?:\.\d+)?)\s*([a-zA-Z]*)\s*", str(v))
    if not m:
        raise ValueError(f"not a byte size: {v!r}")
    num, suf = float(m.group(1)), m.group(2).lower()
    if suf == "":
        return int(num)
    if suf not in _BYTE_SUFFIXES:
        raise ValueError(f"unknown byte suffix {suf!r} in {v!r}")
    return int(num * _BYTE_SUFFIXES[suf])


class ConfEntry:
    def __init__(self, key: str, default: Any, doc: str,
                 converter: Callable[[Any], Any],
                 internal: bool = False):
        self.key = key
        self.default = default
        self.doc = doc
        self.converter = converter
        self.internal = internal
        if key in _REGISTRY:
            raise ValueError(f"duplicate conf key {key}")
        _REGISTRY[key] = self

    def get(self, conf: "TpuConf"):
        raw = conf._settings.get(self.key)
        if raw is None:
            return self.default
        return self.converter(raw)


def _conf(key, default, doc, converter, internal=False) -> ConfEntry:
    return ConfEntry(key, default, doc, converter, internal)


# --- core enables -----------------------------------------------------------
SQL_ENABLED = _conf("spark.rapids.sql.enabled", True,
                    "Enable (true) or disable (false) TPU acceleration of SQL "
                    "plans.", _to_bool)
TEST_CONF = _conf("spark.rapids.sql.test.enabled", False,
                  "Intended for internal testing only: fail if an operation "
                  "falls back to CPU instead of running on the TPU.", _to_bool,
                  internal=True)
TEST_ALLOWED_NONTPU = _conf(
    "spark.rapids.sql.test.allowedNonTpu", "",
    "Comma separated exec class names allowed to stay on CPU in test mode.",
    str, internal=True)
INCOMPATIBLE_OPS = _conf(
    "spark.rapids.sql.incompatibleOps.enabled", False,
    "Enable operations that produce results that differ from Spark in corner "
    "cases (e.g. float aggregation ordering).", _to_bool)
EXPLAIN = _conf(
    "spark.rapids.sql.explain", "NONE",
    "Explain why parts of a query were or were not placed on the TPU. "
    "NONE|ALL|NOT_ON_TPU; METRICS additionally prints the executed plan "
    "tree with each node's accumulated metrics after every query "
    "(EXPLAIN-with-metrics, docs/monitoring.md).", str)
HAS_NANS = _conf(
    "spark.rapids.sql.hasNans", True,
    "Assume floating point data may contain NaNs (affects eligibility of some "
    "ops, matching the reference's hasNans gate).", _to_bool)
VARIABLE_FLOAT_AGG = _conf(
    "spark.rapids.sql.variableFloatAgg.enabled", False,
    "Allow float/double aggregations whose result may differ in last-bit "
    "rounding from CPU due to reduction order.", _to_bool)
ENABLE_CAST_STRING_TO_FLOAT = _conf(
    "spark.rapids.sql.castStringToFloat.enabled", False,
    "Enable string->float casts on device; off by default because corner-case "
    "formats differ from the CPU.", _to_bool)
ENABLE_CAST_FLOAT_TO_STRING = _conf(
    "spark.rapids.sql.castFloatToString.enabled", False,
    "Enable float->string casts on device; formatting differs in corner cases.",
    _to_bool)
ENABLE_CAST_STRING_TO_TIMESTAMP = _conf(
    "spark.rapids.sql.castStringToTimestamp.enabled", False,
    "Enable string->timestamp casts on device.", _to_bool)
IMPROVED_FLOAT_OPS = _conf(
    "spark.rapids.sql.improvedFloatOps.enabled", False,
    "Use device float ops that are faster but not bit-identical to the JVM.",
    _to_bool)

# --- batching ---------------------------------------------------------------
BATCH_SIZE_BYTES = _conf(
    "spark.rapids.sql.batchSizeBytes", 2 << 30,
    "Target size in bytes for TPU columnar batches; operators coalesce "
    "smaller batches up to this goal (reference default 2GiB).", to_bytes)
MAX_READER_BATCH_SIZE_ROWS = _conf(
    "spark.rapids.sql.reader.batchSizeRows", 2 ** 31 - 1,
    "Soft cap on rows per batch produced by file readers.", int)
MAX_READER_BATCH_SIZE_BYTES = _conf(
    "spark.rapids.sql.reader.batchSizeBytes", 2 << 30,
    "Soft cap on bytes per batch produced by file readers.", to_bytes)
MIN_BUCKET_ROWS = _conf(
    "spark.rapids.sql.tpu.minBucketRows", 1024,
    "Smallest row-capacity bucket; batch capacities are rounded up to "
    "power-of-two buckets so XLA recompiles are bounded (TPU-specific: XLA "
    "traces once per static shape).", int)

# --- memory -----------------------------------------------------------------
TPU_ALLOC_FRACTION = _conf(
    "spark.rapids.memory.tpu.allocFraction", 0.9,
    "Fraction of usable HBM to reserve for the columnar batch pool.", float)
TPU_POOL_SIZE = _conf(
    "spark.rapids.memory.tpu.poolSizeBytes", 0,
    "Absolute accounted HBM pool budget in bytes; overrides allocFraction "
    "when > 0.  The knob memory-budget sweeps (bench.py pressure stage) "
    "and the serving tier's per-query budgets are expressed in — an exact "
    "byte budget is reproducible across hosts where a fraction of "
    "detected HBM is not.", to_bytes)
HOST_SPILL_STORAGE_SIZE = _conf(
    "spark.rapids.memory.host.spillStorageSize", 1 << 30,
    "Bytes of host memory to use for spilled device buffers before spilling "
    "to disk.", to_bytes)
TPU_OOM_SPILL_ENABLED = _conf(
    "spark.rapids.memory.tpu.oomSpill.enabled", True,
    "Synchronously spill device buffers when an HBM allocation fails.",
    _to_bool)
TPU_DEBUG = _conf(
    "spark.rapids.memory.tpu.debug", "NONE",
    "Log device allocations/frees: NONE|STDOUT|STDERR.", str)
CONCURRENT_TPU_TASKS = _conf(
    "spark.rapids.sql.concurrentTpuTasks", 1,
    "Number of tasks that may use the TPU concurrently (device semaphore).",
    int)
PINNED_POOL_SIZE = _conf(
    "spark.rapids.memory.pinnedPool.size", 0,
    "Size of the pinned host staging pool used for H2D/D2H transfer.",
    to_bytes)
SPILL_CHECKSUM_ENABLED = _conf(
    "spark.rapids.memory.spill.checksum.enabled", True,
    "Checksum device buffers as they spill to the host tier and verify "
    "on every subsequent movement (host->disk write, disk read, "
    "host/disk->device unspill), so a flipped bit in spilled bytes "
    "surfaces as a typed CorruptBuffer instead of silently wrong query "
    "results.  Uses spark.rapids.shuffle.checksum.algorithm.", _to_bool)
OOM_RETRY_MAX = _conf(
    "spark.rapids.memory.tpu.retry.maxRetries", 2,
    "Same-size retries of an operator allocation attempt after an OOM "
    "(each retry runs behind the synchronous spill cascade) before the "
    "input is split (reference: withRetry over RmmSpark retry OOMs).", int)
OOM_RETRY_SPLIT_DEPTH = _conf(
    "spark.rapids.memory.tpu.retry.maxSplitDepth", 4,
    "Maximum halving depth of split-and-retry: an input batch may be "
    "split into at most 2^depth pieces before the block gives up "
    "(RetryExhausted -> CPU fallback or query failure).", int)
OOM_RETRY_CHECKPOINT = _conf(
    "spark.rapids.memory.tpu.retry.checkpointInputs.enabled", True,
    "Register retryable-block input batches as spillable buffers so the "
    "OOM spill cascade can evict them between attempts (they are pinned "
    "only while an attempt runs).", _to_bool)
OOM_CPU_FALLBACK = _conf(
    "spark.rapids.sql.tpu.cpuFallbackOnOom.enabled", True,
    "When a device operator exhausts its OOM retries and split depth, "
    "re-execute it through its CPU implementation instead of failing the "
    "query; the downgrade is recorded in the operator's numCpuFallbacks "
    "metric.", _to_bool)
MEMORY_SCAN_CACHE_ENABLED = _conf(
    "spark.rapids.sql.tpu.memoryScanCache.enabled", True,
    "Keep device batches decoded from immutable in-memory tables "
    "HBM-resident across queries so repeated scans skip the host->device "
    "transfer (TPU-native storage-layer cache; Spark analogue df.cache()).",
    _to_bool)
MEMORY_SCAN_CACHE_SIZE = _conf(
    "spark.rapids.sql.tpu.memoryScanCache.maxSize", 0,
    "LRU byte bound on HBM held by the in-memory scan cache.  0 (the "
    "default) means half of the session's accounted HBM pool "
    "(spark.rapids.memory.tpu.poolSizeBytes, else allocFraction of the "
    "device's own memory limit): storage's share of the unified region, "
    "as Spark's spark.memory.storageFraction default of 0.5.  About 7 GiB "
    "on a 16 GB TPU v5e.  A byte value > 0 is the bound as given.",
    to_bytes)
WHOLE_STAGE_ENABLED = _conf(
    "spark.rapids.sql.tpu.wholeStage.enabled", True,
    "Compile scan->rowLocal->aggregate stages over equal-capacity batches "
    "into ONE device program (batches stacked on a leading dim, per-batch "
    "work vmapped, partials merged in-program) — the TPU analogue of "
    "whole-stage codegen; one dispatch instead of O(batches), which is "
    "what high host-link latency punishes.", _to_bool)
SCAN_PREFETCH_DEPTH = _conf(
    "spark.rapids.sql.tpu.scan.prefetchDepth", 1,
    "Chunks of device file-scan decode kept ready ahead of the consumer "
    "by a background thread (the reference's MULTITHREADED reader mode): "
    "chunk N+1's host control plane overlaps chunk N's H2D transfer. "
    "0 disables.", int)
COMPILATION_CACHE_DIR = _conf(
    "spark.rapids.sql.tpu.compilationCache.dir", "",
    "Persistent XLA compilation cache directory shared across processes; "
    "a fresh session replays compiled programs from disk instead of "
    "paying seconds to minutes per query shape (the reference has zero "
    "query-time compile cost; this is the TPU equivalent).  Empty (the "
    "default) means .jax_cache/ at the root of the checkout.  Ignored "
    "when JAX_COMPILATION_CACHE_DIR is set in the environment: jax's own "
    "handling of that variable is left alone.  Off when the backend in "
    "use is the CPU.", str)
FUSION_ENABLED = _conf(
    "spark.rapids.sql.tpu.fusion.enabled", True,
    "Whole-stage fusion kill switch: after planning, maximal chains of "
    "row-local device operators (project/filter/expand over scan-decode "
    "output) compile into ONE jitted XLA stage per batch shape "
    "(TpuWholeStageExec), the hash-partition bucketing of a shuffle "
    "exchange fuses into its child stage's program, and grouped "
    "aggregation absorbs the chain into its whole-stage program.  A "
    "stage materializes exactly one ColumnarBatch at its fusion boundary "
    "(exchange, join build, sort, full aggregation) instead of one per "
    "operator; OOM retry runs at stage granularity (split-retry the "
    "stage input, then operator-at-a-time, then per-operator CPU "
    "fallback).  false disables the ENTIRE compiled-stage family — "
    "per-operator dispatch with the legacy FusedPipelineExec chain "
    "fusion only, aggregate whole-stage absorption off too (toggle that "
    "alone via wholeStage.enabled while fusion stays on).", _to_bool)
DONATION_ENABLED = _conf(
    "spark.rapids.sql.tpu.donation.enabled", True,
    "Buffer donation through compiled stage programs: when the fusion "
    "pass proves a stage is the LAST consumer of its input batches "
    "(source is scan decode / host->device adoption / an upstream whole "
    "stage) and the batch gained no second owner at runtime (spillable "
    "registration, scan cache, retry checkpoint — mem/donation.py pins "
    "those), the stage executable compiles with donate_argnums on the "
    "batch-column leaves so XLA reuses input HBM for the outputs instead "
    "of allocating a fresh copy per column per batch.  Results are "
    "byte-identical either way; false restores the copying behavior "
    "(numDonatedBuffers counts what warm runs saved).", _to_bool)
FUSION_MAX_OPS = _conf(
    "spark.rapids.sql.tpu.fusion.maxOpsPerStage", 16,
    "Upper bound on row-local operators fused into one whole-stage "
    "program; longer chains split into consecutive stages (bounds the "
    "size/compile time of any single XLA program).", int)
AGG_MERGE_FAN_IN = _conf(
    "spark.rapids.sql.tpu.agg.mergeFanIn", 8,
    "Number of per-batch partial aggregate states buffered before one "
    "K-way concat+merge; larger values amortize merge-kernel dispatches "
    "and host syncs across more input batches.", int)
AGG_BUCKET_GROUPS = _conf(
    "spark.rapids.sql.tpu.agg.bucketGroups", True,
    "Low-cardinality grouped-aggregate fast path: a batch of at most "
    "1,024 groups is reduced in dense passes of 32 groups, chosen by the "
    "rows' own 31-bit hash ids, in place of the per-batch sort when "
    "every id stands for one distinct key (checked exactly per batch; "
    "dirty batches fall back to the sort path).  Applies to "
    "sum/count/avg and non-string min/max without distinct.", _to_bool)

CLUSTER_EXECUTORS = _conf(
    "spark.rapids.sql.tpu.cluster.executors", 1,
    "Host-mode executor count: each executor owns a runtime + shuffle env "
    "on a shared transport wire; shuffle map tasks write to their "
    "executor's catalog and reduce tasks fetch remote blocks through the "
    "client/server path (plugin.py TpuCluster; reference: one plugin "
    "executor per Spark executor).", int)

# --- multi-chip / shuffle planning ------------------------------------------
MESH_DEVICES = _conf(
    "spark.rapids.sql.tpu.mesh.devices", 0,
    "Devices in the SPMD execution mesh.  >1 routes aggregate/join/sort "
    "subtrees through the distributed all-to-all operators "
    "(exec/distributed.py); 0/1 keeps single-chip execution.  Must be a "
    "power of two and <= the device count (fewer devices is an error, "
    "never a quiet single-chip run).", int)
MESH_COORDINATOR = _conf(
    "spark.rapids.sql.tpu.mesh.coordinator", "",
    "host:port of the jax.distributed coordinator for MULTI-HOST meshes "
    "(empty = single host).  When set, session startup joins the "
    "coordination service so jax.devices() enumerates every host's chips "
    "and the SPMD mesh spans the pod; collectives ride ICI within a slice "
    "and DCN across slices.  Process count/id come from the companion "
    "confs or JAX_NUM_PROCESSES/JAX_PROCESS_ID.", str)
MESH_NUM_PROCESSES = _conf(
    "spark.rapids.sql.tpu.mesh.numProcesses", 0,
    "Total processes in the multi-host mesh (0 = let jax infer from the "
    "TPU runtime, which works on Cloud TPU pods).", int)
MESH_PROCESS_ID = _conf(
    "spark.rapids.sql.tpu.mesh.processId", 0,
    "This process's id in [0, numProcesses) for multi-host bring-up.", int)
MESH_USE_ALLGATHER = _conf(
    "spark.rapids.sql.tpu.mesh.useAllGather", False,
    "Use the sel-mask all-gather exchange instead of the compact quota "
    "all-to-all in distributed operators (zero overflow risk, O(n) cost; "
    "debugging/safety knob).", _to_bool)
ICI_SHUFFLE_ENABLED = _conf(
    "spark.rapids.sql.tpu.shuffle.ici.enabled", True,
    "Lower generic shuffle exchanges (TpuShuffleExchangeExec) into jitted "
    "ICI collectives when the exchange's producer and consumer partitions "
    "are co-resident on one device mesh (mesh.devices > 1, single "
    "process, hash/round_robin/single partitioning): the fused chain, "
    "partition-id compute and the all-to-all compile into ONE program and "
    "the data never leaves HBM.  Off (or off-mesh: a cluster, a range "
    "exchange, too few devices) the exchange takes the host socket tier "
    "byte-identically to the pre-mesh behavior; RetryExhausted inside the "
    "collective also de-lowers to the socket tier (counted in the "
    "transport's socket_fallbacks).", _to_bool)
MESH_INPUT_CHUNK_ROWS = _conf(
    "spark.rapids.sql.tpu.mesh.inputChunkRows", 1 << 20,
    "Row budget per SPMD input chunk.  Distributed aggregate/join STREAM "
    "their input through the mesh in chunks of at most this many rows "
    "(partial-agg then device-resident state merge; per-chunk probe "
    "against a resident build side), so an input larger than HBM never "
    "materializes as one host-side concat.", int)
SHUFFLE_PARTITIONS = _conf(
    "spark.rapids.sql.tpu.shuffle.partitions", 8,
    "Partition count for planner-inserted shuffle exchanges around "
    "shuffled hash joins (spark.sql.shuffle.partitions analogue; the "
    "single-build-batch bound then holds per partition, not per input).",
    int)
PARTITIONED_JOIN_ENABLED = _conf(
    "spark.rapids.sql.tpu.join.partitioned.enabled", True,
    "Insert hash-partition exchanges around non-broadcast equi-joins so "
    "the build side is bounded per partition (EnsureRequirements "
    "analogue; reference GpuShuffledHashJoinExec).", _to_bool)
PARTITIONED_JOIN_THRESHOLD = _conf(
    "spark.rapids.sql.tpu.join.partitioned.threshold", 64 << 20,
    "Estimated build-side bytes above which a non-broadcast join is "
    "planned with partition exchanges; below it the whole build side is "
    "one batch.  Unknown sizes partition.", to_bytes)

# --- formats ----------------------------------------------------------------
CSV_ENABLED = _conf("spark.rapids.sql.format.csv.enabled", True,
                    "Enable CSV read acceleration.", _to_bool)
CSV_READ_ENABLED = _conf("spark.rapids.sql.format.csv.read.enabled", True,
                         "Enable CSV reads.", _to_bool)
PARQUET_ENABLED = _conf("spark.rapids.sql.format.parquet.enabled", True,
                        "Enable Parquet acceleration.", _to_bool)
PARQUET_READ_ENABLED = _conf("spark.rapids.sql.format.parquet.read.enabled",
                             True, "Enable Parquet reads.", _to_bool)
PARQUET_WRITE_ENABLED = _conf("spark.rapids.sql.format.parquet.write.enabled",
                              True, "Enable Parquet writes.", _to_bool)
ORC_ENABLED = _conf("spark.rapids.sql.format.orc.enabled", True,
                    "Enable ORC acceleration.", _to_bool)
ORC_READ_ENABLED = _conf("spark.rapids.sql.format.orc.read.enabled", True,
                         "Enable ORC reads.", _to_bool)
ORC_WRITE_ENABLED = _conf("spark.rapids.sql.format.orc.write.enabled", True,
                          "Enable ORC writes.", _to_bool)
PARQUET_DEVICE_DECODE = _conf(
    "spark.rapids.sql.format.parquet.deviceDecode.enabled", True,
    "Decode parquet PLAIN/dictionary pages of flat numeric/bool columns "
    "on the device (host keeps only page headers, run structure, and "
    "definition levels); columns outside scope fall back to the host "
    "arrow reader per column.", _to_bool)
ORC_DEVICE_ENCODE = _conf(
    "spark.rapids.sql.format.orc.deviceEncode.enabled", True,
    "Encode ORC writes on the device: null compaction, contiguous string "
    "byte packing + lengths, and min/max/count statistics run as device "
    "kernels and the compacted stream payload is the only D2H transfer; "
    "the host writes RLE runs and the protobuf stripe footer / metadata "
    "/ footer / postscript (io/orc_device_write.py).  Timestamp columns "
    "and partitioned writes fall back to the host arrow encoder.",
    _to_bool)
PARQUET_DEVICE_ENCODE = _conf(
    "spark.rapids.sql.format.parquet.deviceEncode.enabled", True,
    "Encode parquet writes on the device: null compaction, string "
    "[len][bytes] stream packing, and column statistics run as device "
    "kernels and the encoded page payload is the only D2H transfer; the "
    "host writes definition-level runs, page headers, and the thrift "
    "footer.  Partitioned writes fall back to the host arrow encoder.",
    _to_bool)
ORC_DEVICE_DECODE = _conf(
    "spark.rapids.sql.format.orc.deviceDecode.enabled", True,
    "Decode the core ORC primitives on the device: floats/doubles (IEEE "
    "payload), tinyint/ints/dates (byte-RLE / RLEv2 DIRECT "
    "bit-extraction), strings "
    "(DIRECT_V2 and DICTIONARY_V2 blob gathers), booleans, and "
    "timestamps.  The host keeps the protobuf control plane, zlib "
    "inflation, byte-RLE bitmaps, and RLEv2 run headers.  "
    "Char/varchar/decimal/binary, non-GMT writer timezones, and nested "
    "types fall back to the host stripe reader column-granularly.",
    _to_bool)
CSV_DEVICE_DECODE = _conf(
    "spark.rapids.sql.format.csv.deviceDecode.enabled", True,
    "Tokenize and parse CSV on the device: the host computes only the "
    "delimiter index structure (one vectorized scan), the device gathers "
    "per-column byte matrices from the raw file buffer and runs the "
    "string->value parse kernels; quoted files tokenize through the "
    "native C scanner.  CR line endings and jagged rows fall back to "
    "the host arrow reader.", _to_bool)
PARQUET_DEBUG_DUMP_PREFIX = _conf(
    "spark.rapids.sql.parquet.debug.dumpPrefix", "",
    "If set, dump the clipped host parquet buffer to this path prefix for "
    "offline repro.", str)

# --- shuffle ----------------------------------------------------------------
SHUFFLE_TRANSPORT_CLASS = _conf(
    "spark.rapids.shuffle.transport.class",
    "spark_rapids_tpu.shuffle.ici.IciShuffleTransport",
    "Implementation of the device shuffle transport "
    "(ICI all-to-all on-slice; loopback transport for tests).", str)
SHUFFLE_MAX_RECV_INFLIGHT = _conf(
    "spark.rapids.shuffle.maxReceiveInflightBytes", 1 << 30,
    "Cap on bytes of shuffle data in flight to a receiving task.", to_bytes)
SHUFFLE_ASYNC_FETCH = _conf(
    "spark.rapids.shuffle.asyncFetch.enabled", True,
    "Pipeline the shuffle read: a producer thread fetches partition k+1 "
    "while partition k is being consumed, bounded by "
    "maxReceiveInflightBytes of un-consumed batches.", _to_bool)
SHUFFLE_DEVICE_RESIDENT = _conf(
    "spark.rapids.shuffle.deviceResident.enabled", True,
    "Keep shuffle partitions resident in HBM (spillable) instead of "
    "serializing to host between stages.", _to_bool)
SHUFFLE_RETRY_ATTEMPTS = _conf(
    "spark.rapids.shuffle.retry.maxAttempts", 4,
    "Attempts per shuffle socket operation (connect, metadata, fetch) "
    "before the error propagates; attempts after the first back off "
    "exponentially with jitter.", int)
SHUFFLE_RETRY_BACKOFF_BASE = _conf(
    "spark.rapids.shuffle.retry.backoffBaseMs", 50,
    "Base backoff in milliseconds between shuffle retries; attempt k "
    "waits ~base*2^k (jittered, capped by backoffCapMs).", int)
SHUFFLE_RETRY_BACKOFF_CAP = _conf(
    "spark.rapids.shuffle.retry.backoffCapMs", 2000,
    "Upper bound in milliseconds on the shuffle retry backoff.", int)
SHUFFLE_CONNECT_TIMEOUT = _conf(
    "spark.rapids.shuffle.connectTimeoutMs", 30000,
    "Per-attempt TCP connect timeout for shuffle clients, in "
    "milliseconds.", int)
SHUFFLE_IO_TIMEOUT = _conf(
    "spark.rapids.shuffle.ioTimeoutMs", 60000,
    "Per-socket-operation I/O deadline for shuffle DATA-plane requests "
    "(metadata, layout, fetch), in milliseconds; a dead peer surfaces as "
    "a timeout within this bound instead of hanging.  0 disables.  "
    "Control-plane RPCs (task dispatch) are exempt: they legitimately "
    "block on first-query compilation at the peer.", int)
SHUFFLE_TXN_TIMEOUT = _conf(
    "spark.rapids.shuffle.transactionTimeoutMs", 600000,
    "Overall deadline for one shuffle fetch transaction (layout + every "
    "data frame + END) in milliseconds; past it the transaction is "
    "CANCELLED and the error propagates without further retries.  "
    "0 disables.", int)
SHUFFLE_CHECKSUM_ENABLED = _conf(
    "spark.rapids.shuffle.checksum.enabled", True,
    "Checksum every shuffle buffer leaf at its first device->host "
    "materialization and verify before fetched bytes become a columnar "
    "batch (streamed, shared-memory and loopback fetch paths).  On "
    "mismatch the reader refetches up to maxRefetchAttempts and runs a "
    "writer-side diagnosis to classify the corruption site "
    "(SPARK-35275/36206 analogue; docs/tuning-guide.md, Data integrity).",
    _to_bool)
SHUFFLE_CHECKSUM_ALGO = _conf(
    "spark.rapids.shuffle.checksum.algorithm", "crc32c",
    "Checksum algorithm for shuffle and spill integrity: crc32c "
    "(hardware CRC32C when the google_crc32c C library is importable, "
    "~10 GB/s; falls back to xxhash then zlib crc32), xxhash (xxh3_64), "
    "crc32, adler32, or none.", str)
SHUFFLE_CHECKSUM_VERIFY_LOCAL = _conf(
    "spark.rapids.shuffle.checksum.verifyOnLocalRead", False,
    "Also verify checksums when a reduce task reads blocks from its OWN "
    "executor's catalog (host-serialized baseline buffers and "
    "host/disk-tier spilled buffers).  Off by default: local reads never "
    "cross a wire, so this only guards against host-memory rot at extra "
    "read cost.", _to_bool)
SHUFFLE_COMPRESSION_CODEC = _conf(
    "spark.rapids.shuffle.compression.codec", "none",
    "Codec for shuffle buffers crossing the wire or served from spill "
    "tiers: lz4, zstd, snappy, or none (reference: "
    "spark.rapids.shuffle.compression.codec / TableCompressionCodec).  "
    "Leaves are compressed into a chunked framed format so chunks "
    "(de)compress in parallel on a side thread pool overlapped with "
    "socket send/recv; incompressible chunks are stored raw.  The codec "
    "is negotiated per fetch: a peer that cannot encode the requested "
    "codec answers raw (counted in numCompressionFallbacks).  Checksums "
    "cover the compressed frames, so corrupt bytes are detected before "
    "they reach a decompressor.  `none` keeps today's raw wire path.",
    str)
SHUFFLE_COMPRESSION_CHUNK_SIZE = _conf(
    "spark.rapids.shuffle.compression.chunkSizeBytes", 1 << 20,
    "Chunk size of the framed compression container (shuffle AND spill "
    "tiers).  Smaller chunks parallelize better across the codec thread "
    "pool and bound the raw-escape granularity; larger chunks compress "
    "slightly better.", to_bytes)
SHUFFLE_COMPRESSION_MIN_SIZE = _conf(
    "spark.rapids.shuffle.compression.minSizeBytes", 1 << 10,
    "Leaves smaller than this skip the codec entirely (framed with raw "
    "chunks): below it the per-call codec overhead outweighs any wire/"
    "disk savings.", to_bytes)
SPILL_COMPRESSION_CODEC = _conf(
    "spark.rapids.memory.spill.compression.codec", "none",
    "Codec for host->disk spill files: lz4, zstd, snappy, or none.  "
    "Conf'd independently of the shuffle wire codec; shares "
    "spark.rapids.shuffle.compression.{chunkSizeBytes,minSizeBytes}.  "
    "Spill-time checksums are recorded over BOTH forms: the compressed "
    "disk image is verified before decompression at disk-read/unspill, "
    "and the decompressed leaves are verified against the original "
    "spill digests after.", str)
SHUFFLE_BOUNCE_POOL_SIZE = _conf(
    "spark.rapids.shuffle.bounce.poolSizeBytes", 8 << 20,
    "Size of the pre-allocated host bounce-buffer staging pool every "
    "shuffle transport sub-allocates transfer slices from "
    "(BounceBufferManager analogue).  "
    "spark.rapids.memory.pinnedPool.size, when set, overrides this.",
    to_bytes)
SHUFFLE_BOUNCE_CHUNK_SIZE = _conf(
    "spark.rapids.shuffle.bounce.chunkSizeBytes", 1 << 20,
    "Size of one bounce-buffer transfer slice: shuffle data frames "
    "cross the wire in chunks of at most this many bytes.", to_bytes)
SHUFFLE_MAX_REFETCH = _conf(
    "spark.rapids.shuffle.maxRefetchAttempts", 2,
    "Refetch attempts for a shuffle buffer whose checksum verification "
    "failed at the reader (transient wire/reader corruption).  Exhausting "
    "them — or a writer-side diagnosis (the peer's stored data no longer "
    "matches its recorded checksum) — escalates to FetchFailed, marking "
    "the map output lost so the map fragment is recomputed.", int)

# --- joins ------------------------------------------------------------------
def _to_bytes_or_disabled(v) -> int:
    """Byte size, or any negative value meaning 'disabled' (Spark allows
    autoBroadcastJoinThreshold=-1; other byte confs stay strictly
    non-negative via to_bytes)."""
    try:
        n = int(str(v).strip())
        if n < 0:
            return n
    except ValueError:
        pass  # tpulint: disable=TPU006 parse fallthrough: not a bare int, try the byte-suffix grammar next
    return to_bytes(v)


AUTO_BROADCAST_JOIN_THRESHOLD = _conf(
    "spark.sql.autoBroadcastJoinThreshold", 10 << 20,
    "Maximum estimated size in bytes of a join build side that will be "
    "broadcast to every consumer instead of shuffled (Spark's conf key; "
    "-1 disables broadcast joins).", _to_bytes_or_disabled)

# --- adaptive query execution -----------------------------------------------
ADAPTIVE_ENABLED = _conf(
    "spark.rapids.sql.tpu.adaptive.enabled", True,
    "Re-plan queries at shuffle-stage boundaries from OBSERVED map-output "
    "sizes (Spark 3 AQE analogue; reference: GpuShuffleExchangeExec + "
    "GpuCustomShuffleReaderExec).  Map stages are materialized first, then "
    "the reduce side is instantiated with coalesced small partitions, "
    "split skewed partitions, and possibly a different join strategy "
    "(adaptive/).", _to_bool)
ADAPTIVE_ADVISORY_PARTITION_SIZE = _conf(
    "spark.rapids.sql.tpu.adaptive.advisoryPartitionSizeBytes", 64 << 20,
    "Target size of a shuffle partition after adaptive re-planning: "
    "contiguous partitions smaller than this are merged by the coalesce "
    "rule, and skewed partitions are split into slices of roughly this "
    "size (spark.sql.adaptive.advisoryPartitionSizeInBytes analogue).",
    to_bytes)
ADAPTIVE_COALESCE_ENABLED = _conf(
    "spark.rapids.sql.tpu.adaptive.coalescePartitions.enabled", True,
    "Enable the AQE rule that merges contiguous small reduce partitions "
    "up to advisoryPartitionSizeBytes (served by "
    "TpuCoalescedShuffleReaderExec).", _to_bool)
ADAPTIVE_SKEW_ENABLED = _conf(
    "spark.rapids.sql.tpu.adaptive.skewJoin.enabled", True,
    "Enable the AQE skew-join rule: a stream-side partition larger than "
    "skewedPartitionFactor x the median partition size is split into "
    "map-range slices, each joined against a replicated copy of the "
    "build-side partition.", _to_bool)
ADAPTIVE_SKEW_FACTOR = _conf(
    "spark.rapids.sql.tpu.adaptive.skewJoin.skewedPartitionFactor", 5.0,
    "A partition is skew-split when its observed bytes exceed this factor "
    "times the median non-empty partition size (and the size floor "
    "skewedPartitionThresholdInBytes).", float)
ADAPTIVE_SKEW_THRESHOLD = _conf(
    "spark.rapids.sql.tpu.adaptive.skewJoin.skewedPartitionThresholdInBytes",
    256 << 20,
    "Size floor below which a partition is never considered skewed, "
    "whatever the factor test says.", to_bytes)
ADAPTIVE_JOIN_STRATEGY_ENABLED = _conf(
    "spark.rapids.sql.tpu.adaptive.joinStrategy.enabled", True,
    "Enable AQE join-strategy switching: a partitioned join whose "
    "observed build side fits under spark.sql.autoBroadcastJoinThreshold "
    "is promoted to a single-build (broadcast-style) join, and a planned "
    "broadcast whose observed build side exceeds the threshold is demoted "
    "to a partitioned join.", _to_bool)

# --- fault injection (test-only) --------------------------------------------
TEST_INJECT_OOM = _conf(
    "spark.rapids.tpu.test.injectOom", "",
    "Deterministic OOM injection spec over the process-wide reserve() "
    "counter: '3' fails reserve #3 once, '3x2' fails #3 and #4, "
    "'split@5' raises SplitAndRetryOOM at #5, 'p=0.05' fails with that "
    "probability (seeded by injectSeed).  Testing only.", str,
    internal=True)
TEST_INJECT_NET = _conf(
    "spark.rapids.tpu.test.injectNetFault", "",
    "Deterministic network-fault injection spec over the client-side "
    "shuffle socket-op counter (same grammar as injectOom, minus "
    "split@).  An @-prefixed item selects a per-SITE ordinal instead "
    "('rpc:run_reduce@1' fails the 1st run_reduce control rpc; sites "
    "are the on_net_op labels: metadata, layout, fetch, fetch_shm, "
    "done, diag, rpc:<method>) — the cluster-rpc fault sweep's "
    "addressing mode.  Testing only.", str, internal=True)
TEST_INJECT_CORRUPTION = _conf(
    "spark.rapids.tpu.test.injectCorruption", "",
    "Deterministic single-bit corruption injection over the transfer/"
    "spill paths.  Items are site-scoped ordinals: 'wire@3' flips a bit "
    "in the 3rd chunk staged for a socket send, 'shm@1' in the 1st "
    "shared-memory leaf fill, 'loopback@2' in the 2nd loopback bounce "
    "chunk, 'spill@1' in the 1st device->host spilled leaf, 'disk@1' in "
    "the 1st host->disk image, 'writer@1x9' in the writer's served "
    "leaves (persistent: window of 9).  A bare ordinal ('5') counts "
    "across all sites; 'p=0.01' corrupts probabilistically (seeded by "
    "injectSeed).  Testing only.", str, internal=True)
TEST_INJECT_DELAY = _conf(
    "spark.rapids.tpu.test.injectDelay", "",
    "Deterministic slowdown injection for straggler/watchdog testing.  "
    "Comma-separated items 'site:ms' or 'scope/site:ms': the injector "
    "sleeps that many milliseconds at every matching delay point "
    "(worker task sites are 'map' and 'reduce').  A scope prefix "
    "restricts the item to the process whose injector scope matches "
    "(executor workers set their executor id as the scope), so "
    "'exec-1/reduce:1500' slows ONLY exec-1's reduce tasks.  "
    "Testing only.", str, internal=True)
TEST_INJECT_CRASH = _conf(
    "spark.rapids.tpu.test.injectCrash", "",
    "Deterministic worker-crash injection for chaos testing: the worker "
    "process calls os._exit mid-task at the selected crash point.  Items "
    "are site-scoped ordinals over the per-process crash-point counter "
    "('map@2' = this process's 2nd map task, 'reduce@1'), bare ordinals "
    "count across all sites, 'p=0.02' crashes probabilistically (seeded "
    "by injectSeed), and a 'scope/' prefix restricts the item to the "
    "process whose injector scope matches ('exec-1/map@1' kills only "
    "exec-1, on its 1st map task) — the same scoping injectDelay uses.  "
    "Testing only.", str, internal=True)
TEST_INJECT_SEED = _conf(
    "spark.rapids.tpu.test.injectSeed", 0,
    "Seed for the probabilistic fault-injection mode.", int,
    internal=True)

# --- observability -----------------------------------------------------------
def _to_metrics_level(v) -> str:
    s = str(v).strip().upper()
    if s not in ("ESSENTIAL", "MODERATE", "DEBUG"):
        raise ValueError(
            f"not a metrics level: {v!r} (ESSENTIAL|MODERATE|DEBUG)")
    return s


METRICS_LEVEL = _conf(
    "spark.rapids.sql.tpu.metrics.level", "MODERATE",
    "How many operator metrics to record (reference: "
    "spark.rapids.sql.metrics.level).  ESSENTIAL keeps only free host-side "
    "counters; MODERATE (default) adds timers and lazily folded device row "
    "counts; DEBUG adds per-batch device-sync metrics (exact row counts, "
    "peakDevMemory) with measurable overhead.  See docs/monitoring.md.",
    _to_metrics_level)
METRICS_JOURNAL_DIR = _conf(
    "spark.rapids.sql.tpu.metrics.journal.dir", "",
    "Directory for per-query structured event journals (JSON-lines spans: "
    "query/operator/retry/spill/fetch events with monotonic timestamps and "
    "parent links; one query-<id>.jsonl per query).  Empty disables the "
    "file journal; at metrics.level=DEBUG an in-memory journal is kept "
    "regardless and is reachable via session.last_execution.journal.  "
    "Executor worker processes additionally write one shard-<executor>"
    ".jsonl trace shard each (docs/monitoring.md, Distributed tracing).",
    str)

# --- roofline-attribution profiler (metrics/roofline.py) ---------------------
ROOFLINE_ENABLED = _conf(
    "spark.rapids.sql.tpu.roofline.enabled", True,
    "Roofline ledger annotations in EXPLAIN METRICS: each plan node's "
    "line gains its bottleneck resource (hbm / h2d / d2h / wire / flops "
    "/ host), achieved rate, and utilization vs the resource's peak, "
    "joined from the operators' cost declarations and measured span "
    "durations.  The underlying cost COUNTERS (hbmBytesRead/Written, "
    "h2dBytes, d2hBytes, wireBytes, estFlops) are ordinary MODERATE "
    "metrics gated by metrics.level, not by this flag.  See "
    "docs/monitoring.md, 'Reading the roofline ledger'.", _to_bool)
ROOFLINE_COST_ENABLED = _conf(
    "spark.rapids.sql.tpu.roofline.costAccounting.enabled", True,
    "Per-operator roofline cost declarations (hbmBytesRead/Written, "
    "h2dBytes, d2hBytes, wireBytes, estFlops — free host-side metadata "
    "increments).  Off disables the declarations entirely (every ledger "
    "node reads host-bound), which is the A/B the bench profile stage "
    "and tests/test_roofline.py measure profiler overhead with.  "
    "Latched per query like the packed-sort flag: the declarations are "
    "observability-only, so a concurrent query with a different setting "
    "at worst records (or skips) its own declarations.", _to_bool)
ROOFLINE_PEAK_HBM = _conf(
    "spark.rapids.sql.tpu.roofline.peakHbmGBs", 0.0,
    "HBM bandwidth roofline in GB/s used as the ledger's denominator "
    "for the 'hbm' resource.  0 (default) picks the device kind's row "
    "(819 GB/s on a v5e, a conservative 20 GB/s on the CPU "
    "backend).  Set it to a measured STREAM-like figure for honest "
    "utilization percentages on your hardware.", float)
ROOFLINE_PEAK_LINK = _conf(
    "spark.rapids.sql.tpu.roofline.peakLinkGBs", 0.0,
    "Host<->device link roofline in GB/s ('h2d'/'d2h' resources).  "
    "0 picks the device kind's nominal row (8 GB/s, PCIe-class: not a "
    "published figure) — setting this to the measured "
    "transfer_microbench number makes host-detour nodes light up "
    "honestly.", float)
ROOFLINE_PEAK_WIRE = _conf(
    "spark.rapids.sql.tpu.roofline.peakWireGBs", 0.0,
    "Socket shuffle-wire roofline in GB/s ('wire' resource).  0 picks "
    "1 GB/s (the measured BENCH_WIRE loopback figure); set to your NIC "
    "line rate on a real cluster.", float)
ROOFLINE_PEAK_GFLOPS = _conf(
    "spark.rapids.sql.tpu.roofline.peakGflops", 0.0,
    "Compute roofline in GFLOP/s ('flops' resource).  0 picks the "
    "device kind's row (197 TFLOP/s bf16 on a v5e, 50 GFLOP/s on the "
    "CPU backend).", float)
ROOFLINE_PEAK_ICI = _conf(
    "spark.rapids.sql.tpu.roofline.peakIciGBs", 0.0,
    "Inter-chip-interconnect roofline in GB/s ('ici' resource): the "
    "denominator for bytes moved by mesh-lowered exchange collectives "
    "(iciBytesMoved).  0 picks the device kind's row (1,600 Gbit/s = "
    "200 GB/s per chip on a v5e; memcpy-class 20 GB/s on the virtual-device CPU "
    "backend, where the 'collective' is a compiled copy).", float)

# --- distributed tracing (metrics/timeline.py + shuffle wire trace) ----------
TRACE_ENABLED = _conf(
    "spark.rapids.sql.tpu.trace.enabled", True,
    "Cluster-wide distributed tracing: every executor worker keeps a "
    "process-lifetime journal shard (task/operator/fetch/serve spans with "
    "a wall-clock anchor record), shuffle wire requests carry a "
    "(query, stage, span, executor) trace context so a reducer's fetch "
    "span flow-links to the mapper's serve span, and the driver can drain "
    "+ merge every shard into ONE query timeline "
    "(python -m spark_rapids_tpu.metrics --timeline; "
    "cluster.merged_timeline()).  Off disables shard journaling, wire "
    "trace stamping and the heartbeat monitor.", _to_bool)
TRACE_STRAGGLER_FACTOR = _conf(
    "spark.rapids.sql.tpu.trace.stragglerFactor", 3.0,
    "A task whose duration exceeds this factor times the median duration "
    "of its stage's tasks is flagged as a straggler by the merged-"
    "timeline analysis (numStragglers; --timeline report).", float)
TRACE_HEARTBEAT_INTERVAL = _conf(
    "spark.rapids.sql.tpu.trace.heartbeatIntervalMs", 1000,
    "Interval between live progress heartbeats pulled from every worker "
    "over a DEDICATED control connection (counters, pool stats, active-"
    "task snapshots -> session.progress() / cluster.progress()).  "
    "0 disables the heartbeat monitor.", int)
TRACE_HUNG_TASK_TIMEOUT = _conf(
    "spark.rapids.sql.tpu.trace.hungTaskTimeoutMs", 600000,
    "A task still active past this bound in a worker's heartbeat "
    "snapshots is logged by the driver's hung-task watchdog and counted "
    "(numHungTasks).  0 disables the watchdog.", int)
TRACE_SHARD_MAX_EVENTS = _conf(
    "spark.rapids.sql.tpu.trace.shard.maxEvents", 65536,
    "Bound on undrained in-memory trace-shard events per worker; overflow "
    "evicts the oldest events and is counted in the drain response "
    "(a driver that never drains must not leak worker memory).", int,
    internal=True)

# --- live telemetry plane (metrics/ring.py + bundle.py + http.py) ------------
TELEMETRY_ENABLED = _conf(
    "spark.rapids.sql.tpu.telemetry.enabled", True,
    "Always-on flight recorder: every process (driver and each executor "
    "worker) keeps a bounded in-memory ring of its last journal records "
    "plus a background gauge-sampler thread snapshotting pool / "
    "transport / scheduler gauges into fixed-interval time series.  The "
    "ring and sampler feed the /metrics endpoint, the Chrome-trace "
    "counter lanes, and post-mortem bundles; their measured overhead is "
    "gated at <=2% wall time by scripts/obs_overhead.py (BENCH_OBS.json). "
    " Off disables the ring tap, the sampler thread and the per-process "
    "HTTP endpoints.", _to_bool)
TELEMETRY_RING_MAX_EVENTS = _conf(
    "spark.rapids.sql.tpu.telemetry.ring.maxEvents", 2048,
    "Capacity of the per-process flight-recorder ring: the last N "
    "journal records are mirrored in memory (oldest evicted first, "
    "evictions counted) and land in post-mortem bundles as "
    "ring-<process>.jsonl.  Sized so a bundle holds the final seconds "
    "of every process at negligible resident cost.", int)
TELEMETRY_SAMPLE_INTERVAL = _conf(
    "spark.rapids.sql.tpu.telemetry.sampleIntervalMs", 250,
    "Interval between gauge-sampler snapshots (pool bytes in use, "
    "in-flight tasks, spill bytes, scheduler queue depths).  Each "
    "snapshot appends one point per series to the in-memory time series "
    "served by /metrics and, when a trace shard is open, one "
    "gaugeSample journal instant that becomes a Chrome-trace counter "
    "lane.  0 disables the sampler thread (the ring tap stays on).",
    int)
TELEMETRY_SAMPLE_MAX = _conf(
    "spark.rapids.sql.tpu.telemetry.sample.maxSamples", 2400,
    "Bound on retained points per sampled gauge series; overflow evicts "
    "the oldest points (10 minutes of history at the default 250ms "
    "interval).", int, internal=True)
TELEMETRY_HTTP_ENABLED = _conf(
    "spark.rapids.sql.tpu.telemetry.http.enabled", True,
    "Per-process loopback HTTP endpoint serving /metrics (Prometheus "
    "text of the sampler's current series, parse_prometheus-clean), "
    "/healthz (liveness verdict) and /debug/observability "
    "(session_observability + progress as JSON).  Workers announce "
    "their port in the ready line; the driver's is in "
    "session_observability['telemetry']['http_address'].", _to_bool)
TELEMETRY_HTTP_PORT = _conf(
    "spark.rapids.sql.tpu.telemetry.http.port", 0,
    "Port for the driver telemetry HTTP endpoint (workers always bind "
    "an ephemeral loopback port and announce it).  0 (default) binds an "
    "ephemeral port.", int)
TELEMETRY_POSTMORTEM_DIR = _conf(
    "spark.rapids.sql.tpu.telemetry.postmortem.dir", "",
    "Directory for automatic post-mortem diagnostic bundles.  When set, "
    "a bundle (config, EXPLAIN with roofline, merged timeline, "
    "memledger replay, SLO state, per-process ring dumps) is dumped on "
    "query failure, hung-task watchdog fire, retry-budget exhaustion, "
    "and SIGUSR1; render one with "
    "`python -m spark_rapids_tpu.metrics postmortem <bundle>`.  "
    "Empty (default) disables automatic dumps — "
    "session.dump_diagnostics() stays available either way.", str)
TELEMETRY_POSTMORTEM_MIN_INTERVAL = _conf(
    "spark.rapids.sql.tpu.telemetry.postmortem.minIntervalMs", 30000,
    "Rate limit between automatic post-mortem dumps: a trigger firing "
    "within this window of the previous dump is counted "
    "(numPostmortemSuppressed) instead of dumped, so a failure storm "
    "cannot fill the disk.", int, internal=True)

# --- distributed task scheduling: deadlines, backoff, speculation ------------
TASK_TIMEOUT = _conf(
    "spark.rapids.sql.tpu.task.timeoutMs", 0,
    "Per-attempt deadline for a distributed task rpc (run_map/run_reduce "
    "on a ProcCluster worker), in milliseconds.  A task past its deadline "
    "is ABANDONED (counted in numAbandonedTasks), its worker is "
    "health-probed over the heartbeat monitor's dedicated connection, and "
    "a wedged-but-alive worker is evicted exactly like a dead one "
    "(replaced, its map fragments recomputed from the lineage).  "
    "0 (default) derives the deadline from "
    "spark.rapids.sql.tpu.trace.hungTaskTimeoutMs; set both to 0 to run "
    "task waves unbounded (the pre-deadline behavior).", int)
TASK_RETRY_BACKOFF = _conf(
    "spark.rapids.sql.tpu.task.retryBackoffMs", 200,
    "Base backoff in milliseconds between distributed task retry waves; "
    "wave k waits ~base*2^k with deterministic jitter, capped by "
    "task.maxBackoffMs — a failed wave backs off instead of hammering a "
    "recovering peer.  0 disables the inter-wave backoff.", int)
TASK_MAX_BACKOFF = _conf(
    "spark.rapids.sql.tpu.task.maxBackoffMs", 10000,
    "Upper bound in milliseconds on the distributed task retry backoff.",
    int)
TASK_SPECULATION_ENABLED = _conf(
    "spark.rapids.sql.tpu.task.speculation.enabled", True,
    "Speculatively re-execute straggling distributed tasks: when a task "
    "runs longer than spark.rapids.sql.tpu.trace.stragglerFactor x the "
    "median task duration of its stage (or past the hung-task watchdog "
    "bound), a second copy launches on the least-loaded healthy worker "
    "under a distinct attempt id.  First result wins; the loser is "
    "cancelled/ignored and map-output registration is attempt-id-guarded "
    "so the reduce side never reads a mix of attempts "
    "(numSpeculativeTasks / numSpeculationWins).", _to_bool)
TASK_MAX_WORKER_REPLACEMENTS = _conf(
    "spark.rapids.sql.tpu.task.maxWorkerReplacements", 8,
    "Worker replacements allowed per query (run_map_reduce call) before "
    "the cluster degrades gracefully: when the budget is exhausted — or "
    "a replacement spawn itself fails — the dead worker's slot is "
    "SHRUNK away and its task assignments re-balance onto the surviving "
    "workers instead of failing the query (worker_shrinks counter, "
    "journal kind 'spec').  Negative means unlimited.", int)

# --- memory ledger (mem/ledger.py + metrics/memledger.py) --------------------
MEM_LEDGER_ENABLED = _conf(
    "spark.rapids.sql.tpu.memory.ledger.enabled", True,
    "Memory-pressure ledger: journal every allocation-boundary event of "
    "the spill framework (alloc/free/spill/unspill/oomSpill, journal kind "
    "'mem') stamped with the active trace context and causally linked — "
    "an oomSpill record names the triggering reservation site and the "
    "exact victim buffer ids, so spill cascades are traversable chains.  "
    "Events land in the active query journal / worker trace shard; "
    "`python -m spark_rapids_tpu.metrics --memory <journal-dir>` "
    "reconstructs peak attribution, spill churn, victim quality and a "
    "headroom estimate offline.  At metrics.level=DEBUG every reserve() "
    "is additionally journaled; below DEBUG only pressured reservations "
    "are (docs/tuning-guide.md, Memory observability).", _to_bool)
MEM_LEDGER_SAMPLE_MS = _conf(
    "spark.rapids.sql.tpu.memory.ledger.sampleIntervalMs", 100,
    "Minimum milliseconds between sampled memory-pressure records "
    "(ledger 'pressure' instants carrying per-tier used bytes + the pool "
    "limit — the per-worker memory lane of the Chrome trace / merged "
    "timeline).  OOM events always force a sample.  0 samples on every "
    "ledger event.", int)

# --- data-movement policy engine (policy/) -----------------------------------
POLICY_ENABLED = _conf(
    "spark.rapids.sql.tpu.policy.enabled", True,
    "Master switch for the data-movement policy engine (policy/): "
    "next-use spill victim selection, proactive unspill of soon-needed "
    "buffers, reduce-driven shuffle flow control, and roofline-driven "
    "codec re-selection.  The engine only CONSUMES signals the ledgers "
    "already produce (memory ledger re-touch history, shuffle read "
    "order, roofline wire peak) and journals every decision under kind "
    "'policy'.  false is the kill switch: victim order, fetch admission "
    "and wire codec revert byte-identically to the pre-policy engine "
    "(docs/tuning-guide.md, Data-movement policy).", _to_bool)
POLICY_RETOUCH_WEIGHT = _conf(
    "spark.rapids.sql.tpu.policy.victim.retouchWeight", 4.0,
    "Score bonus protecting a spill victim per prior spill of the same "
    "buffer (capped at 4 round trips).  The memory ledger's re-touch "
    "history is the churn signal: a buffer that already paid a "
    "spill+unspill round trip is this much LESS likely to be evicted "
    "again than a never-spilled peer.  0 disables re-touch protection; "
    "victims then rank purely on shuffle-partition liveness.", float)
POLICY_EARLY_RELEASE = _conf(
    "spark.rapids.sql.tpu.policy.earlyRelease.enabled", True,
    "Free a shuffle partition's map-side device buffers as soon as the "
    "declared read plan has consumed it for the LAST time (single-"
    "consumer local exchanges only — never with a cluster attached, "
    "where a peer or a speculative re-read may still fetch the block).  "
    "A fully-consumed partition has next-use = never: releasing it "
    "outright returns its bytes to the pool with no spill write, where "
    "the baseline would re-spill it under pressure and count churn.  "
    "Skew slices and coalesced specs that read a partition more than "
    "once are planned for — the release fires only after the final "
    "planned consumption.", _to_bool)
POLICY_UNSPILL_INTERVAL = _conf(
    "spark.rapids.sql.tpu.policy.unspill.intervalMs", 20,
    "Wake interval of the proactive-unspill policy thread.  Each tick "
    "re-materializes up to a few spilled buffers with the nearest "
    "declared next use, charged to the owning query's ledger scope "
    "(and its serve.queryBudgetBytes, so a prefetch can never cause "
    "another query's OOM).  0 disables the thread; victim scoring and "
    "flow control stay active.", int)
POLICY_UNSPILL_HEADROOM = _conf(
    "spark.rapids.sql.tpu.policy.unspill.headroomFraction", 0.5,
    "Pool fraction that must remain free AFTER a proactive unspill for "
    "it to be admitted — the prefetch is opportunistic and must never "
    "push the device pool toward an eviction it would not otherwise "
    "have performed.  Unspills additionally require the pool to be "
    "spill-quiescent since the policy's previous tick.", float)
POLICY_FLOW_MIN_WINDOW = _conf(
    "spark.rapids.sql.tpu.policy.flow.minWindowBytes", 4 << 20,
    "Floor of the reduce-driven flow-control window.  The window is "
    "max(this, observed reduce consumption rate x flow.horizonMs): a "
    "stalled consumer shrinks admission to this floor (progress is "
    "always possible; one batch of any size still admits alone), a fast "
    "consumer widens it up to the transport's static "
    "maxReceiveInflightBytes bound.", to_bytes)
POLICY_FLOW_HORIZON = _conf(
    "spark.rapids.sql.tpu.policy.flow.horizonMs", 200,
    "Flow-control horizon: the in-flight-bytes window targets this many "
    "milliseconds of the reduce side's observed consumption rate, so a "
    "producer holds at most ~horizon's worth of un-consumed bytes in "
    "flight instead of ballooning host memory behind a slow consumer.",
    int)
POLICY_FLOW_MAX_STALL = _conf(
    "spark.rapids.sql.tpu.policy.flow.maxServeStallMs", 50,
    "Upper bound on one map-side serve stall when in-flight served "
    "bytes exceed the flow-control window; past it the serve proceeds "
    "anyway (soft backpressure — bounded stalls cannot deadlock the "
    "exchange; counted in numBackpressureStalls).", int, internal=True)
POLICY_CODEC = _conf(
    "spark.rapids.sql.tpu.policy.codec.candidate", "lz4",
    "Codec the policy engine advises for fetches of an exchange proven "
    "wire-bound at runtime (read throughput at or above "
    "codec.wireBoundFraction of the roofline wire peak at "
    "codec.minExchangeBytes volume).  Rides the shuffle compression "
    "negotiation end to end — the server may still answer raw when the "
    "codec is unavailable there.  'none' disables re-selection; a "
    "session with spark.rapids.shuffle.compression.codec explicitly "
    "enabled is never second-guessed.", str)
POLICY_CODEC_MIN_BYTES = _conf(
    "spark.rapids.sql.tpu.policy.codec.minExchangeBytes", 32 << 20,
    "Minimum wire bytes an exchange's read phase must have moved before "
    "its throughput evidence can trigger codec re-selection — tiny "
    "exchanges prove nothing and never pay codec CPU.", to_bytes)
POLICY_CODEC_WIRE_BOUND = _conf(
    "spark.rapids.sql.tpu.policy.codec.wireBoundFraction", 0.5,
    "Fraction of the roofline wire peak (metrics/roofline.py "
    "platform_peaks, overridable via ROOFLINE_PEAK_* confs) an "
    "exchange's observed read throughput must reach to be judged "
    "wire-bound for codec re-selection.", float)

# --- serving tier (serve/: scheduler, admission, plan cache) -----------------
SERVE_MAX_CONCURRENT = _conf(
    "spark.rapids.sql.tpu.serve.maxConcurrentQueries", 4,
    "Worker threads the session's QueryScheduler runs — the upper bound "
    "on queries EXECUTING at once (TpuSession.submit).  Device occupancy "
    "within an executing query is still bounded by "
    "spark.rapids.sql.concurrentTpuTasks (the device semaphore); this "
    "knob bounds how many queries overlap their host-side phases "
    "(planning, scan decode, D2H) around it.", int)
SERVE_QUEUE_CAPACITY = _conf(
    "spark.rapids.sql.tpu.serve.queue.capacity", 256,
    "Submitted-but-not-yet-admitted queries the scheduler will hold; a "
    "submit() past this bound raises AdmissionRejected (counted in "
    "numAdmissionRejections) instead of buffering without bound — "
    "backpressure belongs at admission, not in the spill tier.", int)
SERVE_ADMISSION_FRACTION = _conf(
    "spark.rapids.sql.tpu.serve.admission.memoryFraction", 1.5,
    "Fair-share admission bound: the sum of in-flight queries' declared/"
    "estimated memory needs is kept under this fraction of the accounted "
    "HBM pool (poolSizeBytes / allocFraction x detected HBM).  >1 "
    "oversubscribes deliberately — estimates are peak, not resident, and "
    "the spill tier absorbs overlap; <1 keeps headroom for unestimated "
    "allocations.  A query whose need alone exceeds the bound is still "
    "admitted when nothing else is in flight (progress over strictness).",
    float)
SERVE_DEFAULT_NEED = _conf(
    "spark.rapids.sql.tpu.serve.defaultMemoryNeedBytes", 256 << 20,
    "Memory need assumed for a submitted query when the caller declared "
    "none and the planner's size estimate is unavailable (memory scans "
    "of unknown size, exotic plans).", to_bytes)
SERVE_QUERY_BUDGET = _conf(
    "spark.rapids.sql.tpu.serve.queryBudgetBytes", 0,
    "Per-query device-bytes budget enforced at reserve() time for "
    "queries run through the scheduler: a query over its budget spills "
    "its OWN buffers (never its neighbors'), then raises RetryOOM into "
    "its own spill-retry/split/CPU-fallback ladder (numBudgetOoms).  "
    "0 disables; size it ~poolSizeBytes / maxConcurrentQueries so "
    "concurrent peaks cannot force cross-query eviction "
    "(docs/tuning-guide.md, Concurrent serving).", to_bytes)
SERVE_PLAN_CACHE_ENABLED = _conf(
    "spark.rapids.sql.tpu.serve.planCache.enabled", True,
    "Parameterized plan cache for scheduler-submitted queries "
    "(serve/plan_cache.py): literals in row-local positions are lifted "
    "into parameters, the normalized plan keys the cache, and parameter "
    "values enter compiled whole-stage programs as runtime arguments — "
    "so the 2nd..Nth literal-variant submission skips trace AND compile "
    "(planCacheHits).  Blocking collect() paths are unaffected.",
    _to_bool)
SERVE_PLAN_CACHE_SIZE = _conf(
    "spark.rapids.sql.tpu.serve.planCache.maxEntries", 128,
    "LRU bound on distinct normalized plans the plan cache tracks.", int)
SERVE_LIFECYCLE_ENABLED = _conf(
    "spark.rapids.sql.tpu.serve.lifecycle.enabled", True,
    "Query lifecycle layer for scheduler-submitted queries "
    "(serve/lifecycle.py): cooperative cancellation "
    "(QueryFuture.cancel()), per-query deadlines (submit deadline_ms=, "
    "with admission-time shedding) and SLO-aware preemption all ride a "
    "per-query token checked at reserve()/retry/stage/exchange "
    "boundaries.  Kill switch: false installs no token at all, making "
    "every checkpoint a no-op byte-identical to the pre-lifecycle "
    "paths — cancel() then returns False and deadlines are ignored.",
    _to_bool)
SERVE_PREEMPTION_ENABLED = _conf(
    "spark.rapids.sql.tpu.serve.preemption.enabled", False,
    "SLO-aware preemption: when a higher-priority query arrives while a "
    "lower-priority one holds the admission share/device gate, the "
    "scheduler asks the victim to suspend at its next stage boundary — "
    "its device buffers park as spillable state charged to its own "
    "budget, its semaphore slots and admission share release — and "
    "resume FIFO-within-priority once no higher-priority work remains, "
    "bit-for-bit with the unpreempted run (numPreemptions, "
    "numPreemptionResumes, SLO phase 'preempt').  Off by default: "
    "preemption trades victim latency for latency-class p99, a policy "
    "choice the operator should opt into (docs/tuning-guide.md, Query "
    "lifecycle).  Requires serve.lifecycle.enabled.", _to_bool)
SERVE_PREEMPTION_RESUME_TIMEOUT = _conf(
    "spark.rapids.sql.tpu.serve.preemption.resumeTimeoutSeconds", 600.0,
    "Hard bound on how long a preempted query stays suspended waiting "
    "for the scheduler's resume grant; past it the victim force-resumes "
    "(re-taking its admission share even over budget) so a scheduler "
    "fault can never hang a suspended query forever.", float)
SERVE_DEADLINE_SHED_FACTOR = _conf(
    "spark.rapids.sql.tpu.serve.deadline.shedSafetyFactor", 1.0,
    "Admission-time shedding margin: a query is shed (numDeadlineSheds, "
    "typed QueryDeadlineExceeded on its future) when its remaining "
    "deadline is under this factor x the scheduler's EWMA of observed "
    "plan+compile seconds — rejecting a doomed query at admission is "
    "cheaper than admitting it to time out mid-compile.  0 disables "
    "estimate-based shedding (already-expired deadlines still shed).",
    float)

# --- streaming micro-batch engine (streaming/) ------------------------------
STREAM_MAX_BATCH_ROWS = _conf(
    "spark.rapids.sql.tpu.streaming.maxBatchRows", 65536,
    "Upper bound on rows one streaming epoch reads from an append-only "
    "source (streaming/source.py epoch planner).  Keeping it CONSTANT "
    "for a query's lifetime keeps micro-batch capacities in one bucket, "
    "so warm epochs replay compiled stages instead of re-tracing "
    "(docs/tuning-guide.md, Streaming micro-batch execution).", int)
STREAM_MAX_FILES_PER_EPOCH = _conf(
    "spark.rapids.sql.tpu.streaming.maxFilesPerEpoch", 1,
    "Upper bound on newly-arrived files one epoch of a directory-tail "
    "streaming source decodes through the io/ device readers.", int)
STREAM_CHECKPOINT_KEEP = _conf(
    "spark.rapids.sql.tpu.streaming.checkpoint.keepEpochs", 2,
    "Committed epoch snapshots retained in a streaming checkpoint "
    "directory; older epoch dirs are pruned after each atomic commit "
    "(the commit marker always lands last, so a kill mid-commit "
    "resumes from the previous epoch bit-for-bit).", int)
STREAM_EPOCH_DEADLINE_MS = _conf(
    "spark.rapids.sql.tpu.streaming.epochDeadlineMs", 0.0,
    "Default per-epoch deadline for streaming queries: each epoch is a "
    "scheduler query carrying a lifecycle token, so past the deadline "
    "it stops at its next checkpoint with QueryDeadlineExceeded and "
    "owner-confined cleanup — the stream's device-resident state is "
    "untouched and the next trigger retries the epoch.  0 disables.",
    float)

# --- export -----------------------------------------------------------------
EXPORT_COLUMNAR_RDD = _conf(
    "spark.rapids.sql.exportColumnarRdd", False,
    "Allow exporting device columnar data for ML integration "
    "(ColumnarRdd equivalent).", _to_bool)


class TpuConf:
    """A view over string settings, like RapidsConf over SparkConf."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None,
                 use_env: bool = True):
        self._settings: Dict[str, Any] = {}
        if use_env:
            for k, v in os.environ.items():
                if k.startswith("SPARK_RAPIDS_"):
                    key = k.lower().replace("_", ".").replace(
                        "spark.rapids.", "spark.rapids.", 1)
                    self._settings[key] = v
        if settings:
            self._settings.update(settings)

    def get(self, entry_or_key):
        if isinstance(entry_or_key, ConfEntry):
            return entry_or_key.get(self)
        entry = _REGISTRY.get(entry_or_key)
        if entry is not None:
            return entry.get(self)
        return self._settings.get(entry_or_key)

    def set(self, key: str, value) -> "TpuConf":
        self._settings[key] = value
        return self

    def is_op_enabled(self, conf_key: str, default: bool = True) -> bool:
        raw = self._settings.get(conf_key)
        if raw is None:
            return default
        return _to_bool(raw)

    # convenience properties (subset; prefer .get(ENTRY))
    @property
    def sql_enabled(self):
        return self.get(SQL_ENABLED)

    @property
    def is_test_enabled(self):
        return self.get(TEST_CONF)

    @property
    def explain(self):
        return str(self.get(EXPLAIN)).upper()

    @property
    def batch_size_bytes(self):
        return self.get(BATCH_SIZE_BYTES)


def registered_entries() -> List[ConfEntry]:
    return sorted(_REGISTRY.values(), key=lambda e: e.key)


def help_doc(include_internal: bool = False) -> str:
    """Generate docs/configs.md, like RapidsConf.help (RapidsConf.scala:600-689)."""
    lines = [
        "# TPU Accelerator for Apache Spark — Configuration",
        "",
        "The following configs are generated from the registry in "
        "`spark_rapids_tpu/config.py`; do not edit by hand.",
        "",
        "Name | Description | Default Value",
        "-----|-------------|--------------",
    ]
    for e in registered_entries():
        if e.internal and not include_internal:
            continue
        lines.append(f"{e.key}|{e.doc}|{e.default}")
    lines += [
        "",
        "## Fine-tuning: per-operator enables",
        "",
        "Every accelerated expression, exec, scan and partitioning also gets an "
        "auto-derived boolean config `spark.rapids.sql.<kind>.<Name>` that can "
        "force it back to the CPU (see `spark_rapids_tpu/plan/overrides.py`).",
        "",
    ]
    return "\n".join(lines)


def write_config_docs(path: str = None) -> str:
    """Emit docs/configs.md from the registry (the reference generates its
    configs.md from RapidsConf.main the same way, RapidsConf.scala:689)."""
    import os
    if path is None:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "docs", "configs.md")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    text = help_doc()
    with open(path, "w") as f:
        f.write(text)
    return path


if __name__ == "__main__":  # python -m spark_rapids_tpu.config
    print(write_config_docs())
