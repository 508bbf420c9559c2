"""Flagship benchmark: TPC-H Q6/Q1 + scan-included Q6 + TPC-DS q5 on the
device engine vs this framework's own CPU (pyarrow) executors.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
  metric/value = device-engine steady-state throughput on the HEADLINE
                query (TPC-H Q6 over a device-resident cached table, the
                same metric as rounds 1-3 so the series stays comparable)
  vs_baseline  = speedup over the CPU oracle on the same query (the
                 stand-in for the reference's CPU-Spark-vs-GPU headline,
                 19.8x, reference README.md:7-15)
  extra        = per-query breakdown (Q6 cached, Q6 scan-included from
                 parquet on disk, Q1 grouped agg, TPC-DS q5 joins),
                 host-link transfer microbench (H2D/D2H MB/s, dispatch
                 latency), effective GB/s vs an HBM roofline, and
                 vs_ref_headline = vs_baseline / 19.8 (the
                 engine-vs-reference-target ratio; VERDICT r3 item 10).

Robustness (a hung device run must not erase the evidence):
  * ALL device work runs in ONE CHILD that streams one JSON line per
    completed stage; the parent never touches jax (a chip belongs to one
    process at a time) and mirrors every line into BENCH_partial.json;
  * the child enforces ITS OWN deadline: after every stage/run it checks
    the clock, emits {"stage":"abort"} and exits cleanly (sys.exit(0));
    a child that overruns the parent's budget is killed;
  * the CPU oracle runs in its own forced-CPU child, so a device hang can
    never erase the baseline;
  * there is no CPU substitute: a device run that yields no q6 makes the
    bench exit non-zero.  `JAX_PLATFORMS=cpu python bench.py` measures the
    engine on the CPU backend because it was asked to, and the unit
    carries the platform tag ([cpu]).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
N_ROWS = int(os.environ.get("BENCH_ROWS", 6_000_000))  # ~SF1 lineitem
TPCDS_SF = float(os.environ.get("BENCH_TPCDS_SF", 0.1))
N_RUNS = 3
# The driver's own benchmark timeout killed rounds 1-2 at ~450s; everything
# must finish (or be abandoned) inside this global budget.
GLOBAL_BUDGET_S = float(os.environ.get("BENCH_GLOBAL_S", 400))
TPU_PROBE_S = float(os.environ.get("BENCH_TPU_PROBE_S", 240))
T0 = time.time()

sys.path.insert(0, REPO)
# lineitem generator + the q6/q1 shapes are shared with chip_smoke.py
from benchmarks.tpch.bulk import (D_1994, D_1995, D_19980902,  # noqa: E402,F401
                                  make_lineitem, q1, q6)


def log(msg: str) -> None:
    print(f"[bench +{time.time() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


# --------------------------------------------------------------------------
# child: executes the workload on one backend, emits a JSON line per stage
# --------------------------------------------------------------------------

_SILENT = False
_DEADLINE = [float("inf")]


def emit(stage: str, **kw):
    global _SILENT
    if _SILENT:
        return
    try:
        print(json.dumps({"stage": stage, **kw}), flush=True)
    except (BrokenPipeError, OSError):
        # parent abandoned us; keep running to a clean exit, silently
        _SILENT = True


def checkpoint(label: str) -> None:
    """Clean in-process deadline: abort BETWEEN units of work, so the
    stages already emitted stay valid evidence."""
    if time.time() > _DEADLINE[0]:
        emit("abort", reason="deadline", at=label)
        sys.exit(0)


def checksum(rows) -> float:
    """Stable scalar over a collected result for the oracle cross-check."""
    acc = 0.0
    for r in rows:
        for v in r:
            if isinstance(v, bool) or v is None:
                acc += 1.0 if v else 0.0
            elif isinstance(v, (int, float)):
                acc += float(v)
            else:
                acc += float(sum(str(v).encode()) % 1000)
    return acc


def timed(name: str, fn, n_runs: int) -> None:
    t0 = time.time()
    val = fn()
    emit("warmup", q=name, t=time.time() - t0, value=val)
    checkpoint(name)
    for i in range(n_runs):
        t0 = time.time()
        val = fn()
        emit("run", q=name, i=i, t=time.time() - t0, value=val)
        checkpoint(name)


def transfer_microbench():
    """Host-link microbench: H2D and D2H MB/s, per-dispatch latency.
    Context for the roofline numbers (the host link's own rates)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    h = np.empty(16 << 20, np.uint8)  # 16 MiB
    t = []
    for _ in range(2):
        t0 = time.time()
        d = jax.device_put(h)
        d.block_until_ready()
        t.append(time.time() - t0)
    h2d = (16 / min(t)) if min(t) > 0 else 0.0
    small = jax.device_put(np.empty(2 << 20, np.uint8))
    small.block_until_ready()
    t0 = time.time()
    np.asarray(small)
    d2h_t = time.time() - t0
    d2h = (2 / d2h_t) if d2h_t > 0 else 0.0
    x = jnp.ones(1024, jnp.float32)
    f = jax.jit(lambda a: a + 1)
    f(x).block_until_ready()
    t0 = time.time()
    for _ in range(20):
        y = f(x)
    y.block_until_ready()
    disp_ms = (time.time() - t0) / 20 * 1e3
    emit("transfer", h2d_mb_s=round(h2d, 1), d2h_mb_s=round(d2h, 1),
         dispatch_ms=round(disp_ms, 3))


def integrity_microbench(session) -> dict:
    """Checksum on/off wire-throughput delta (the ISSUE-4 acceptance
    number): an in-process socket pair streams a buffer with reader-side
    verification enabled then disabled; the delta is the integrity tax.
    On a multi-core host the AsyncLeafVerifier overlaps hashing with the
    recv loop (expected <=5% with crc32c); on a single-core container the
    hash cannot hide behind the wire and costs ~wire_rate/hash_rate
    (~10% at 1 GB/s) — `single_core` labels the number accordingly.
    Session-cumulative integrity counters ride along so a perf number is
    never read without knowing whether corruption recovery fired."""
    import numpy as np
    from spark_rapids_tpu.mem.integrity import ChecksumPolicy
    from spark_rapids_tpu.metrics import names as MN
    from spark_rapids_tpu.shuffle.net import (ShuffleSocketServer,
                                              SocketTransport)

    nbytes = 32 << 20
    data = np.arange(nbytes, dtype=np.uint8)
    policy = ChecksumPolicy(True, "crc32c")
    digest = policy.checksum_one(data)

    class OneBufferServer:
        def buffer_layout(self, bid):
            return [((nbytes,), "uint8", nbytes)], {"bid": bid}

        def buffer_checksums(self, bid):
            return (policy.algorithm, (digest,))

        def copy_leaf_chunk(self, bid, li, off, length, view):
            view[:length] = data[off:off + length]

        def done_serving(self, bid):
            pass

    srv = SocketTransport(pool_size=16 << 20, chunk_size=4 << 20,
                          max_inflight_bytes=1 << 40)
    server = ShuffleSocketServer(srv, OneBufferServer())
    cli = SocketTransport(pool_size=16 << 20, chunk_size=4 << 20,
                          max_inflight_bytes=1 << 40)
    cli.set_peers({"peer": server.address})
    client = cli.make_client("peer")
    try:
        client.fetch_buffer(1)  # warm (connect + allocations)

        def measure(n=3):
            best = 0.0
            for _ in range(n):
                t0 = time.time()
                out, _meta = client.fetch_buffer(2)
                assert out[0].nbytes == nbytes
                best = max(best, nbytes / (time.time() - t0) / 1e6)
            return best

        results = {}
        for label, pol in (("on", ChecksumPolicy(True, "crc32c")),
                           ("off", ChecksumPolicy(False, "crc32c"))):
            cli.integrity = pol
            results[label] = measure()
    finally:
        server.close()
        srv.shutdown()
        cli.shutdown()
    overhead = (results["off"] - results["on"]) / results["off"] * 100 \
        if results["off"] > 0 else 0.0
    totals = dict(getattr(session, "query_metrics_total", {}) or {})
    pool = session.runtime.pool_stats() if session._runtime is not None \
        else {}
    return {
        "algorithm": policy.algorithm,
        "wire_mb_s_checksum_on": round(results["on"], 1),
        "wire_mb_s_checksum_off": round(results["off"], 1),
        "overhead_pct": round(overhead, 2),
        "single_core": (os.cpu_count() or 1) <= 1,
        "numChecksumMismatches": int(
            totals.get(MN.NUM_CHECKSUM_MISMATCHES, 0)
            + pool.get(MN.NUM_CHECKSUM_MISMATCHES, 0)),
        "numCorruptionRefetches": int(
            totals.get(MN.NUM_CORRUPTION_REFETCHES, 0)
            + pool.get(MN.NUM_CORRUPTION_REFETCHES, 0)),
        "numLostMapOutputs": int(
            totals.get(MN.NUM_LOST_MAP_OUTPUTS, 0)
            + pool.get(MN.NUM_LOST_MAP_OUTPUTS, 0)),
        "checksumTime_s": round(float(
            pool.get(MN.CHECKSUM_TIME, 0.0)), 4),
    }


def compress_microbench() -> dict:
    """Spill write/read delta per codec (the ISSUE-5 acceptance number):
    the host->disk spill path timed with compression off and on, same
    leaves, same disk.  `none` is the current raw path — the on/off delta
    is the codec tax (or win) at the spill tier; the wire-side per-codec
    numbers live in BENCH_WIRE.json (tests/test_wire_throughput.py)."""
    import tempfile

    import numpy as np
    from spark_rapids_tpu.compress import (CompressionPolicy,
                                           available_codecs, resolve_codec)
    from spark_rapids_tpu.mem.buffer import read_leaves, write_leaves
    from spark_rapids_tpu.mem.buffer import BatchMeta, ColumnLeafMeta

    rng = np.random.RandomState(42)
    n = 2_000_000  # ~48MB of typical columnar leaves
    leaves = [
        np.cumsum(rng.randint(0, 10, n)).astype(np.int64),  # sorted-ish
        rng.uniform(900.0, 105000.0, n),                    # prices
        np.ones(n, dtype=np.bool_),                          # validity
    ]
    meta = BatchMeta(
        schema=None, capacity=n,
        leaf_meta=[ColumnLeafMeta(str(a.dtype), [a.shape], [a.dtype.str])
                   for a in leaves[:-1]],
        sel_shape=leaves[-1].shape,
        size_bytes=sum(a.nbytes for a in leaves))
    raw_total = sum(a.nbytes for a in leaves)
    out = {"nbytes": raw_total, "codecs": {}}
    with tempfile.TemporaryDirectory(prefix="bench_spill_") as d:
        for codec_name in ["none"] + [c for c in ("lz4", "zstd")
                                      if c in available_codecs()]:
            pol = CompressionPolicy(codec_name, min_size=0)
            path = os.path.join(d, f"spill_{codec_name}.bin")
            t0 = time.time()
            if pol.enabled:
                frames = pol.compress_leaves(leaves)
                write_leaves(path, frames)
                disk_bytes = sum(f.nbytes for f in frames)
            else:
                write_leaves(path, leaves)
                disk_bytes = raw_total
            w_t = time.time() - t0
            t0 = time.time()
            if pol.enabled:
                from spark_rapids_tpu.native import spill_read
                raw = spill_read(path, disk_bytes)
                codec = resolve_codec(codec_name)
                off = 0
                back = []
                for f in frames:
                    frame = np.frombuffer(raw, np.uint8, count=f.nbytes,
                                          offset=off)
                    back.append(pol.decompress_one(frame, codec))
                    off += f.nbytes
                assert sum(b.nbytes for b in back) == raw_total
            else:
                back = read_leaves(path, meta)
            r_t = time.time() - t0
            out["codecs"][codec_name] = {
                "write_mb_s": round(raw_total / w_t / 1e6, 1),
                "read_mb_s": round(raw_total / r_t / 1e6, 1),
                "disk_bytes": disk_bytes,
                "ratio": round(raw_total / disk_bytes, 2),
            }
    base = out["codecs"].get("none", {})
    for name, rec in out["codecs"].items():
        if name != "none" and base.get("write_mb_s"):
            rec["write_delta_pct"] = round(
                (rec["write_mb_s"] - base["write_mb_s"])
                / base["write_mb_s"] * 100, 1)
            rec["read_delta_pct"] = round(
                (rec["read_mb_s"] - base["read_mb_s"])
                / base["read_mb_s"] * 100, 1)
    out["host_cpus"] = os.cpu_count() or 1
    out["available_codecs"] = available_codecs()
    return out


def fusion_microbench() -> dict:
    """Whole-stage fusion on/off deltas (the ISSUE-6 acceptance numbers):
    a q1-shaped pipeline (scan -> filter -> project -> partial agg) and an
    exchange-bucketing pipeline, each run on a fresh session with cleared
    kernel caches, recording per-query jit-compile count, per-batch
    dispatch count, and warmup seconds — so the compile-count claim
    (>= 2x fewer programs with fusion ON) is measured, not asserted."""
    import jax
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.plan.logical import col, functions as F, lit
    from spark_rapids_tpu.utils import kernel_cache as KC

    # ground truth for compile counts: jax fires one
    # /jax/compilation_cache/compile_requests_use_cache per compiled
    # computation, EAGER primitives included — so the count also sees the
    # per-op dispatch programs fusion eliminates (our kernel_cache
    # counters only see whole programs built through the exec layer)
    xla_compiles = [0]
    try:
        jax.monitoring.register_event_listener(
            lambda name, **kw: xla_compiles.__setitem__(
                0, xla_compiles[0]
                + (name == "/jax/compilation_cache/"
                           "compile_requests_use_cache")))
    except Exception:
        pass

    n = 200_000
    base_conf = {
        "spark.rapids.sql.variableFloatAgg.enabled": "true",
        # several reader batches so per-batch dispatch counts mean
        # something (one giant batch would make every mode look fused)
        "spark.rapids.sql.reader.batchSizeRows": str(n // 4),
        "spark.rapids.sql.tpu.memoryScanCache.enabled": "false",
    }

    def q1_shape(s, df):
        return (df.filter(col("l_shipdate") <= D_19980902)
                .select(col("l_returnflag"), col("l_linestatus"),
                        (col("l_extendedprice")
                         * (lit(1.0) - col("l_discount"))).alias("disc"))
                .group_by(col("l_returnflag"), col("l_linestatus"))
                .agg(F.sum(col("disc")).alias("s"),
                     F.count(lit(1)).alias("c")))

    def exchange_shape(s, df):
        return (df.filter(col("l_discount") >= 0.02)
                .select(col("l_shipdate"), col("l_quantity"))
                .repartition(4, col("l_shipdate")))

    table = make_lineitem(n)
    out = {"rows": n, "queries": {}}
    for qname, build in (("q1_shape", q1_shape),
                         ("exchange_shape", exchange_shape)):
        rec = {}
        for label, fusion in (("fusion_off", "false"), ("fusion_on", "true")):
            conf = dict(base_conf)
            conf["spark.rapids.sql.tpu.fusion.enabled"] = fusion
            KC.clear()
            jax.clear_caches()
            before = KC.stats()
            xla0 = xla_compiles[0]
            s = TpuSession(conf)
            df = s.from_arrow(table)
            t0 = time.time()
            r1 = checksum(build(s, df).collect())
            warmup_s = time.time() - t0
            after_compile = KC.stats()
            xla1 = xla_compiles[0]
            t0 = time.time()
            r2 = checksum(build(s, df).collect())
            steady_s = time.time() - t0
            after = KC.stats()
            rec[label] = {
                "jit_compiles": (after_compile["builds"]
                                 - before["builds"]
                                 + after_compile["stage_compiles"]
                                 - before["stage_compiles"]),
                "xla_compiles": xla1 - xla0,
                "dispatches_warm_run": (after["dispatches"]
                                        - after_compile["dispatches"]),
                # input buffers donated to compiled programs during the
                # warm run (ISSUE 11): each one is an HBM copy the warm
                # dispatch did NOT pay; 0 with fusion off (no stage
                # programs) or donation disabled
                "donated_copies_warm_run": (after["donated_buffers"]
                                            - after_compile[
                                                "donated_buffers"]),
                "warmup_s": round(warmup_s, 3),
                "steady_s": round(steady_s, 4),
                "value": r1,
            }
            assert abs(r1 - r2) <= 1e-6 * max(1.0, abs(r1))
        off, on = rec["fusion_off"], rec["fusion_on"]
        rec["match"] = bool(abs(off["value"] - on["value"])
                            <= 1e-4 * max(1.0, abs(off["value"])))
        # xla_compiles is the ground truth, but if the monitoring event
        # never fired (older jax without the hook) fall back to the
        # exec-layer program count rather than reporting 0/0 = no change
        src = ("xla_compiles" if off["xla_compiles"] or on["xla_compiles"]
               else "jit_compiles")
        rec["compile_reduction"] = round(
            off[src] / max(1, on[src]), 2)
        out["queries"][qname] = rec
    return out


def tracing_microbench() -> dict:
    """Distributed-tracing overhead (the ISSUE-7 <5% acceptance gate):
    the q1 pipeline on fresh sessions with tracing + a file journal ON
    vs OFF (same table, kernels warm after each session's own warmup
    run), plus the live-heartbeat rpc cost measured against a real
    worker process — so 'tracing is cheap' is a recorded artifact, not
    an assertion."""
    import tempfile

    from spark_rapids_tpu.engine import TpuSession

    n = 200_000
    table = make_lineitem(n)

    def measure(conf):
        s = TpuSession({"spark.rapids.sql.variableFloatAgg.enabled":
                        "true", **conf})
        df = s.from_arrow(table)
        checksum(q1(df).collect())          # warmup: compile + caches
        runs = []
        for _ in range(5):
            t0 = time.perf_counter()
            checksum(q1(df).collect())
            runs.append(time.perf_counter() - t0)
        return min(runs)

    off_s = measure({"spark.rapids.sql.tpu.trace.enabled": "false"})
    jdir = tempfile.mkdtemp(prefix="bench_trace_")
    on_s = measure({"spark.rapids.sql.tpu.trace.enabled": "true",
                    "spark.rapids.sql.tpu.metrics.journal.dir": jdir})
    overhead_pct = (on_s - off_s) / off_s * 100.0 if off_s > 0 else 0.0
    out = {"rows": n, "q1_trace_off_s": round(off_s, 4),
           "q1_trace_on_s": round(on_s, 4),
           "overhead_pct": round(overhead_pct, 2),
           # the acceptance gate: tracing must cost <5% on q1
           "gate_ok": bool(overhead_pct < 5.0)}

    # heartbeat cost: round-trip latency of rpc_heartbeat against a live
    # worker process (the monitor polls on DEDICATED connections, so this
    # latency is the whole cost — it never blocks the query path)
    try:
        from spark_rapids_tpu.cluster import ProcCluster
        cluster = ProcCluster(
            1, conf={"spark.rapids.sql.tpu.trace."
                     "heartbeatIntervalMs": "0"}, cpu=True)
        try:
            w = cluster.workers[0]
            w.rpc("heartbeat")              # connection warmup
            t0 = time.perf_counter()
            n_polls = 20
            for _ in range(n_polls):
                hb = w.rpc("heartbeat")
            out["heartbeat_rpc_ms"] = round(
                (time.perf_counter() - t0) / n_polls * 1e3, 3)
            out["heartbeat_fields"] = sorted(hb.keys())
        finally:
            cluster.shutdown()
    except Exception as e:  # the worker probe must never sink the bench
        out["heartbeat_error"] = repr(e)[:200]
    return out


def pressure_microbench(write_artifact: bool = True) -> dict:
    """Memory-budget sweep (the ISSUE-8 acceptance artifact, and the
    BENCH_PRESSURE stage ROADMAP item 4 asks for): the spill-cascade
    slice (partitioned join -> grouped agg -> sort) run at accounted-pool
    budgets of 100/75/50/25% of its measured working set, with the
    memory ledger's breakdown (spill bytes, churn ratio, victim quality,
    retry counts, headroom) recorded per budget — so the data-movement
    scheduler PR has a reproducible baseline to beat.  Also measures the
    ledger's own cost: q1 with the ledger (and a file journal) on vs off
    at MODERATE level, gated <5% like the tracing stage."""
    import shutil
    import tempfile

    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.metrics import names as MN
    from spark_rapids_tpu.metrics.memledger import analyze_shards
    from spark_rapids_tpu.metrics.timeline import load_journal_dir
    from spark_rapids_tpu.plan.logical import col, functions as F, lit

    n = int(os.environ.get("BENCH_PRESSURE_ROWS", 120_000))
    base_conf = {
        "spark.rapids.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.memory.host.spillStorageSize": str(1 << 20),
        "spark.rapids.sql.batchSizeBytes": str(512 << 10),
        "spark.rapids.sql.reader.batchSizeRows": "16384",
        "spark.sql.autoBroadcastJoinThreshold": "-1",
        "spark.rapids.sql.tpu.join.partitioned.threshold": "1",
        "spark.rapids.sql.tpu.shuffle.partitions": "8",
        "spark.rapids.sql.tpu.memoryScanCache.enabled": "false",
    }

    def slice_query(s):
        fact = s.from_pydict({
            "k": [i % 7 for i in range(n)],
            "v": [float(i) for i in range(n)],
            "q": [i % 3 for i in range(n)]})
        dim = s.from_pydict({"k": list(range(7)),
                             "name": [f"g{j}" for j in range(7)]})
        return checksum(
            fact.join(dim, on="k").filter(col("q") < 2)
            .group_by(col("name"))
            .agg(F.sum(col("v")).alias("sv"), F.count(lit(1)).alias("c"))
            .order_by(col("name")).collect())

    def run(pool_bytes=0, jdir=None, extra=None):
        """One measured slice run.  The warmup query shares the session
        (compiles + H2D), so everything reported is a DELTA over the
        timed run only: counter movement, and only the journal files the
        timed query opened — otherwise every breakdown would double-count
        the warmup's spills against one run's time_s."""
        conf = dict(base_conf, **(extra or {}))
        if pool_bytes:
            conf["spark.rapids.memory.tpu.poolSizeBytes"] = str(pool_bytes)
        if jdir:
            conf["spark.rapids.sql.tpu.metrics.journal.dir"] = jdir
        s = TpuSession(conf)
        slice_query(s)                     # warmup: compiles + H2D
        warm_files = set(os.listdir(jdir)) if jdir else set()
        ps_before = dict(s.runtime.pool_stats())
        tot_before = dict(getattr(s, "query_metrics_total", {}) or {})
        t0 = time.perf_counter()
        val = slice_query(s)
        elapsed = time.perf_counter() - t0
        ps_after = s.runtime.pool_stats()
        counters = {k: int(ps_after.get(k, 0)) - int(ps_before.get(k, 0))
                    for k in (MN.OOM_SPILL_RETRIES, MN.OOM_ALLOC_FAILURES,
                              MN.NUM_POLICY_VICTIM_PICKS,
                              MN.NUM_POLICY_VICTIM_OVERRIDES,
                              MN.NUM_POLICY_EARLY_RELEASES,
                              MN.NUM_PROACTIVE_UNSPILLS)}
        tot_after = dict(getattr(s, "query_metrics_total", {}) or {})
        totals = {k: tot_after.get(k, 0) - tot_before.get(k, 0)
                  for k in tot_after}
        new_shards = []
        if jdir:
            fresh = set(os.listdir(jdir)) - warm_files

            def shard_files(label):
                # invert load_journal_dir's labeling: 'driver/query-N'
                # came from query-N.jsonl, a worker label 'exec-K' from
                # shard-exec-K.jsonl (process-lifetime: only counted
                # when the file itself is fresh)
                base = label.rsplit("/", 1)[-1]
                return {base + ".jsonl", "shard-" + base + ".jsonl"}

            new_shards = [sh for sh in load_journal_dir(jdir)
                          if shard_files(sh["label"]) & fresh]
        return elapsed, val, ps_after, counters, totals, new_shards

    # 1. unconstrained run: the measured working set is the 100% budget.
    # The baseline gets a journal dir too, so slowdown_vs_unconstrained
    # isolates BUDGET pressure rather than folding in journal-write cost
    jdir0 = tempfile.mkdtemp(prefix="bench_pressure_base_")
    try:
        el0, val0, ps0, _c0, _t0, _sh0 = run(jdir=jdir0)
    finally:
        shutil.rmtree(jdir0, ignore_errors=True)
    working_set = int(ps0.get("device_peak", 0)) or 1

    def budget_row(pool, prefix, extra=None):
        jdir = tempfile.mkdtemp(prefix=prefix)
        try:
            el, val, _ps, counters, totals, shards = run(pool, jdir,
                                                         extra)
            rep = analyze_shards(shards)
        finally:
            shutil.rmtree(jdir, ignore_errors=True)
        t = rep["totals"]
        row = {
            "pool_bytes": pool,
            "time_s": round(el, 4),
            "slowdown_vs_unconstrained": round(el / el0, 3) if el0 else None,
            "match": bool(abs(val - val0) <= 1e-6 * max(1.0, abs(val0))),
            # ledger-derived breakdown (metrics/memledger.py)
            "spill_bytes": t["spilled_bytes"],
            "respill_bytes": t["respill_bytes"],
            "churn_ratio": rep["churn"]["churn_ratio"],
            "victim_quality": rep["victim_quality"]["quality"],
            "headroom_bytes": rep["headroom"]["bytes"],
            "cascades": len(rep["cascades"]),
            "oom_spills": t["oom_spills"],
            "oom_fails": t["oom_fails"],
            "ledger_events": t["events"],
            # runtime/retry view of the same run (timed-run deltas)
            "oomSpillRetries": counters[MN.OOM_SPILL_RETRIES],
            "oomAllocFailures": counters[MN.OOM_ALLOC_FAILURES],
            "numPolicyVictimPicks": counters[MN.NUM_POLICY_VICTIM_PICKS],
            "numPolicyVictimOverrides":
                counters[MN.NUM_POLICY_VICTIM_OVERRIDES],
            "numPolicyEarlyReleases":
                counters[MN.NUM_POLICY_EARLY_RELEASES],
            "numProactiveUnspills": counters[MN.NUM_PROACTIVE_UNSPILLS],
            "retries": int(sum(totals.get(f"{b}Retries", 0)
                               for b in MN.RETRY_BLOCKS)),
            "splits": int(sum(totals.get(f"{b}Splits", 0)
                              for b in MN.RETRY_BLOCKS)),
        }
        return row, val

    # each budget runs twice — data-movement policy engine ON (the
    # default) and OFF — so the artifact carries the ISSUE-18 acceptance
    # comparison (churn/slowdown deltas, and bit-for-bit row checksums)
    policy_off_conf = {"spark.rapids.sql.tpu.policy.enabled": "false"}
    budgets = {}
    for pct in (100, 75, 50, 25):
        pool = max(1 << 16, working_set * pct // 100)
        row, val_on = budget_row(pool, f"bench_pressure_{pct}_")
        off, val_off = budget_row(pool, f"bench_pressure_{pct}off_",
                                  policy_off_conf)
        row["policy_off"] = {k: off[k] for k in (
            "time_s", "slowdown_vs_unconstrained", "match",
            "spill_bytes", "respill_bytes", "churn_ratio",
            "victim_quality", "cascades", "oomSpillRetries")}
        row["policy_bit_for_bit"] = bool(val_on == val_off)
        budgets[str(pct)] = row

    # 2. ledger overhead gate (<5% on q1 at MODERATE, journal on — the
    # ISSUE-8 twin of the tracing stage's gate)
    table = make_lineitem(200_000)

    def measure_q1(ledger_on):
        jdir = tempfile.mkdtemp(prefix="bench_pressure_ovh_")
        try:
            s = TpuSession({
                "spark.rapids.sql.variableFloatAgg.enabled": "true",
                "spark.rapids.sql.tpu.metrics.journal.dir": jdir,
                "spark.rapids.sql.tpu.memory.ledger.enabled":
                    "true" if ledger_on else "false"})
            df = s.from_arrow(table)
            checksum(q1(df).collect())      # warmup
            runs = []
            for _ in range(5):
                t0 = time.perf_counter()
                checksum(q1(df).collect())
                runs.append(time.perf_counter() - t0)
            return min(runs)
        finally:
            shutil.rmtree(jdir, ignore_errors=True)

    off_s = measure_q1(False)
    on_s = measure_q1(True)
    overhead_pct = (on_s - off_s) / off_s * 100.0 if off_s > 0 else 0.0

    rec = {
        "recorded_unix": int(time.time()),
        "rows": n,
        "working_set_bytes": working_set,
        "unconstrained_time_s": round(el0, 4),
        "conf": {k: v for k, v in base_conf.items()
                 if "variableFloat" not in k},
        "budgets": budgets,
        "ledger_overhead": {
            "q1_ledger_off_s": round(off_s, 4),
            "q1_ledger_on_s": round(on_s, 4),
            "overhead_pct": round(overhead_pct, 2),
            "gate_ok": bool(overhead_pct < 5.0)},
        "note": ("join->agg->sort spill-cascade slice at 25/50/75/100% "
                 "of measured working set; breakdowns reconstructed "
                 "offline from the memory ledger journal "
                 "(python -m spark_rapids_tpu.metrics --memory)"),
    }
    try:
        import jax
        rec["platform"] = jax.devices()[0].platform
    except Exception:  # noqa: BLE001
        rec["platform"] = "unknown"
    if write_artifact:
        try:
            with open(os.path.join(REPO, "BENCH_PRESSURE.json"), "w") as f:
                json.dump(rec, f, indent=1)
        except OSError:
            pass
    return rec


def serve_microbench(write_artifact: bool = True) -> dict:
    """Serving-tier bench (ISSUE 10 acceptance; also BENCH_SERVE.json).

    Part 1 — parameterized plan cache: a q1-shaped query is submitted
    cold (cleared kernel caches), then re-submitted with CHANGED literals
    (date cutoff, price scale).  The variant must ride the plan cache
    (hit counters prove the path) and compile >= 5x fewer XLA programs
    than the cold run — values re-bind into the cached compiled stages.

    Part 2 — mixed workload: 12 short selective queries (literal
    variants, priority 5) race 2 long parquet-scan queries (priority 0)
    through the scheduler at concurrency 1/4/16, all on warm compile
    caches (one untimed warmup round first, so the concurrency deltas
    measure OVERLAP, not compile luck).  Records wall time, throughput,
    p50/p95 latency, p95 queue time, admission stats — plus an OOM-
    injection round at concurrency 4 whose per-query checksums must be
    bit-for-bit identical to the serial round's."""
    import jax
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.plan.logical import col, functions as F, lit
    from spark_rapids_tpu.utils import kernel_cache as KC

    xla_compiles = [0]
    try:
        jax.monitoring.register_event_listener(
            lambda name, **kw: xla_compiles.__setitem__(
                0, xla_compiles[0]
                + (name == "/jax/compilation_cache/"
                           "compile_requests_use_cache")))
    except Exception:
        pass

    n = 300_000
    table = make_lineitem(n)
    base_conf = {"spark.rapids.sql.variableFloatAgg.enabled": "true"}

    def q1_param(df, cutoff, scale):
        disc = col("l_extendedprice") * (lit(scale) - col("l_discount"))
        return (df.filter(col("l_shipdate") <= cutoff)
                .group_by(col("l_returnflag"), col("l_linestatus"))
                .agg(F.sum(col("l_quantity")).alias("sum_qty"),
                     F.sum(disc).alias("sum_disc"),
                     F.avg(col("l_discount")).alias("avg_disc"),
                     F.count(lit(1)).alias("n"))
                .order_by("l_returnflag", "l_linestatus"))

    out = {"rows": n, "single_core": (os.cpu_count() or 1) == 1}

    # ---- part 1: parameterized plan cache ---------------------------------
    KC.clear()
    jax.clear_caches()
    s = TpuSession(base_conf)
    df = s.from_arrow(table)
    variants = [(D_19980902, 1.0), (D_1995, 1.02), (D_1994, 0.98)]
    runs = []
    for i, (cutoff, scale) in enumerate(variants):
        b0, x0, t0 = KC.stats(), xla_compiles[0], time.time()
        val = checksum(s.submit(q1_param(df, cutoff, scale)).collect(300))
        b1, x1 = KC.stats(), xla_compiles[0]
        runs.append({
            "label": "cold" if i == 0 else f"variant{i}",
            "seconds": round(time.time() - t0, 3),
            "xla_compiles": x1 - x0,
            "jit_compiles": (b1["builds"] - b0["builds"]
                             + b1["stage_compiles"] - b0["stage_compiles"]),
            "value": val,
        })
    sched = s.scheduler.stats()
    s.shutdown_serving()
    cold, var1 = runs[0], runs[1]
    src = ("xla_compiles" if cold["xla_compiles"] or var1["xla_compiles"]
           else "jit_compiles")
    out["plan_cache"] = {
        "runs": runs,
        "hits": sched["plan_cache"]["hits"],
        "misses": sched["plan_cache"]["misses"],
        "params_lifted": sched["plan_cache"]["params_lifted"],
        "compile_reduction": round(
            cold[src] / max(1, max(r[src] for r in runs[1:])), 2),
        "warmup_reduction": round(
            cold["seconds"] / max(1e-9, max(r["seconds"]
                                            for r in runs[1:])), 2),
    }

    # ---- part 2: mixed workload at concurrency 1/4/16 ---------------------
    pq_dir = os.path.join("/tmp", f"bench_serve_{n}")
    pq_path = os.path.join(pq_dir, "lineitem.parquet")
    if not os.path.exists(pq_path):
        import pyarrow.parquet as papq
        os.makedirs(pq_dir, exist_ok=True)
        tmp = f"{pq_path}.{os.getpid()}.tmp"
        papq.write_table(table, tmp, compression="snappy")
        os.replace(tmp, pq_path)

    short_variants = [(8300 + 137 * i, 0.01 + 0.005 * (i % 8), 25 + i % 20)
                      for i in range(12)]

    def q_short(df, lo, dmin, qmax):
        return (df.filter((col("l_shipdate") >= lo)
                          & (col("l_discount") >= dmin)
                          & (col("l_quantity") < qmax))
                .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                     .alias("revenue")))

    def run_round(concurrency, inject=None):
        conf = dict(base_conf)
        conf["spark.rapids.sql.tpu.serve.maxConcurrentQueries"] = \
            str(concurrency)
        conf["spark.rapids.sql.concurrentTpuTasks"] = str(concurrency)
        if inject:
            conf["spark.rapids.tpu.test.injectOom"] = inject
        rs = TpuSession(conf)
        rdf = rs.from_arrow(table)
        t0 = time.time()
        futs = [(f"short{i}", rs.submit(q_short(rdf, *v), priority=5))
                for i, v in enumerate(short_variants)]
        futs += [(f"long{j}", rs.submit(q6(rs.read.parquet(pq_path)),
                                        priority=0))
                 for j in range(2)]
        values = {name: checksum(f.collect(600)) for name, f in futs}
        wall = time.time() - t0
        lats = sorted(f.latency_seconds for _n2, f in futs)
        queues = sorted(f.queue_seconds for _n2, f in futs)

        def pct(xs, p):
            return round(xs[min(len(xs) - 1, int(p * len(xs)))], 4)
        stats = rs.scheduler.stats()
        rs.shutdown_serving()
        return {
            "concurrency": concurrency,
            "queries": len(futs),
            "wall_s": round(wall, 3),
            "throughput_qps": round(len(futs) / wall, 3),
            "p50_latency_s": pct(lats, 0.50),
            "p95_latency_s": pct(lats, 0.95),
            "p95_queue_s": pct(queues, 0.95),
            "plan_cache_hits": stats["plan_cache"]["hits"],
            "admitted": stats["admitted"],
            "failed": stats["failed"],
        }, values

    # serial BLOCKING baseline: the same mix through collect() loops on a
    # fresh session with cleared caches — what "one query owns the
    # runtime" costs a second user: every literal variant pays its own
    # baked-literal trace+compile, and nothing overlaps.  This is the
    # "serial execution of the same query mix" the acceptance criterion
    # compares concurrency-4 against.
    KC.clear()
    jax.clear_caches()
    sb = TpuSession(base_conf)
    sdf = sb.from_arrow(table)
    t0 = time.time()
    serial_values = {}
    for i, v in enumerate(short_variants):
        serial_values[f"short{i}"] = checksum(q_short(sdf, *v).collect())
    for j in range(2):
        serial_values[f"long{j}"] = checksum(
            q6(sb.read.parquet(pq_path)).collect())
    serial_wall = time.time() - t0
    n_mix = len(serial_values)
    serial_blocking = {"wall_s": round(serial_wall, 3),
                       "queries": n_mix,
                       "throughput_qps": round(n_mix / serial_wall, 3)}

    run_round(4)  # warm the parameterized programs, untimed
    rounds = {"serial_blocking": serial_blocking}
    baseline_values = None
    mismatches = 0
    for c in (1, 4, 16):
        rec, values = run_round(c)
        if baseline_values is None:
            baseline_values = values
        else:
            for k, v in values.items():
                if abs(v - baseline_values[k]) > 1e-6 * max(1.0, abs(v)):
                    mismatches += 1
        rounds[f"c{c}"] = rec
    rec, values = run_round(4, inject="5x2,17x2,29x2,41x2")
    for k, v in values.items():
        if abs(v - baseline_values[k]) > 1e-6 * max(1.0, abs(v)):
            mismatches += 1
    rec["injectOom"] = "5x2,17x2,29x2,41x2"
    rounds["c4_oom"] = rec
    # the scheduler rounds must agree with the BLOCKING run too (same
    # queries, parameterized vs baked execution paths)
    for k, v in baseline_values.items():
        if abs(v - serial_values[k]) > 1e-6 * max(1.0, abs(v)):
            mismatches += 1
    out["mixed_workload"] = rounds
    out["mismatches"] = mismatches

    # ---- part 3: SLO-aware preemption (ISSUE 19) --------------------------
    # A latency class (priority 10, selective short queries) arrives
    # while a long priority-0 background scan holds the single device
    # semaphore slot.  Preemption OFF: each short query waits for the
    # whole remaining background run.  Preemption ON: the background
    # query suspends at its next stage boundary (parks its buffers,
    # releases the semaphore) and resumes afterwards — the latency-class
    # p99 is the headline, the preempt SLO phase (suspend->resume
    # seconds the victim paid) is the cost side, and every background
    # checksum must stay bit-for-bit identical to the unpreempted run.
    def q_bg(df):
        return (df.filter(col("l_quantity") > lit(0.0))
                .select((col("l_extendedprice")
                         * (lit(1.0) - col("l_discount"))).alias("v"),
                        (col("l_quantity") * lit(3.0)).alias("w"),
                        col("l_shipdate")))

    def preempt_round(enabled: bool):
        conf = dict(base_conf)
        conf.update({
            "spark.rapids.sql.tpu.serve.maxConcurrentQueries": "2",
            "spark.rapids.sql.concurrentTpuTasks": "1",
            "spark.rapids.sql.reader.batchSizeRows": "4000",
            "spark.rapids.sql.tpu.serve.preemption.enabled":
                "true" if enabled else "false",
        })
        ps = TpuSession(conf)
        pdf = ps.from_arrow(table)
        # warm both shapes (untimed): the round measures CONTENTION, not
        # compile luck
        checksum(ps.submit(q_bg(pdf)).collect(600))
        checksum(ps.submit(q_short(pdf, *short_variants[0])).collect(600))
        bg_vals = []
        f_bg = ps.submit(q_bg(pdf), priority=0)
        lats = []
        for i in range(10):
            if f_bg.done():
                bg_vals.append(checksum(f_bg.collect(600)))
                f_bg = ps.submit(q_bg(pdf), priority=0)
            f = ps.submit(q_short(pdf, *short_variants[i % 12]),
                          priority=10)
            f.result(600)
            lats.append(f.latency_seconds)
            time.sleep(0.02)
        bg_vals.append(checksum(f_bg.collect(600)))
        lats.sort()

        def pct(p):
            return round(lats[min(len(lats) - 1, int(p * len(lats)))], 4)
        st = ps.scheduler.stats()
        slo = ps.scheduler.slo.report()
        ps.shutdown_serving()
        rec = {
            "enabled": enabled,
            "latency_queries": len(lats),
            "p50_latency_s": pct(0.50),
            "p95_latency_s": pct(0.95),
            "p99_latency_s": pct(0.99),
            "bg_runs": len(bg_vals),
            "preemptions": st["lifecycle"]["preemptions"],
            "preemption_resumes": st["lifecycle"]["preemption_resumes"],
        }
        pre = slo.get("preempt", {}).get("10", None) \
            or slo.get("preempt", {}).get("0", None)
        if pre:
            # suspend->resume latency the victims paid (SLO phase)
            rec["preempt_p50_s"] = pre["p50_s"]
            rec["preempt_p99_s"] = pre["p99_s"]
        return rec, bg_vals

    try:
        rec_off, bg_off = preempt_round(False)
        rec_on, bg_on = preempt_round(True)
        bg_mismatch = sum(1 for v in bg_on + bg_off
                          if abs(v - bg_on[0]) > 1e-6 * max(1.0, abs(v)))
        # shed/cancel accounting round: expired deadlines shed at
        # admission, a cancel of the queued second query resolves it
        # without it ever costing a worker (maxConcurrentQueries=1 keeps
        # it deterministically queued behind the first)
        cconf = dict(base_conf)
        cconf["spark.rapids.sql.tpu.serve.maxConcurrentQueries"] = "1"
        cconf["spark.rapids.sql.reader.batchSizeRows"] = "4000"
        cs = TpuSession(cconf)
        cdf = cs.from_arrow(table)
        f1 = cs.submit(q_bg(cdf))
        fc = cs.submit(q_bg(cdf))
        fc.cancel("bench accounting round")
        fc.exception(600)
        f1.result(600)
        shed_futs = [cs.submit(q_short(cdf, *short_variants[i]),
                               deadline_ms=0.001) for i in range(4)]
        for f in shed_futs:
            f.exception(600)
        acct = cs.scheduler.stats()["lifecycle"]
        cs.shutdown_serving()
        out["preemption"] = {
            "off": rec_off,
            "on": rec_on,
            "p99_improvement": round(
                rec_off["p99_latency_s"]
                / max(1e-9, rec_on["p99_latency_s"]), 3),
            "bg_checksum_mismatches": bg_mismatch,
            "sheds": acct["deadline_sheds"],
            "cancels": acct["cancelled"],
        }
    except Exception as e:  # noqa: BLE001 — bench stage must not abort
        out["preemption"] = {"error": repr(e)[:200]}
    out["speedup_c4_vs_serial"] = round(
        rounds["c4"]["throughput_qps"]
        / max(1e-9, serial_blocking["throughput_qps"]), 3)
    out["speedup_c16_vs_serial"] = round(
        rounds["c16"]["throughput_qps"]
        / max(1e-9, serial_blocking["throughput_qps"]), 3)
    # isolated concurrency effect on warm caches (on a single-core host
    # expect ~1.0: there is no second core for overlapped work)
    out["speedup_c4_vs_c1_warm"] = round(
        rounds["c4"]["throughput_qps"]
        / max(1e-9, rounds["c1"]["throughput_qps"]), 3)
    try:
        out["platform"] = jax.devices()[0].platform
    except Exception:  # noqa: BLE001
        out["platform"] = "unknown"
    if write_artifact:
        try:
            with open(os.path.join(REPO, "BENCH_SERVE.json"), "w") as f:
                json.dump(out, f, indent=1)
        except OSError:
            pass
    return out


def streaming_microbench(write_artifact: bool = True) -> dict:
    """Streaming micro-batch bench (ISSUE 20 acceptance artifact:
    BENCH_STREAM.json).

    For several epoch batch sizes: a grouped sum/avg/count query runs
    incrementally over an in-memory append stream (reader batch rows
    pinned to the epoch size — the bit-for-bit alignment contract).
    After a 3-epoch warm-up, the sweep records epochs/s, p50/p95 epoch
    latency, and the warm-epoch compile count, which must be ZERO (every
    epoch after the first is a plan-cache hit replaying compiled
    stages).  At the largest stream length it also times one full batch
    re-query over everything seen so far: the incremental epoch must
    beat it >= 3x (the speedup grows with stream length — that is the
    point of keeping state resident), and the incremental result's
    checksum must match the batch oracle's."""
    import jax
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.engine import DataFrame, TpuSession
    from spark_rapids_tpu.plan import logical as L
    from spark_rapids_tpu.plan.logical import col, functions as F, lit
    from spark_rapids_tpu.streaming import MemoryStream, StreamingQuery
    from spark_rapids_tpu.types import LongType, DoubleType, Schema, \
        StructField
    from spark_rapids_tpu.utils import kernel_cache as KC

    xla_compiles = [0]
    try:
        jax.monitoring.register_event_listener(
            lambda name, **kw: xla_compiles.__setitem__(
                0, xla_compiles[0]
                + (name == "/jax/compilation_cache/"
                           "compile_requests_use_cache")))
    except Exception:
        pass

    schema = Schema([StructField("k", LongType),
                     StructField("v", DoubleType)])
    rng = np.random.default_rng(42)

    def make_chunk(rows):
        return pa.table({
            "k": pa.array(rng.integers(0, 64, rows), type=pa.int64()),
            "v": pa.array(rng.random(rows) * 100.0, type=pa.float64())})

    def build(df):
        return df.group_by(col("k")).agg(
            F.sum(col("v")).alias("sv"), F.avg(col("v")).alias("av"),
            F.count(lit(1)).alias("c"))

    WARMUP = 3
    out = {"single_core": (os.cpu_count() or 1) == 1, "batch_sizes": []}
    for batch_rows, n_epochs in ((2_000, 24), (8_000, 24), (32_000, 24)):
        conf = {
            "spark.rapids.sql.variableFloatAgg.enabled": "true",
            "spark.rapids.sql.reader.batchSizeRows": str(batch_rows),
            "spark.rapids.sql.tpu.streaming.maxBatchRows": str(batch_rows),
        }
        s = TpuSession(conf)
        src = MemoryStream(schema, name=f"bench{batch_rows}")
        q = StreamingQuery(s, src, build, name=f"bench{batch_rows}")
        for _ in range(WARMUP):
            src.append(make_chunk(batch_rows))
            q.trigger_once()
        b0, x0 = KC.stats(), xla_compiles[0]
        times = []
        for _ in range(n_epochs - WARMUP):
            src.append(make_chunk(batch_rows))
            t0 = time.time()
            q.trigger_once()
            times.append(time.time() - t0)
        b1, x1 = KC.stats(), xla_compiles[0]
        times.sort()

        def pct(p):
            return round(times[min(len(times) - 1,
                                   int(p * len(times)))], 5)

        rec = {
            "epoch_rows": batch_rows,
            "epochs": n_epochs,
            "warm_epochs": len(times),
            "epochs_per_s": round(len(times) / max(1e-9, sum(times)), 2),
            "p50_epoch_s": pct(0.50),
            "p95_epoch_s": pct(0.95),
            "rows_per_s": round(batch_rows * len(times)
                                / max(1e-9, sum(times)), 1),
            "warm_compiles": (b1["builds"] - b0["builds"]
                              + b1["stage_compiles"]
                              - b0["stage_compiles"]),
            "warm_xla_compiles": x1 - x0,
        }
        if batch_rows == 32_000:
            # incremental-vs-full-requery at the longest stream: one
            # more epoch incrementally vs the whole history from scratch
            src.append(make_chunk(batch_rows))
            t0 = time.time()
            q.trigger_once()
            t_inc = time.time() - t0
            full_df = build(DataFrame(s, L.LogicalScan(
                src.rows_between(0, src.latest_offset()), schema,
                "memory")))
            t_full = None
            for _ in range(2):  # first run may compile the final concat
                t1 = time.time()
                full = full_df.to_arrow()
                t_full = time.time() - t1
            inc = q.result()
            cks = {
                "incremental": round(checksum(
                    sorted(zip(*(inc.column(i).to_pylist()
                                 for i in range(inc.num_columns))))), 4),
                "batch_oracle": round(checksum(
                    sorted(zip(*(full.column(i).to_pylist()
                                 for i in range(full.num_columns))))), 4),
            }
            rec["requery"] = {
                "stream_rows": src.latest_offset(),
                "incremental_epoch_s": round(t_inc, 5),
                "full_requery_s": round(t_full, 5),
                "speedup": round(t_full / max(1e-9, t_inc), 2),
                "checksum_match": abs(cks["incremental"]
                                      - cks["batch_oracle"])
                <= 1e-6 * max(1.0, abs(cks["batch_oracle"])),
                **cks,
            }
        out["batch_sizes"].append(rec)
        q.stop()
        s.shutdown_serving()
    out["warm_compiles_total"] = sum(r["warm_compiles"]
                                     for r in out["batch_sizes"])
    out["zero_warm_compiles"] = out["warm_compiles_total"] == 0
    last = out["batch_sizes"][-1].get("requery", {})
    out["incremental_speedup"] = last.get("speedup")
    out["checksum_match"] = last.get("checksum_match")
    try:
        out["platform"] = jax.devices()[0].platform
    except Exception:  # noqa: BLE001
        out["platform"] = "unknown"
    if write_artifact:
        try:
            with open(os.path.join(REPO, "BENCH_STREAM.json"), "w") as f:
                json.dump(out, f, indent=1)
        except OSError:
            pass
    return out


def chaos_microbench(write_artifact: bool = True) -> dict:
    """Chaos/recovery bench (ISSUE 15 acceptance artifact:
    BENCH_CHAOS.json).  On a 3-worker CPU ProcCluster running the
    representative grouped-aggregation slice:

      * recovery-latency rows at 0 / 1 / 2 injected mid-task kills per
        query (injectCrash armed per round over rpc_inject_faults, so
        replacements spawn healthy), each round verified EXACTLY equal
        to the fault-free result (int64 aggregation: order-invariant);
      * a measured speculation win on an injected-delay straggler: the
        speculative copy finishes first (wall clock well under the
        injected delay), the result is identical, and
        numSpeculationWins moves.

    Workers are always forced-CPU subprocesses (a chip belongs to one
    process, and the device child holds it; driver side only plans and
    compares)."""
    from spark_rapids_tpu.cluster import ProcCluster
    from spark_rapids_tpu.engine import DataFrame, TpuSession
    from spark_rapids_tpu.plan import logical as PL
    from spark_rapids_tpu.plan.logical import col, functions as F

    import pyarrow as pa

    rows = int(os.environ.get("BENCH_CHAOS_ROWS", 6000))
    n_workers = 3
    delay_ms = 8000
    session = TpuSession()
    table = pa.table({"k": pa.array([i % 32 for i in range(rows)],
                                    pa.int64()),
                      "v": pa.array([5 * i + 3 for i in range(rows)],
                                    pa.int64())})
    step = (rows + n_workers - 1) // n_workers
    map_plans = [session.from_arrow(table.slice(i * step, step)).plan
                 for i in range(n_workers)]
    map_schema = DataFrame(session, map_plans[0]).schema
    reduce_plan = (DataFrame(session, PL.LogicalPlaceholder(map_schema))
                   .group_by(col("k"))
                   .agg(F.sum(col("v")).alias("sv"),
                        F.count(col("v")).alias("c"))).plan
    out = {"rows": rows, "workers": n_workers, "kill_rounds": []}
    cluster = ProcCluster(
        n_workers,
        conf={"spark.rapids.sql.tpu.task.timeoutMs": "30000",
              "spark.rapids.sql.tpu.task.retryBackoffMs": "50",
              "spark.rapids.sql.tpu.task.maxBackoffMs": "500",
              "spark.rapids.shuffle.retry.backoffBaseMs": "5",
              "spark.rapids.sql.tpu.trace.heartbeatIntervalMs": "200"},
        cpu=True, max_task_retries=3)
    try:
        def run_once():
            t0 = time.perf_counter()
            res, _stats = cluster.run_map_reduce(map_plans, ["k"],
                                                 2 * n_workers,
                                                 reduce_plan)
            dt = time.perf_counter() - t0
            return {k: (sv, c) for k, sv, c in
                    zip(res["k"].to_pylist(), res["sv"].to_pylist(),
                        res["c"].to_pylist())}, dt

        oracle, _warm = run_once()   # warm compile caches
        _, clean_s = run_once()      # steady-state fault-free latency
        out["clean_s"] = round(clean_s, 3)
        for kills in (0, 1, 2):
            for w in cluster.workers:
                w.rpc("inject_faults")  # disarm
            for w in cluster.workers[:kills]:
                w.rpc("inject_faults", crash="map@1")
            retries0 = cluster.task_retries
            got, dt = run_once()
            out["kill_rounds"].append({
                "kills": kills,
                "seconds": round(dt, 3),
                "recovery_latency_s": round(max(0.0, dt - clean_s), 3),
                "replacements": cluster.task_retries - retries0,
                "bit_for_bit": got == oracle})
        # speculation win on an injected-delay straggler
        for w in cluster.workers:
            w.rpc("inject_faults")
        cluster.workers[1].rpc("inject_faults",
                               delay=f"reduce:{delay_ms}")
        wins0, spec0 = cluster.speculation_wins, cluster.speculative_tasks
        got, dt = run_once()
        out["speculation"] = {
            "injected_delay_s": delay_ms / 1e3,
            "seconds": round(dt, 3),
            "beat_the_straggler": bool(dt < delay_ms / 1e3),
            "speculative_tasks": cluster.speculative_tasks - spec0,
            "numSpeculationWins": cluster.speculation_wins - wins0,
            "bit_for_bit": got == oracle}
        out["recovery"] = {
            "task_retries": cluster.task_retries,
            "evicted_workers": cluster.evicted_workers,
            "abandoned_tasks": cluster.abandoned_tasks,
            "worker_shrinks": cluster.worker_shrinks,
            "driver_counters": {
                k: v for k, v in sorted(
                    cluster._transport.counters.items())
                if k.startswith("task_retries_")
                or k == "worker_shrinks"}}
        out["ok"] = bool(
            all(r["bit_for_bit"] for r in out["kill_rounds"])
            and out["speculation"]["bit_for_bit"]
            and out["speculation"]["numSpeculationWins"] >= 1)
    finally:
        cluster.shutdown()
    if write_artifact:
        try:
            with open(os.path.join(REPO, "BENCH_CHAOS.json"), "w") as f:
                json.dump(out, f, indent=1)
        except OSError:
            pass
    return out


def profile_microbench(write_artifact: bool = True) -> dict:
    """Roofline-attribution capture (ISSUE 13 acceptance artifact:
    BENCH_PROFILE.json).  Runs the representative query set (q1 grouped
    agg, q6 selective agg) with a journal, captures each query's
    roofline ledger — per-operator declared bytes per resource,
    estimated/HLO flops, measured span seconds, the named bottleneck
    resource, achieved-vs-peak utilization — plus a serving-tier round
    that populates the per-priority SLO phase histograms, and measures
    the profiler's own overhead (cost accounting + ledger build ON vs
    the costAccounting kill switch, same MODERATE level, <5% gate).
    scripts/profile_regression.py diffs this artifact against the
    checked-in BASELINE_PROFILE.json in CI."""
    import shutil
    import tempfile

    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.metrics import roofline as RL
    from spark_rapids_tpu.plan.logical import col, functions as F

    n = int(os.environ.get("BENCH_PROFILE_ROWS", 200_000))
    table = make_lineitem(n)
    base_conf = {"spark.rapids.sql.variableFloatAgg.enabled": "true"}
    peaks = None
    out = {"rows": n, "queries": {}}

    def run_q1(s):
        return checksum(q1(s.from_arrow(table)).collect())

    def run_q6(s):
        return checksum(q6(s.from_arrow(table)).collect())

    nj = n // 4

    def run_join(s):
        # exchange + partitioned join + grouped agg + sort: the shape
        # that exercises the wire/d2h/link declarations q1/q6 cannot
        fact = s.from_pydict({
            "k": [i % 7 for i in range(nj)],
            "v": [float(i) for i in range(nj)],
            "q": [i % 3 for i in range(nj)]})
        dim = s.from_pydict({"k": list(range(7)),
                             "name": [f"g{j}" for j in range(7)]})
        return checksum(
            fact.join(dim, on="k").filter(col("q") < 2)
            .group_by(col("name"))
            .agg(F.sum(col("v")).alias("sv"))
            .order_by(col("name")).collect())

    join_conf = {
        "spark.sql.autoBroadcastJoinThreshold": "-1",
        "spark.rapids.sql.tpu.join.partitioned.threshold": "1",
        "spark.rapids.sql.tpu.shuffle.partitions": "4",
    }

    # ---- per-query roofline ledgers ---------------------------------------
    for qname, run_fn, extra in (("q1", run_q1, {}), ("q6", run_q6, {}),
                                 ("join_slice", run_join, join_conf)):
        jdir = tempfile.mkdtemp(prefix=f"bench_profile_{qname}_")
        try:
            s = TpuSession({**base_conf, **extra,
                            "spark.rapids.sql.tpu.metrics.journal.dir":
                            jdir})
            run_fn(s)                               # warm: compiles + H2D
            t0 = time.perf_counter()
            val = run_fn(s)
            elapsed = time.perf_counter() - t0
            qe = s.last_execution
            if peaks is None:
                peaks = RL.platform_peaks(conf=s.conf)
            ledger = qe.roofline_ledger(peaks)
            out["queries"][qname] = {
                "time_s": round(elapsed, 4),
                "value": val,
                "nodes": len(ledger),
                # the acceptance criterion: every plan node names a
                # bottleneck resource ('host' = declared orchestration-
                # bound, still a named attribution)
                "all_nodes_attributed": all(
                    r["bottleneck"] for r in ledger),
                "summary": RL.summarize(ledger),
                "ledger": ledger,
            }
        finally:
            shutil.rmtree(jdir, ignore_errors=True)
    out["peaks"] = peaks

    # ---- profiler overhead gate (<5% on q1, min-of-5, same level) ---------
    def measure_q1(conf):
        s = TpuSession({**base_conf, **conf})
        df = s.from_arrow(table)
        checksum(q1(df).collect())
        runs = []
        for _ in range(5):
            t0 = time.perf_counter()
            checksum(q1(df).collect())
            runs.append(time.perf_counter() - t0)
        return min(runs)

    off_s = measure_q1({
        "spark.rapids.sql.tpu.roofline.costAccounting.enabled": "false",
        "spark.rapids.sql.tpu.roofline.enabled": "false"})
    on_s = measure_q1({})
    overhead_pct = (on_s - off_s) / off_s * 100.0 if off_s > 0 else 0.0
    out["profiler_overhead"] = {
        "q1_cost_off_s": round(off_s, 4),
        "q1_cost_on_s": round(on_s, 4),
        "overhead_pct": round(overhead_pct, 2),
        "gate_ok": bool(overhead_pct < 5.0),
    }

    # ---- serving SLO phase histograms (per priority class) ----------------
    s = TpuSession(base_conf)
    df = s.from_arrow(table)
    futs = []
    for i in range(6):
        qv = q6(df) if i % 2 else \
            df.filter(col("l_discount") >= 0.01 * (i + 1)).agg(
                F.sum(col("l_extendedprice")).alias("r"))
        futs.append(s.submit(qv, priority=5 if i % 2 else 0))
    for f in futs:
        f.result(300)
    sched = s.scheduler
    out["slo"] = sched.stats()["slo"]
    out["fairness"] = sched.fairness_snapshot()
    s.shutdown_serving()
    try:
        import jax
        out["platform"] = jax.devices()[0].platform
    except Exception:  # noqa: BLE001
        out["platform"] = "unknown"
    out["recorded_unix"] = int(time.time())
    if write_artifact:
        try:
            with open(os.path.join(REPO, "BENCH_PROFILE.json"), "w") as f:
                json.dump(out, f, indent=1)
        except OSError:
            pass
    return out


# --------------------------------------------------------------------------
# multichip: mesh-vs-socket exchange tiers per device count (ISSUE 14)
# --------------------------------------------------------------------------

MULTICHIP_DEVICE_COUNTS = (2, 4, 8)


def multichip_measure(n_devices: int, rows: int = 1 << 17,
                      runs: int = 4, parity: bool = True) -> dict:
    """In-process mesh-vs-socket exchange measurement (the
    --multichip-child entry calls this AFTER provisioning `n_devices`
    virtual CPU devices; scripts/ci.sh's dryrun reuses it at a smaller
    size).  One generic hash exchange over the same table on both tiers:

      * MESH tier: `TpuShuffleExchangeExec` lowered to jitted shard_map
        collectives (shuffle/mesh_exchange.py) — materialize + full
        per-partition read, everything device-resident;
      * SOCKET tier: the kill-switched exchange (device catalog write)
        plus the production cross-host read — every partition's buffers
        served by the env's real ShuffleServer over a REAL TCP loopback
        socket (shuffle/net.py bounce/chunk path, the BENCH_WIRE wire)
        and re-adopted H2D.  This is the D2H -> wire -> H2D tax the
        mesh tier exists to eliminate.

    Reports per-tier effective throughput over the exchange's LOGICAL
    bytes (the codec-invariant map-statistics figure, identical across
    tiers by construction — asserted), warm-run compiled-program
    dispatch/compile counts for the mesh tier, checksum mismatches
    between the tiers' partition contents, and (parity=True) q1/join
    -slice bit-for-bit checks across mesh / kill-switch / mesh-less
    sessions."""
    import jax

    from spark_rapids_tpu import config as C  # noqa: F401 (conf keys)
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.mem.buffer import host_to_batch
    from spark_rapids_tpu.mem.runtime import TpuRuntime
    from spark_rapids_tpu.plan.logical import col
    from spark_rapids_tpu.shuffle.manager import get_shuffle_env
    from spark_rapids_tpu.shuffle.net import (ShuffleSocketServer,
                                              SocketTransport)
    from spark_rapids_tpu.utils import kernel_cache as KC

    # wide rows (one int64 key + 12 float64 payload columns, ~118
    # logical B/row): the exchange tiers differ in how they MOVE bytes,
    # and narrow rows would let the shared per-row partition-id compute
    # dominate both tiers on a small host
    table = {"k": [(i * 2654435761) % (1 << 31) for i in range(rows)]}
    for j in range(12):
        table[f"v{j}"] = [float(i + j) * 0.5 for i in range(rows)]

    def find_exchange(node):
        if isinstance(node, TpuShuffleExchangeExec):
            return node
        for c in node.children:
            r = find_exchange(c)
            if r is not None:
                return r
        return None

    def tier_setup(ici: bool):
        conf = {"spark.rapids.sql.tpu.mesh.devices": str(n_devices),
                "spark.rapids.sql.tpu.shuffle.ici.enabled":
                    "true" if ici else "false"}
        s = TpuSession(conf)
        return s, TpuRuntime(s.conf)

    def fresh_exchange(s, rt):
        # fresh plan instance per run (an exchange caches its handle),
        # SAME session/runtime so the scan cache and kernel caches warm
        # across runs and the measurement is the exchange, not warmup
        df = s.from_pydict(table).repartition(n_devices, col("k"))
        ex = find_exchange(df.physical_plan())
        return ex, ExecContext(conf=s.conf, runtime=rt)

    def drain_seconds(s, rt):
        ex, ctx = fresh_exchange(s, rt)
        t0 = time.time()
        batches = [b for b in ex.children[0].execute(ctx)]
        jax.block_until_ready([c.data for b in batches
                               for c in b.columns])
        return time.time() - t0

    def checksum_parts(parts_by_p):
        total_rows = 0
        acc = 0.0
        for p in sorted(parts_by_p):
            for tb in parts_by_p[p]:
                total_rows += tb.num_rows
                for j in range(tb.num_columns):
                    acc += float((p + 1)) * sum(
                        v for v in tb.column(j).to_pylist()
                        if v is not None)
        return total_rows, round(acc, 3)

    # ---- mesh tier ----------------------------------------------------
    mesh_sums = None
    logical_bytes = 0
    mesh_t = []
    dispatches_warm = compiles_warm = 0
    s, rt = tier_setup(True)
    for r in range(runs):
        ex, ctx = fresh_exchange(s, rt)
        before = KC.stats()
        t0 = time.time()
        h = ex.materialize(ctx)
        parts = {}
        for p in range(h.num_partitions):
            subs = h.fetch(p)
            jax.block_until_ready([c.data for b in subs
                                   for c in b.columns])
            parts[p] = subs
        mesh_t.append(time.time() - t0)
        after = KC.stats()
        if r == runs - 1:  # warm run: caches populated by earlier runs
            dispatches_warm = after["dispatches"] - before["dispatches"]
            compiles_warm = (after["stage_compiles"]
                             - before["stage_compiles"])
            logical_bytes = h.stats().total_bytes
            mesh_sums = checksum_parts(
                {p: [b.to_arrow() for b in subs]
                 for p, subs in parts.items()})
        assert getattr(h, "is_mesh", False), "mesh tier never lowered"
        h.release()
    mesh_drain = min(drain_seconds(s, rt) for _ in range(2))

    # ---- socket tier --------------------------------------------------
    sock_t = []
    sock_sums = None
    sock_bytes = 0
    s, rt = tier_setup(False)
    for r in range(runs):
        ex, ctx = fresh_exchange(s, rt)
        env = get_shuffle_env(ctx.runtime, ctx.conf)
        # PRODUCTION-default transport geometry (8MB bounce pool, 1MB
        # chunks, conf-registry defaults) over a real TCP loopback —
        # the same wire BENCH_WIRE measures
        server_tp = SocketTransport()
        server = ShuffleSocketServer(server_tp, env.server)
        client_tp = SocketTransport()
        client_tp.set_peers({"peer": ("127.0.0.1", server.address[1])})
        client = client_tp.make_client("peer")
        try:
            t0 = time.time()
            h = ex.materialize(ctx)
            parts = {}
            for p in range(h.num_partitions):
                got = []
                for block in env.catalog.blocks_for_reduce(h.sid, p):
                    for bid in env.catalog.buffers_for(block):
                        leaves, meta = client.fetch_buffer(bid)
                        batch = host_to_batch(list(leaves), meta)
                        jax.block_until_ready(
                            [c.data for c in batch.columns])
                        got.append(batch)
                parts[p] = got
            sock_t.append(time.time() - t0)
            if r == runs - 1:
                sock_bytes = h.stats().total_bytes
                sock_sums = checksum_parts(
                    {p: [b.to_arrow() for b in subs]
                     for p, subs in parts.items()})
            h.release()
        finally:
            server.close()
            client_tp.shutdown()
            server_tp.shutdown()
    sock_drain = min(drain_seconds(s, rt) for _ in range(2))

    assert logical_bytes == sock_bytes, (logical_bytes, sock_bytes)
    mismatches = 0 if mesh_sums == sock_sums else 1

    # ---- q1/join-slice parity across tiers ----------------------------
    q1_match = join_match = None
    if parity:
        def q1_like(s):
            from spark_rapids_tpu.plan.logical import functions as F
            n = 20000
            df = s.from_pydict(
                {"k": [i % 5 for i in range(n)],
                 "q": [float(i % 50) for i in range(n)],
                 "p": [float(i % 90) * 0.01 for i in range(n)]})
            return (df.repartition(4, col("k"))
                    .filter(col("p") < 0.7)
                    .group_by("k")
                    .agg(F.sum(col("q")).alias("sq"),
                         F.count(col("q")).alias("c"))
                    .order_by(col("k")))

        def join_slice(s):
            from spark_rapids_tpu.plan.logical import functions as F
            n = 12000
            left = s.from_pydict(
                {"k": [i % 40 for i in range(n)],
                 "v": [float(i % 17) for i in range(n)]})
            dim = s.from_pydict(
                {"k": list(range(40)),
                 "name": [f"g{i}" for i in range(40)]})
            return (left.repartition(4)
                    .join(dim, on="k")
                    .group_by("name")
                    .agg(F.sum(col("v")).alias("sv"))
                    .order_by(col("name")))

        def across_tiers(q):
            base = {"spark.rapids.sql.variableFloatAgg.enabled": "true"}
            mesh_conf = {**base, "spark.rapids.sql.tpu.mesh.devices":
                         str(n_devices)}
            got = [q(TpuSession(c)).collect() for c in (
                mesh_conf,
                {**mesh_conf,
                 "spark.rapids.sql.tpu.shuffle.ici.enabled": "false"},
                base)]
            return got[0] == got[1] == got[2]

        q1_match = across_tiers(q1_like)
        join_match = across_tiers(join_slice)

    # effective EXCHANGE throughput: both tiers consume the identical
    # child (drained from the same warm scan cache) — subtracting the
    # separately-measured drain isolates what the tiers actually differ
    # on (partition + move + serve).  Raw end-to-end times reported too.
    mesh_best = min(mesh_t)
    sock_best = min(sock_t)
    mesh_ex = max(mesh_best - mesh_drain, 1e-6)
    sock_ex = max(sock_best - sock_drain, 1e-6)
    return {"n_devices": n_devices, "rows": rows,
            "logical_mb": round(logical_bytes / 1e6, 2),
            "mesh_s": round(mesh_best, 4),
            "socket_s": round(sock_best, 4),
            "drain_s": round(min(mesh_drain, sock_drain), 4),
            "mesh_exchange_gb_s": round(logical_bytes / mesh_ex / 1e9,
                                        3),
            "socket_exchange_gb_s": round(logical_bytes / sock_ex / 1e9,
                                          3),
            "ratio": round(sock_ex / mesh_ex, 2),
            "ratio_end_to_end": round(sock_best / mesh_best, 2),
            "dispatches_per_exchange_warm": dispatches_warm,
            "compiles_warm_run": compiles_warm,
            "checksum_mismatches": mismatches,
            "q1_match": q1_match, "join_match": join_match}


def multichip_child(n_devices: int) -> None:
    """`bench.py --multichip-child=N`: self-provision N virtual CPU
    devices (device count latches at backend init, hence one process per
    count) and print ONE JSON row."""
    from spark_rapids_tpu.utils.cpu_backend import force_cpu_backend
    force_cpu_backend(n_devices=n_devices)
    import jax
    os.environ.setdefault("JAX_ENABLE_X64", "1")
    jax.config.update("jax_enable_x64", True)
    # parity queries are compile-heavy: run them once, in the widest
    # (8-device) child — the ratio rows stay cheap for every count
    row = multichip_measure(n_devices, parity=(n_devices == 8))
    print(json.dumps(row), flush=True)


def multichip_microbench(write_artifact: bool = True) -> dict:
    """Per-device-count exchange tiers (also `python bench.py
    --multichip`): one forced-CPU child per device count in
    MULTICHIP_DEVICE_COUNTS (XLA's host-platform device count latches at
    backend init), rows collected into MULTICHIP.json — REAL rows
    (throughput, ratio, warm dispatch/compile counts, checksum parity)
    replacing the ok-flag-only MULTICHIP_r*.json records."""
    rows = []
    for n in MULTICHIP_DEVICE_COUNTS:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # the child sets its own device count
        try:
            out = subprocess.run(
                [sys.executable, "-u", os.path.abspath(__file__),
                 f"--multichip-child={n}"],
                capture_output=True, text=True, timeout=280, env=env)
            line = out.stdout.strip().splitlines()[-1] if \
                out.stdout.strip() else ""
            rows.append(json.loads(line) if line.startswith("{") else
                        {"n_devices": n, "error":
                         (out.stderr or "no output")[-300:]})
        except (subprocess.TimeoutExpired, ValueError) as e:
            rows.append({"n_devices": n, "error": repr(e)[:300]})
    ok_rows = [r for r in rows if "error" not in r]
    result = {
        "rows": rows,
        "ratio_max_devices": (ok_rows[-1]["ratio"] if ok_rows else None),
        "checksum_mismatches": sum(r.get("checksum_mismatches", 0)
                                   for r in ok_rows),
        "q1_match": next((r["q1_match"] for r in ok_rows
                          if r.get("q1_match") is not None), None),
        "join_match": next((r["join_match"] for r in ok_rows
                            if r.get("join_match") is not None), None),
        "ok": bool(ok_rows) and all(
            r.get("checksum_mismatches", 1) == 0 for r in ok_rows),
    }
    if write_artifact:
        artifact = {
            "metric": "mesh_vs_socket_exchange_throughput",
            "value": result["ratio_max_devices"],
            "unit": "x(socket->mesh)",
            "note": "generic hash exchange per device count: mesh tier "
                    "= jitted shard_map all-to-all (data stays in "
                    "device memory), socket tier = device catalog "
                    "write + real TCP-loopback serve + H2D re-adopt "
                    "(the production cross-host path).  Throughput is "
                    "over LOGICAL (map-statistics) bytes; "
                    "dispatches/compiles are the warm run's "
                    "compiled-program counts",
            **result,
        }
        try:
            with open(os.path.join(REPO, "MULTICHIP.json"), "w") as f:
                json.dump(artifact, f, indent=1)
        except OSError:
            pass
    return result


def child_main(mode: str) -> None:
    _DEADLINE[0] = time.time() + float(
        os.environ.get("BENCH_CHILD_DEADLINE_S", "1e9"))
    sys.path.insert(0, REPO)
    t0 = time.time()
    if mode in ("cpu", "oracle"):
        from spark_rapids_tpu.utils.cpu_backend import force_cpu_backend
        force_cpu_backend()
    import jax
    # persistent compilation cache: the q1/q5 whole-stage programs cost
    # tens of seconds to minutes to compile for the chip; caching them on
    # disk makes every bench rerun start from warm compiles.  Same
    # idempotent helper (and the same directory rule) the engine and the
    # executor worker bootstrap use (utils/compile_cache.py), forced on
    # because the bench wants warm compiles on every backend it measures.
    from spark_rapids_tpu.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache(force=True)
    try:
        platform = jax.devices()[0].platform
    except Exception as e:
        # a backend that cannot init is a reportable stage, not a
        # traceback on a stream the parent may have stopped reading
        emit("backend_error", error=repr(e)[:300], t=time.time() - t0)
        sys.exit(0)
    emit("backend", platform=platform, t=time.time() - t0)
    checkpoint("backend")

    t0 = time.time()
    table = make_lineitem(N_ROWS)
    emit("datagen", rows=N_ROWS, t=time.time() - t0)
    checkpoint("datagen")

    from spark_rapids_tpu.engine import TpuSession
    if mode == "oracle":
        conf = {"spark.rapids.sql.enabled": "false"}
    else:
        # variableFloatAgg: sums/avgs over doubles; without it the aggregate
        # falls back to CPU and the bench degenerates into a D2H-bound CPU
        # query (round-2 postmortem).  The reference enables the same conf
        # for its TPC-H/TPCxBB runs (docs/configs.md variableFloatAgg).
        conf = {"spark.rapids.sql.variableFloatAgg.enabled": "true"}
    session = TpuSession(conf)
    li = session.from_arrow(table)

    # the oracle has no compile/H2D warmup effects, so one run suffices
    # (the parent takes min over warmup+runs for the CPU child); device
    # children take 3 steady runs — the FIRST post-warmup run still
    # absorbs async tails (r4: tpcds_q5 runs [1.24s, 0.26s]), so min()
    # over 3 is the honest steady state
    heavy_runs = 1 if mode == "oracle" else 3
    # headline first: if the deadline lands mid-suite, Q6-cached survives
    timed("q6", lambda: checksum(q6(li).collect()),
          N_RUNS if mode != "oracle" else 1)
    timed("q1", lambda: checksum(q1(li).collect()), heavy_runs)

    try:
        transfer_microbench()
    except Exception as e:  # microbench must never sink the bench
        emit("transfer", error=repr(e)[:200])
    checkpoint("transfer")

    # scan-included Q6: parquet from disk through the device decode path
    # (file scans are NOT in the memory scan cache — every run re-decodes)
    pq_dir = os.path.join("/tmp", f"bench_lineitem_{N_ROWS}")
    pq_path = os.path.join(pq_dir, "lineitem.parquet")
    if not os.path.exists(pq_path):
        import pyarrow.parquet as papq
        os.makedirs(pq_dir, exist_ok=True)
        # per-pid temp name: the oracle and device children run
        # CONCURRENTLY and may both lose the exists() race; the atomic
        # replace makes last-writer-wins safe
        tmp = f"{pq_path}.{os.getpid()}.tmp"
        papq.write_table(table, tmp, compression="snappy")
        os.replace(tmp, pq_path)
    emit("parquet_ready", path=pq_path,
         bytes=os.path.getsize(pq_path))
    checkpoint("parquet_ready")
    timed("q6_scan",
          lambda: checksum(q6(session.read.parquet(pq_path)).collect()),
          heavy_runs)

    # TPC-DS q5 (3-channel union + dim joins + ROLLUP) — BASELINE config 3
    t0 = time.time()
    from benchmarks.tpcds.datagen import load_tables as ds_load
    from benchmarks.tpcds.queries import q5 as ds_q5
    ds = ds_load(session, sf=TPCDS_SF)
    emit("tpcds_datagen", sf=TPCDS_SF, t=time.time() - t0)
    checkpoint("tpcds_datagen")
    timed("tpcds_q5", lambda: checksum(ds_q5(ds).collect()), heavy_runs)

    # the reference's HEADLINE query: TPCxBB-like Q5 (19.8x on the chart,
    # reference README.md:7-15) — clickstream x item join + per-user
    # conditional-sum pivot + demographics join
    t0 = time.time()
    from benchmarks.tpcxbb.datagen import load_tables as xbb_load
    from benchmarks.tpcxbb.queries import q5 as xbb_q5
    xbb = xbb_load(session, sf=TPCDS_SF)
    emit("tpcxbb_datagen", sf=TPCDS_SF, t=time.time() - t0)
    checkpoint("tpcxbb_datagen")
    timed("tpcxbb_q5", lambda: checksum(xbb_q5(xbb).collect()), heavy_runs)

    # SF1 scale tier (opt-in: BENCH_SF1=1): ~2.88M-row store_sales
    # (1.2GB of tables), streamed through the multi-batch path (the
    # reference's chart is SF10k on a cluster, README.md:7-15 — this is
    # the one-chip scale point).
    if os.environ.get("BENCH_SF1") == "1":
        from benchmarks.tpcds.queries import QUERIES as DSQ
        t0 = time.time()
        ds1 = ds_load(session, sf=1.0)
        emit("tpcds_sf1_datagen", t=time.time() - t0)
        checkpoint("tpcds_sf1_datagen")
        for name, qn in (("sf1_q5", 5), ("sf1_q3", 3), ("sf1_q7", 7),
                         ("sf1_q19", 19)):
            timed(name,
                  lambda qn=qn: checksum(DSQ[qn](ds1).collect()),
                  heavy_runs)

    # observability rollup: the session-cumulative retry/spill/fallback/
    # wire counters ride along in the BENCH_* artifacts so a perf number
    # is never read without knowing how hard the memory/retry machinery
    # worked to produce it (docs/monitoring.md)
    try:
        from spark_rapids_tpu.metrics.export import session_observability
        emit("observability", **session_observability(session))
    except Exception as e:  # the rollup must never sink the bench
        emit("observability", error=repr(e)[:200])
    # telemetry-plane rollup (ISSUE 17): flight-recorder/sampler state
    # of the driving process, so an artifact records whether the
    # always-on plane was live for the numbers above (its overhead is
    # gated separately: scripts/obs_overhead.py -> BENCH_OBS.json)
    try:
        from spark_rapids_tpu.metrics.ring import get_telemetry
        t = get_telemetry()
        if t is None:
            emit("telemetry", enabled=False)
        else:
            emit("telemetry", enabled=True, role=t.role,
                 sampler_ticks=t.sampler.ticks,
                 series=sorted(t.sampler.latest()),
                 **t.recorder.stats())
    except Exception as e:
        emit("telemetry", error=repr(e)[:200])
    # adaptive-execution rollup (PR-3): coalesce/skew/strategy-change
    # counts and stage re-plan latency next to the observability block,
    # so a perf number is never read without knowing whether AQE rewrote
    # the plan that produced it
    try:
        from spark_rapids_tpu.metrics.export import session_adaptive
        emit("adaptive", **session_adaptive(session))
    except Exception as e:
        emit("adaptive", error=repr(e)[:200])
    # integrity rollup (ISSUE 4): checksum on/off wire-throughput delta
    # plus the session's corruption-recovery counters, so the BENCH_*
    # artifacts track the verification tax and any recoveries that fired
    try:
        emit("integrity", **integrity_microbench(session))
    except Exception as e:
        emit("integrity", error=repr(e)[:200])
    # compression rollup (ISSUE 5): spill write/read delta per codec
    # (codec none == the pre-compression raw path; the deltas say what a
    # codec costs/buys at the spill tier on THIS host), next to the wire
    # per-codec numbers BENCH_WIRE.json carries
    try:
        emit("compress", **compress_microbench())
    except Exception as e:
        emit("compress", error=repr(e)[:200])
    # fusion rollup (ISSUE 6): per-query jit-compile count, per-batch
    # dispatch count and warmup seconds with whole-stage fusion on vs
    # off, so the >= 2x compile-count acceptance is a measured artifact
    try:
        emit("fusion", **fusion_microbench())
    except Exception as e:
        emit("fusion", error=repr(e)[:200])
    # tracing rollup (ISSUE 7): q1 with distributed tracing + journal on
    # vs off (<5% acceptance gate) and the heartbeat rpc round-trip cost,
    # so the observability tax is a measured BENCH_* artifact
    try:
        emit("tracing", **tracing_microbench())
    except Exception as e:
        emit("tracing", error=repr(e)[:200])
    # pressure rollup (ISSUE 8): the memory-budget sweep at 25/50/75/100%
    # of measured working set with ledger-derived breakdowns, plus the
    # ledger's own <5% overhead gate; also writes BENCH_PRESSURE.json
    try:
        emit("pressure", **pressure_microbench())
    except Exception as e:
        emit("pressure", error=repr(e)[:200])
    # profile rollup (ISSUE 13): per-operator roofline ledgers for the
    # representative query set (declared bytes/flops joined against
    # measured spans, bottleneck resource per plan node), serving SLO
    # phase histograms, and the profiler's own <5% overhead gate; also
    # writes BENCH_PROFILE.json — the capture scripts/
    # profile_regression.py diffs against the checked-in baseline
    try:
        emit("profile", **profile_microbench())
    except Exception as e:
        emit("profile", error=repr(e)[:200])
    # serving rollup (ISSUE 10): parameterized plan-cache compile
    # reduction on a q1-shaped literal variant, and the mixed-workload
    # scheduler sweep at concurrency 1/4/16 (throughput, p95 latency and
    # queue time, OOM-injection bit-for-bit check); also writes
    # BENCH_SERVE.json
    try:
        emit("serve", **serve_microbench())
    except Exception as e:
        emit("serve", error=repr(e)[:200])
    # streaming rollup (ISSUE 20): incremental micro-batch epochs/s per
    # batch size, p50/p95 epoch latency, the zero-warm-compile gate
    # (every epoch after the first replays compiled stages), and the
    # incremental-vs-full-requery speedup with a batch-oracle checksum
    # cross-check; also writes BENCH_STREAM.json
    try:
        emit("streaming", **streaming_microbench())
    except Exception as e:
        emit("streaming", error=repr(e)[:200])
    # chaos rollup (ISSUE 15): recovery latency at 0/1/2 injected
    # mid-task kills on a 3-worker ProcCluster plus a measured
    # speculation win on an injected-delay straggler, every round
    # verified bit-for-bit; also writes BENCH_CHAOS.json.  CPU worker
    # subprocesses only (the device child holds the one chip).
    # The stage costs ~60s of cluster spawns; when the deadline cannot
    # afford it, it rides the standing artifact (refresh standalone:
    # `python bench.py --chaos`)
    try:
        if _DEADLINE[0] - time.time() >= 90:
            emit("chaos", **chaos_microbench())
        else:
            with open(os.path.join(REPO, "BENCH_CHAOS.json")) as f:
                art = json.load(f)
            emit("chaos", from_artifact=True, ok=art.get("ok"),
                 clean_s=art.get("clean_s"),
                 kill_rounds=art.get("kill_rounds"),
                 speculation=art.get("speculation"))
    except Exception as e:
        emit("chaos", error=repr(e)[:200])
    # multichip rollup (ISSUE 14): per-device-count mesh-vs-socket
    # exchange throughput (forced-CPU children: the device child holds
    # the one chip), warm dispatch/compile counts, and
    # the cross-tier checksum/q1/join parity flags; also writes
    # MULTICHIP.json — real rows where the ok-flag dryrun record was.
    # The sweep spawns one fresh-backend child per device count (~200s):
    # when the bench deadline cannot afford that, the stage rides the
    # standing artifact (refresh standalone: `python bench.py
    # --multichip`) instead of silently vanishing into an abort
    try:
        if _DEADLINE[0] - time.time() >= 260:
            emit("multichip", **multichip_microbench())
        else:
            with open(os.path.join(REPO, "MULTICHIP.json")) as f:
                art = json.load(f)
            emit("multichip", from_artifact=True,
                 recorded_note=art.get("note"),
                 rows=art.get("rows"),
                 ratio_max_devices=art.get("ratio_max_devices"),
                 checksum_mismatches=art.get("checksum_mismatches"),
                 q1_match=art.get("q1_match"),
                 join_match=art.get("join_match"),
                 ok=art.get("ok"))
    except Exception as e:
        emit("multichip", error=repr(e)[:200])
    emit("done", t=time.time() - (_DEADLINE[0] - float(
        os.environ.get("BENCH_CHILD_DEADLINE_S", "1e9"))))


# --------------------------------------------------------------------------
# parent: budget-enforced orchestration
# --------------------------------------------------------------------------

class StageReader:
    """Reads JSON stage lines from a child under per-read budgets."""

    def __init__(self, label: str, mode: str, deadline_s: float):
        self.label = label
        env = dict(os.environ)
        if mode in ("cpu", "oracle"):
            env["JAX_PLATFORMS"] = "cpu"
        env["BENCH_CHILD_DEADLINE_S"] = str(max(deadline_s, 5.0))
        # per-child stderr LOG FILE, never the shared stderr: a child
        # traceback after the headline line would make the driver's
        # combined capture unparseable
        self._errlog = open(f"/tmp/bench_{label}.stderr.log", "a")
        self.proc = subprocess.Popen(
            [sys.executable, "-u", os.path.abspath(__file__),
             f"--child={mode}"],
            stdout=subprocess.PIPE, stderr=self._errlog, text=True, env=env)
        self._lines: list = []
        self._reader = threading.Thread(target=self._pump, daemon=True)
        self._lock = threading.Condition()
        self._eof = False
        self._reader.start()

    def _pump(self):
        for line in self.proc.stdout:
            with self._lock:
                self._lines.append(line)
                self._lock.notify()
        with self._lock:
            self._eof = True
            self._lock.notify()

    def next_stage(self, budget_s: float):
        """Next parsed stage line, or None on timeout/eof.  On timeout the
        child is killed (its chip, if any, frees with the process)."""
        deadline = time.time() + budget_s
        while True:
            with self._lock:
                while not self._lines:
                    if self._eof:
                        return None
                    remaining = deadline - time.time()
                    if remaining <= 0:
                        log(f"{self.label}: budget exceeded "
                            f"({budget_s:.0f}s) — killing child")
                        self.proc.kill()
                        return None
                    self._lock.wait(timeout=min(remaining, 5))
                line = self._lines.pop(0)
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                rec = None
            if not isinstance(rec, dict) or "stage" not in rec:
                log(f"{self.label}: ignoring non-stage stdout: "
                    f"{line.strip()[:120]}")
                continue
            log(f"{self.label}: {rec}")
            _write_partial(self.label, rec)
            return rec

    def close(self):
        try:
            self.proc.kill()
        except OSError:
            pass
        try:
            self._errlog.close()
        except OSError:
            pass


_PARTIAL: dict = {"stages": []}


def _write_partial(label: str, rec: dict) -> None:
    _PARTIAL["stages"].append({"child": label, **rec})
    try:
        with open(os.path.join(REPO, "BENCH_partial.json"), "w") as f:
            json.dump(_PARTIAL, f, indent=1)
    except OSError:
        pass


def collect(r: "StageReader", end_at: float) -> dict:
    """Read a child's stages until eof/abort/deadline.  Returns
    {platform, runs: {q: [t..]}, warmup: {q: t}, values: {q: v},
    transfer: {...}}."""
    out = {"platform": None, "runs": {}, "warmup": {}, "values": {},
           "transfer": None, "aborted": False, "backend_error": None,
           "observability": None, "adaptive": None, "integrity": None,
           "compress": None, "fusion": None, "tracing": None,
           "pressure": None, "serve": None, "streaming": None,
           "profile": None, "chaos": None, "multichip": None}
    first = True
    try:
        while True:
            budget = min(TPU_PROBE_S if first else 240.0,
                         end_at - time.time())
            if budget <= 0:
                break
            rec = r.next_stage(budget)
            if rec is None:
                break
            first = False
            st = rec.get("stage")
            if st == "backend_error":
                out["backend_error"] = rec.get("error")
                break
            if st == "backend":
                out["platform"] = rec.get("platform")
            elif st == "warmup":
                out["warmup"][rec["q"]] = rec["t"]
                out["values"][rec["q"]] = rec.get("value")
            elif st == "run":
                out["runs"].setdefault(rec["q"], []).append(rec["t"])
                out["values"][rec["q"]] = rec.get("value", None)
            elif st == "transfer":
                out["transfer"] = {k: v for k, v in rec.items()
                                   if k != "stage"}
            elif st == "observability":
                out["observability"] = {k: v for k, v in rec.items()
                                        if k != "stage"}
            elif st == "adaptive":
                out["adaptive"] = {k: v for k, v in rec.items()
                                   if k != "stage"}
            elif st == "integrity":
                out["integrity"] = {k: v for k, v in rec.items()
                                    if k != "stage"}
            elif st == "compress":
                out["compress"] = {k: v for k, v in rec.items()
                                   if k != "stage"}
            elif st == "fusion":
                out["fusion"] = {k: v for k, v in rec.items()
                                 if k != "stage"}
            elif st == "tracing":
                out["tracing"] = {k: v for k, v in rec.items()
                                  if k != "stage"}
            elif st == "pressure":
                out["pressure"] = {k: v for k, v in rec.items()
                                   if k != "stage"}
            elif st == "serve":
                out["serve"] = {k: v for k, v in rec.items()
                                if k != "stage"}
            elif st == "streaming":
                out["streaming"] = {k: v for k, v in rec.items()
                                    if k != "stage"}
            elif st == "profile":
                out["profile"] = {k: v for k, v in rec.items()
                                  if k != "stage"}
            elif st == "chaos":
                out["chaos"] = {k: v for k, v in rec.items()
                                if k != "stage"}
            elif st == "multichip":
                out["multichip"] = {k: v for k, v in rec.items()
                                    if k != "stage"}
            elif st == "abort":
                out["aborted"] = True
                break
            elif st == "done":
                break
        return out
    finally:
        r.close()


def main():
    if len(sys.argv) > 1 and sys.argv[1].startswith("--child="):
        child_main(sys.argv[1].split("=", 1)[1])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--pressure":
        # standalone memory-budget sweep: regenerate BENCH_PRESSURE.json
        # without the full suite (runs on whatever backend is available;
        # set JAX_PLATFORMS=cpu to keep it off the chip)
        print(json.dumps(pressure_microbench(), indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--profile":
        # standalone roofline-attribution capture: regenerate
        # BENCH_PROFILE.json (per-operator ledgers + SLO histograms +
        # profiler overhead gate) without the full suite
        print(json.dumps(profile_microbench(), indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--serve":
        # standalone serving-tier sweep: regenerate BENCH_SERVE.json
        # (plan-cache compile reduction + concurrency 1/4/16 mixed
        # workload) without the full suite
        print(json.dumps(serve_microbench(), indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--streaming":
        # standalone streaming micro-batch sweep: regenerate
        # BENCH_STREAM.json (epochs/s per batch size, p50/p95 epoch
        # latency, zero-warm-compile gate, incremental-vs-full-requery
        # speedup + checksum) without the full suite
        print(json.dumps(streaming_microbench(), indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--chaos":
        # standalone chaos/recovery sweep: regenerate BENCH_CHAOS.json
        # (kill-recovery latency at 0/1/2 kills + the speculation win)
        # without the full suite; worker subprocesses are forced-CPU
        print(json.dumps(chaos_microbench(), indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1].startswith("--multichip-child="):
        multichip_child(int(sys.argv[1].split("=", 1)[1]))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--multichip":
        # standalone per-device-count mesh-vs-socket exchange sweep:
        # regenerate MULTICHIP.json (real rows) without the full suite
        print(json.dumps(multichip_microbench(), indent=1))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--fusion":
        # standalone whole-stage fusion/donation sweep (CPU backend:
        # the stage is a CPU child in the full run too) — compile and
        # dispatch counts plus donated_copies_warm_run per query shape
        from spark_rapids_tpu.utils.cpu_backend import force_cpu_backend
        force_cpu_backend()
        print(json.dumps(fusion_microbench(), indent=1))
        return

    # The headline line is emitted UNCONDITIONALLY (round-4 postmortem:
    # parsed=null after a 554-turn round).  Whatever _run() manages — or
    # doesn't — the last stdout act of this process is one JSON line, also
    # mirrored to BENCH_HEADLINE.json.
    result = {"metric": "tpch_q6_like_device_throughput", "value": 0.0,
              "unit": "Mrows/s[none]", "vs_baseline": 0.0}
    try:
        result = _run() or result
    except SystemExit:
        pass
    except BaseException as e:  # noqa: BLE001 — report, never crash out
        import traceback
        traceback.print_exc(file=sys.stderr)
        result.setdefault("extra", {})["fatal"] = repr(e)[:500]
    finally:
        line = json.dumps(result)
        try:
            with open(os.path.join(REPO, "BENCH_HEADLINE.json"), "w") as f:
                f.write(line + "\n")
        except OSError:
            pass
        print(line, flush=True)
    if result.get("extra", {}).get("fatal"):
        sys.exit(1)  # no q6 from the device (or a crash) is a failed bench


def _run():
    end_at = T0 + GLOBAL_BUDGET_S
    # no platform pinned means the accelerator; JAX_PLATFORMS=cpu asks for
    # the engine on the CPU backend explicitly (unit tag [cpu])
    dev_mode = "cpu" if os.environ.get("JAX_PLATFORMS", "") == "cpu" \
        else "tpu"

    # 1. start the device child FIRST: backend init and its first compiles
    # overlap for free with the oracle; its stage lines buffer in the
    # reader thread until we consume them.  It is the ONE process that
    # touches the chip.
    dev_reader = StageReader("device", dev_mode, end_at - time.time() - 5)

    # 2. CPU oracle (forced-CPU child).  The oracle is deterministic in
    # (N_ROWS, TPCDS_SF); BENCH_ORACLE_CACHE=1 lets reruns skip the ~3min
    # oracle replay.
    cache_path = f"/tmp/bench_oracle_{N_ROWS}_{TPCDS_SF}.json"
    cpu = None
    if os.environ.get("BENCH_ORACLE_CACHE") == "1" \
            and os.path.exists(cache_path):
        try:
            with open(cache_path) as f:
                cpu = json.load(f)
            log(f"oracle loaded from {cache_path}")
        except (OSError, ValueError):
            cpu = None
    if cpu is None or not cpu.get("runs", {}).get("q6"):
        # SF1 adds ~40s datagen + 4 scale queries to the oracle's budget
        oracle_cap = 600 if os.environ.get("BENCH_SF1") == "1" else 210
        cpu = collect(StageReader("cpu-oracle", "oracle",
                                  min(end_at, T0 + oracle_cap)
                                  - time.time()),
                      min(end_at, T0 + oracle_cap))
        if not cpu["runs"].get("q6") and not cpu["warmup"].get("q6"):
            log("FATAL: CPU oracle produced no q6 runs")
            return {"metric": "tpch_q6_like_device_throughput",
                    "value": 0.0, "unit": "Mrows/s[none]",
                    "vs_baseline": 0.0,
                    "extra": {"fatal": "cpu oracle produced no q6 runs"}}
        # the oracle has no warmup effects: fold warmup times in as runs
        for q, t in cpu["warmup"].items():
            cpu["runs"].setdefault(q, []).append(t)
        if os.environ.get("BENCH_ORACLE_CACHE") == "1" \
                and len(cpu["runs"]) >= 5 and not cpu.get("aborted"):
            try:
                with open(cache_path, "w") as f:
                    json.dump(cpu, f)
            except OSError:
                pass

    # 3. consume the device child (already running).  No retry and no CPU
    # substitute: a device run that yields no q6 is a failed bench.
    dev = collect(dev_reader, end_at)
    unit_note = ""
    if not dev["runs"].get("q6") and dev.get("warmup", {}).get("q6"):
        # deadline landed between warmup and run 1: the warmup time
        # (compile+H2D inclusive) is still device evidence — report it
        # with an explicit unit marker instead of discarding it
        log("device runs missing; falling back to warmup time")
        dev["runs"]["q6"] = [dev["warmup"]["q6"]]
        unit_note = ":warmup-only"
    if not dev["runs"].get("q6"):
        log("FATAL: device child produced no q6 run")
        return {"metric": "tpch_q6_like_device_throughput",
                "value": 0.0, "unit": "Mrows/s[none]",
                "vs_baseline": 0.0,
                "extra": {"fatal": "device child produced no q6 run",
                          "backend_error": dev.get("backend_error")}}

    platform = (dev["platform"] or "unknown") + unit_note
    per_query = {}
    mismatch = False
    for q in sorted(set(dev["runs"]) | set(cpu["runs"])):
        d = min(dev["runs"][q]) if dev["runs"].get(q) else None
        c = min(cpu["runs"][q]) if cpu["runs"].get(q) else None
        entry = {"dev_s": round(d, 4) if d else None,
                 "cpu_s": round(c, 4) if c else None,
                 "vs_oracle": round(c / d, 3) if d and c else None,
                 "warmup_s": round(dev["warmup"].get(q, 0), 2)}
        dv, cv = dev["values"].get(q), cpu["values"].get(q)
        if dv is not None and cv is not None:
            entry["match"] = bool(abs(dv - cv) <= 1e-4 * max(1.0, abs(cv)))
            if not entry["match"]:
                mismatch = True
                log(f"ORACLE MISMATCH {q}: dev={dv} cpu={cv}")
        per_query[q] = entry

    q6_t = min(dev["runs"]["q6"])
    cpu_t = min(cpu["runs"]["q6"])
    vs = cpu_t / q6_t
    if mismatch:
        platform += ":MISMATCH"
    # Q6 touches 4 float64/int64 columns -> 32 B/row per pass
    eff_gb_s = N_ROWS * 32 / q6_t / 1e9
    extra = {
        "per_query": per_query,
        "transfer": dev.get("transfer"),
        "observability": dev.get("observability"),
        "adaptive": dev.get("adaptive"),
        "integrity": dev.get("integrity"),
        "compress": dev.get("compress"),
        "fusion": dev.get("fusion"),
        "tracing": dev.get("tracing"),
        "pressure": dev.get("pressure"),
        "serve": dev.get("serve"),
        "streaming": dev.get("streaming"),
        "profile": dev.get("profile"),
        "chaos": dev.get("chaos"),
        "multichip": dev.get("multichip"),
        "q6_effective_gb_s": round(eff_gb_s, 2),
        "hbm_roofline_note": "v5e HBM ~819 GB/s; q6 reads 32 B/row",
        "vs_ref_headline": round(vs / 19.8, 4),
        "tpcds_sf": TPCDS_SF,
        "aborted": dev.get("aborted", False),
    }
    result = {
        "metric": f"tpch_q6_like_{N_ROWS // 1_000_000}M_rows_device_throughput",
        "value": round(N_ROWS / q6_t / 1e6, 3),
        "unit": f"Mrows/s[{platform}]",
        "vs_baseline": round(vs, 3),
        "extra": extra,
    }
    onchip_path = os.path.join(REPO, "BENCH_ONCHIP.json")
    if platform.startswith("tpu") and not mismatch:
        # persist chip evidence.  MERGE with the previous on-chip record:
        # a partial suite (deadline mid-run) must never erase queries an
        # earlier run did capture — stale entries are marked.
        now = int(time.time())
        for e in extra["per_query"].values():
            if e.get("dev_s") is not None:
                e["recorded_unix"] = now
        try:
            with open(onchip_path) as f:
                oldpq = json.load(f).get("extra", {}).get("per_query", {})
            for q, e in oldpq.items():
                cur = extra["per_query"].get(q, {})
                if cur.get("dev_s") is None and e.get("dev_s") is not None:
                    # carry the earlier window's number (with its own
                    # recorded_unix) so partial windows accumulate
                    extra["per_query"][q] = {**e, "stale": True}
        except (OSError, ValueError):
            pass
        try:
            with open(onchip_path, "w") as f:
                json.dump({"recorded_unix": int(time.time()), **result}, f,
                          indent=1)
        except OSError:
            pass
    elif os.path.exists(onchip_path):
        # chip unavailable THIS run: point at the last real on-chip
        # record (clearly labeled; the headline metric stays this run's)
        try:
            with open(onchip_path) as f:
                extra["last_onchip"] = json.load(f)
        except (OSError, ValueError):
            pass
    try:
        with open(os.path.join(REPO, "BENCH_DETAIL.json"), "w") as f:
            json.dump({"dev": dev, "cpu": cpu, "extra": extra}, f, indent=1)
    except OSError:
        pass
    return result


if __name__ == "__main__":
    main()
