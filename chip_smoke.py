"""Chip smoke: the engine's main query path, end to end, on one TPU.

    python chip_smoke.py                  # one chip, TPC-H SF1 widths
    python chip_smoke.py --chips 4        # mesh path only, four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 200000   # rehearsal

One process, no child touches JAX.  The device check comes first and fixes
the verdict: anything but a `tpu` platform exits non-zero at once at the
default size; with `--rows` (the CPU rehearsal) the queries still run so
paths and control flow are exercised, and the script then ends with
`"ok": false` and a non-zero exit.  No CPU run can print `"ok": true`.

Every query goes through `TpuSession` with `spark.rapids.sql.test.enabled`
(an operator planned onto the host raises) and is compared with the same
query on a `spark.rapids.sql.enabled=false` session (pyarrow executors and
ops/cpu_eval.py: independent code).  One JSON object per line; the last
line is the verdict with the device as JAX reports it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

LINEITEM_ROWS = 6_000_000   # TPC-H SF1 lineitem; orders is a quarter of it

# Doubles are compared at this relative tolerance; integers, strings, row
# counts and row order exactly.  Measured on a v5e (scripts/f64_probe.py and
# this script, PR 22): XLA:TPU carries a double as a pair of f32, so a value
# moves by up to 1.8e-15 crossing the host link and one multiply by up to
# 1.3e-14, and the device folds 6M addends in another order than pyarrow.
# The worst result drift seen at SF1 was 1.7e-13 (q1's sums); one missing or
# doubled row moves a sum of a million rows by about 1e-6.  1e-10 sits three
# orders above the drift and four below the smallest wrong answer.
DOUBLE_RTOL = 1e-10


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


class CompileWatch:
    """Counts XLA compiles (eager programs included) and their seconds."""

    def __init__(self):
        import jax
        self.requests = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on_duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs


def rows_match(dev, host, rtol):
    """(ok, worst relative double error): ints, strings, row count and
    order exact; doubles within rtol."""
    if len(dev) != len(host):
        return False, float("nan")
    worst = 0.0
    for dr, hr in zip(dev, host):
        if len(dr) != len(hr):
            return False, worst
        for d, h in zip(dr, hr):
            if isinstance(h, float) and isinstance(d, float):
                if math.isnan(h) or math.isnan(d):
                    if not (math.isnan(h) and math.isnan(d)):
                        return False, float("nan")
                    continue
                err = abs(d - h) / max(abs(h), 1e-300)
                worst = max(worst, err)
            elif d != h:
                return False, worst
    return worst <= rtol, worst


def run_query(name, query, expected, watch):
    """Cold run and warm run of `query()`, compared with the `expected`
    rows; emits the per-query line.  The warm run must add no compile and
    repeat the cold run's answer bit for bit."""
    from spark_rapids_tpu.utils import kernel_cache as KC
    c0, s0, t0 = watch.requests, watch.seconds, time.perf_counter()
    first = query().collect()
    first_s = time.perf_counter() - t0
    compiles, compile_s = watch.requests - c0, watch.seconds - s0
    c1, k1 = watch.requests, KC.stats()
    t0 = time.perf_counter()
    warm = query().collect()
    run_s = time.perf_counter() - t0
    k2 = KC.stats()
    warm_compiles = (watch.requests - c1
                     + k2["builds"] - k1["builds"]
                     + k2["stage_compiles"] - k1["stage_compiles"])
    same, worst = rows_match(warm, expected, DOUBLE_RTOL)
    stable, _ = rows_match(first, warm, 0.0)
    ok = bool(same and stable and warm_compiles == 0)
    emit(query=name, ok=ok, rows=len(warm), compile_s=round(compile_s, 3),
         first_s=round(first_s, 3), run_s=round(run_s, 4),
         compiles=compiles, warm_compiles=warm_compiles,
         max_double_rel_err=worst, matches_reference=bool(same),
         repeatable=bool(stable))
    return ok


def check(name, ok, **kw):
    emit(check=name, ok=bool(ok), **kw)
    return bool(ok)


def check_no_cpu_fallbacks(session):
    n = session.query_metrics_total.get("numCpuFallbacks", 0)
    return check("no_cpu_fallbacks", n == 0, numCpuFallbacks=n)


def table_device_bytes(table) -> int:
    """Bytes a pyarrow table takes as device columns (strings aside)."""
    import pyarrow as pa
    n = 0
    for c in table.columns:
        if pa.types.is_floating(c.type) or pa.types.is_integer(c.type):
            n += table.num_rows * c.type.bit_width // 8
    return n


def base_conf():
    return {
        # a host-planned operator raises instead of quietly running there
        "spark.rapids.sql.test.enabled": "true",
        # double sums/averages on the device (the reference turns the same
        # switch on for its TPC-H runs)
        "spark.rapids.sql.variableFloatAgg.enabled": "true",
    }


def make_tables(args):
    """lineitem and orders from --seed, numpy-vectorised (seconds at SF1)."""
    from benchmarks.tpch import bulk
    n = args.rows or LINEITEM_ROWS
    n_orders = max(n // 4, 1)
    t0 = time.perf_counter()
    lineitem = bulk.make_lineitem(n, seed=args.seed, n_orders=n_orders)
    orders = bulk.make_orders(n_orders, seed=args.seed)
    emit(phase="datagen", lineitem_rows=n, orders_rows=n_orders,
         seed=args.seed, seconds=round(time.perf_counter() - t0, 2))
    return lineitem, orders


def one_chip_phase(args, devices, watch) -> bool:
    import pyarrow.parquet as papq
    from benchmarks.tpch import bulk
    from spark_rapids_tpu import native
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.metrics.registry import ENGINE_COUNTERS
    from spark_rapids_tpu.utils.compile_cache import active_cache_dir
    from spark_rapids_tpu import config as C

    lineitem, orders = make_tables(args)
    dev = TpuSession(base_conf())
    host = TpuSession({"spark.rapids.sql.enabled": "false"})
    queries = {
        "q6": lambda li, od: bulk.q6(li),
        "q1": lambda li, od: bulk.q1(li),
        "q3_join": bulk.q3_shape,
    }
    on_dev = (dev.from_arrow(lineitem), dev.from_arrow(orders))
    on_host = (host.from_arrow(lineitem), host.from_arrow(orders))
    ok = True
    for name, q in queries.items():
        ok &= run_query(name, lambda: q(*on_dev), q(*on_host).collect(),
                        watch)
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")  # under TMPDIR
    try:
        pq_path = os.path.join(scratch, "lineitem.parquet")
        papq.write_table(lineitem, pq_path, compression="snappy")
        emit(phase="parquet_written", bytes=os.path.getsize(pq_path))
        ok &= run_query("q6_parquet",
                        lambda: bulk.q6(dev.read.parquet(pq_path)),
                        bulk.q6(host.read.parquet(pq_path)).collect(),
                        watch)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)

    # -- nothing gave way quietly ------------------------------------------
    ok &= check_no_cpu_fallbacks(dev)
    ok &= check("numHbmDetectFallbacks",
                ENGINE_COUNTERS.get("numHbmDetectFallbacks") == 0,
                value=ENGINE_COUNTERS.get("numHbmDetectFallbacks"))
    stats = devices[0].memory_stats() or {}
    limit = int(stats.get("bytes_limit", 0))
    pool = dev.runtime.pool_limit
    want_pool = int(limit * float(dev.conf.get(C.TPU_ALLOC_FRACTION)))
    ok &= check("pool_from_device", limit > 0 and pool == want_pool,
                pool_bytes=pool, device_bytes_limit=limit)
    ok &= check("native_host_library", native.native_available())
    resident = table_device_bytes(lineitem)
    peak = int(stats.get("peak_bytes_in_use", 0))
    ok &= check("data_lived_on_device", peak >= resident,
                peak_bytes_in_use=peak, resident_table_bytes=resident)
    cache_dir = active_cache_dir() or os.environ.get(
        "JAX_COMPILATION_CACHE_DIR")
    entries = len(os.listdir(cache_dir)) \
        if cache_dir and os.path.isdir(cache_dir) else 0
    ok &= check("compile_cache", bool(cache_dir) and entries > 0,
                dir=cache_dir, entries=entries,
                persistent_cache_hits=watch.cache_hits,
                compile_requests=watch.requests)
    return ok


def four_chip_phase(args, devices, watch) -> bool:
    """The mesh path and what it is compared with, nothing else: q1 and
    the join query (SPMD aggregate/join/sort, exec/distributed.py), and q1
    behind a repartition (the generic exchange lowered to an
    ICI all-to-all, shuffle/mesh_exchange.py) on a 4-device mesh session,
    against the same three on device 0 of this process."""
    from benchmarks.tpch import bulk
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.plan.logical import col

    lineitem, orders = make_tables(args)

    def device_bytes():
        return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in devices[:4]]
    before = device_bytes()
    mesh = TpuSession({**base_conf(),
                       "spark.rapids.sql.tpu.mesh.devices": "4"})
    one = TpuSession(base_conf())
    li_m, od_m = mesh.from_arrow(lineitem), mesh.from_arrow(orders)
    li_1, od_1 = one.from_arrow(lineitem), one.from_arrow(orders)
    q1_on_one = bulk.q1(li_1).collect()
    ok = True
    ok &= run_query("mesh_q1", lambda: bulk.q1(li_m), q1_on_one, watch)
    ok &= run_query("mesh_q3_join", lambda: bulk.q3_shape(li_m, od_m),
                    bulk.q3_shape(li_1, od_1).collect(), watch)
    # the generic exchange, lowered to an ICI all-to-all: q1 behind a
    # repartition, which moves rows, not answers (q1's one-device rows
    # again)
    ok &= run_query(
        "mesh_q1_repartitioned",
        lambda: bulk.q1(li_m.repartition(4, col("l_orderkey"))),
        q1_on_one, watch)

    totals = mesh.query_metrics_total
    ok &= check("ici_exchanges", totals.get("numIciExchanges", 0) > 0,
                numIciExchanges=totals.get("numIciExchanges", 0),
                iciBytesMoved=totals.get("iciBytesMoved", 0))
    ok &= check_no_cpu_fallbacks(mesh)
    grew = [a - b for a, b in zip(device_bytes(), before)]
    ok &= check("every_device_held_a_shard", all(g > 0 for g in grew),
                peak_bytes_growth_per_device=grew)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=0,
                    help="lineitem rows for the CPU rehearsal (default: "
                         f"{LINEITEM_ROWS}, the size the driver runs)")
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh path and its one-device "
                         "comparison")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = device["platform"] == "tpu" and len(devices) >= args.chips
    if not on_chip:
        print(f"chip_smoke: need {args.chips} tpu device(s), JAX reports "
              f"{device}", file=sys.stderr, flush=True)
        if not args.rows:
            return 2
        emit(check="device", ok=False, **device)

    # the program under test: alone in a directory the script dies here,
    # on the import, before it has printed anything that reads as a result
    import benchmarks.tpch.bulk  # noqa: F401
    import spark_rapids_tpu  # noqa: F401

    watch = CompileWatch()
    try:
        if args.chips == 4:
            ok = four_chip_phase(args, devices, watch)
        else:
            ok = one_chip_phase(args, devices, watch)
    except Exception as e:  # the verdict line is still owed
        import traceback
        traceback.print_exc()
        emit(error=f"{type(e).__name__}: {e}"[:2000])
        ok = False
    verdict = bool(ok and on_chip)
    print(json.dumps({"ok": verdict, "device": device}), flush=True)
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
