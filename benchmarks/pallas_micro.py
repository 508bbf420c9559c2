"""Pallas-vs-XLA microbench: settle `spark.rapids.sql.tpu.pallas.enabled`
with measured data (VERDICT r4 item 7).

Benchmarks, on the ambient backend (meant for the real chip; prints the
platform so CPU-backend runs are self-labeling):
  1. cumsum        — ops/pallas_kernels.cumsum_1d vs jnp.cumsum (the
                     segmented-aggregation inner primitive, _masked_cumsum)
  2. seg_sum       — exec/aggregate._seg_sum (cumsum + 2 searchsorted
                     gathers) with the pallas cumsum vs the XLA cumsum
  3. bit_unpack    — io/parquet_device._bitpacked_unpack (XLA gather/
                     shift/mask), timed in GB/s to decide whether a
                     pallas rival is worth writing at all
  4. sort_encode   — exec/sort key-encode + argsort (XLA), same question

Writes BENCH_PALLAS.json at the repo root:
  {platform, results: [{name, n, dtype, xla_ms, pallas_ms, speedup}...],
   verdict: "..."}

Run: timeout 900 python benchmarks/pallas_micro.py   (on the machine with
the chip: one process per chip; `--cpu` is the interpret-mode self-test).
The installed Pallas TPU lowering refuses all three kernels, so on a chip
this script fails at the first pallas row until they are rewritten."""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--cpu" in sys.argv:
    # CPU self-test: pin the backend before first use
    from spark_rapids_tpu.utils.cpu_backend import force_cpu_backend
    force_cpu_backend()

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def timeit(fn, *args, n_runs: int = 10) -> float:
    """Median ms of a jitted fn (blocked)."""
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(n_runs):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) * 1e3)
    ts.sort()
    return ts[len(ts) // 2]


def main() -> None:
    platform = jax.devices()[0].platform
    results = []
    rng = np.random.RandomState(7)

    # 1/2. cumsum + seg_sum
    from spark_rapids_tpu.exec import aggregate as agg
    from spark_rapids_tpu.ops.pallas_kernels import cumsum_1d
    for n in (1 << 20, 1 << 23):
        for dt in (jnp.int32, jnp.float32):
            v = jnp.asarray(rng.randint(0, 100, n), dtype=dt)
            xla_ms = timeit(jax.jit(jnp.cumsum), v)
            mode = "compiled"
            try:
                pal_ms = timeit(jax.jit(cumsum_1d), v)
            except Exception as e:
                # compiled pallas unavailable on this backend: measure
                # the INTERPRET-mode kernel so the row is filled, and
                # LABEL it — interpreter timings are functional checks,
                # not chip numbers (no speedup reported)
                print(f"pallas cumsum failed n={n} {dt.__name__}: "
                      f"{e!r}"[:160], file=sys.stderr)
                mode = "interpret"
                try:
                    pal_ms = timeit(
                        jax.jit(lambda x: cumsum_1d(x, interpret=True)),
                        v, n_runs=2)
                except Exception as e2:
                    pal_ms = None
                    mode = "unavailable"
                    print(f"interpret cumsum failed too: {e2!r}"[:160],
                          file=sys.stderr)
            results.append({
                "name": "cumsum", "n": n, "dtype": dt.__name__,
                "xla_ms": round(xla_ms, 3),
                "pallas_ms": round(pal_ms, 3) if pal_ms else None,
                "pallas_mode": mode,
                "speedup": (round(xla_ms / pal_ms, 2)
                            if pal_ms and mode == "compiled" else None)})

    n = 1 << 22
    gid = jnp.asarray(np.sort(rng.randint(0, 1024, n)).astype(np.int32))
    vals = jnp.asarray(rng.randint(0, 1000, n).astype(np.int32))
    contribute = jnp.asarray(rng.rand(n) < 0.9)

    def seg(v, g, c):
        return agg._seg_sum(v, g, c, 1024)
    for mode in ("xla", "pallas"):
        agg.set_pallas_cumsum(mode == "pallas")
        # the dispatcher is BACKEND-gated (TPU -> pallas, CPU -> XLA):
        # record which path actually ran, not which flag was set
        path = agg._pallas_seg_mode() or "xla"
        try:
            ms = timeit(jax.jit(seg), vals, gid, contribute)
        except Exception as e:
            ms = None
            print(f"seg_sum {mode} failed: {e!r}"[:160], file=sys.stderr)
        results.append({"name": f"seg_sum[{mode}]", "n": n,
                        "dtype": "int32", "path": path,
                        "ms": round(ms, 3) if ms else None})
    agg.set_pallas_cumsum(False)

    # 2b. fused multi-aggregate segmented reduction: the scatter path
    # (one jax.ops.segment_* per aggregate — the pre-ISSUE-11 shape)
    # vs the fused dispatcher (shared searchsorted + prefix sums on
    # CPU; ONE pallas pass on TPU).  sum+count+min+max of one column.
    def seg_scatter(v, g, c):
        vz = jnp.where(c, v, 0)
        return (jax.ops.segment_sum(vz, g, num_segments=1024,
                                    indices_are_sorted=True),
                jax.ops.segment_sum(c.astype(jnp.int64), g,
                                    num_segments=1024,
                                    indices_are_sorted=True),
                jax.ops.segment_min(jnp.where(c, v, 2**31 - 1), g,
                                    num_segments=1024,
                                    indices_are_sorted=True),
                jax.ops.segment_max(jnp.where(c, v, -2**31), g,
                                    num_segments=1024,
                                    indices_are_sorted=True))

    def seg_fused(v, g, c):
        return tuple(agg._seg_multi(
            [("sum", v, c, 0),
             ("sum", c.astype(jnp.int64), jnp.ones_like(c), 0, True),
             ("min", v, c, jnp.int32(2**31 - 1)),
             ("max", v, c, jnp.int32(-2**31))], g, 1024))
    sc_ms = timeit(jax.jit(seg_scatter), vals, gid, contribute)
    # flag ON for the fused measurement so a TPU backend actually runs
    # the pallas kernel (the dispatcher stays backend-gated: CPU still
    # records path=xla by design)
    agg.set_pallas_cumsum(True)
    fu_path = agg._pallas_seg_mode() or "xla"
    fu_ms = timeit(jax.jit(seg_fused), vals, gid, contribute)
    agg.set_pallas_cumsum(False)
    results.append({"name": "seg_agg_scatter", "n": n, "aggs": 4,
                    "ms": round(sc_ms, 3)})
    results.append({"name": "seg_agg_fused", "n": n, "aggs": 4,
                    "path": fu_path, "ms": round(fu_ms, 3),
                    "speedup": round(sc_ms / fu_ms, 2)})

    # 3. parquet bit-unpack (XLA): GB/s of unpacked output
    from spark_rapids_tpu.io.parquet_device import _bitpacked_unpack
    for bw in (3, 11, 20):
        count = 1 << 21
        packed = rng.randint(0, 256, (count * bw + 7) // 8 + 8,
                             dtype=np.uint8).tobytes()

        def unpack(bw=bw, count=count, packed=packed):
            return _bitpacked_unpack(packed, bw, count, count)
        ms = timeit(lambda: unpack())
        results.append({"name": "bit_unpack_xla", "n": count,
                        "bit_width": bw, "ms": round(ms, 3),
                        "out_gb_s": round(count * 4 / ms / 1e6, 2)})

    # 4. sort key-encode + argsort (XLA)
    keys = jnp.asarray(rng.randint(-10**9, 10**9, 1 << 21)
                       .astype(np.int64))
    ms = timeit(jax.jit(jnp.argsort), keys)
    results.append({"name": "argsort_xla", "n": 1 << 21,
                    "dtype": "int64", "ms": round(ms, 3)})

    # 4b. packed-key multi-column sort (ISSUE 11): the full sort_order
    # path — lexsort (variadic sort HLO) vs the packed path (components
    # fused into 64-bit words + embedded row ids, single-operand sort
    # passes).  One-shot spec (everything fits one word), a 2-pass and
    # a 3-pass spec; permutations are verified identical.
    from spark_rapids_tpu import types as RT
    from spark_rapids_tpu.columnar import Column, ColumnarBatch
    from spark_rapids_tpu.exec.sort import sort_order
    from spark_rapids_tpu.ops.expressions import BoundReference
    from spark_rapids_tpu.utils import packed_sort as PS
    ns = 1 << 21
    sort_specs = {
        "int32+byte": (
            [RT.IntegerType, RT.ByteType],
            [rng.randint(-10**9, 10**9, ns).astype(np.int32),
             rng.randint(-100, 100, ns).astype(np.int8)]),
        "int32+int32": (
            [RT.IntegerType, RT.IntegerType],
            [rng.randint(-10**9, 10**9, ns).astype(np.int32),
             rng.randint(-10**9, 10**9, ns).astype(np.int32)]),
        "int32+int64": (
            [RT.IntegerType, RT.LongType],
            [rng.randint(-10**9, 10**9, ns).astype(np.int32),
             rng.randint(-10**17, 10**17, ns).astype(np.int64)]),
    }
    for spec_name, (dts, arrs) in sort_specs.items():
        schema = RT.Schema([RT.StructField(f"c{i}", dt)
                            for i, dt in enumerate(dts)])
        cols = [Column(jnp.asarray(a), jnp.ones(ns, jnp.bool_), dt)
                for a, dt in zip(arrs, dts)]
        batch = ColumnarBatch(cols, jnp.ones(ns, jnp.bool_), schema)
        exprs = [BoundReference(i, dt, f"c{i}")
                 for i, dt in enumerate(dts)]
        asc = [True] * len(dts)
        nf = [True] * len(dts)
        st = {}

        def order_fn(b, _e=exprs, _a=asc, _n=nf, _st=st):
            return sort_order(b, _e, _a, _n, stats=_st)
        PS.set_packed_enabled(False)
        lex_fn = jax.jit(order_fn)
        lex_ms = timeit(lex_fn, batch, n_runs=5)
        o_lex = np.asarray(lex_fn(batch))
        PS.set_packed_enabled(True)
        pk_fn = jax.jit(lambda b, _e=exprs, _a=asc, _n=nf, _st=st:
                        sort_order(b, _e, _a, _n, stats=_st))
        pk_ms = timeit(pk_fn, batch, n_runs=5)
        o_pk = np.asarray(pk_fn(batch))
        results.append({"name": "argsort_lexsort", "spec": spec_name,
                        "n": ns, "ms": round(lex_ms, 3)})
        results.append({"name": "argsort_packed", "spec": spec_name,
                        "n": ns, "ms": round(pk_ms, 3),
                        "passes": st.get("passes"),
                        "identical_perm": bool(np.array_equal(o_lex,
                                                              o_pk)),
                        "speedup": round(lex_ms / pk_ms, 2)})

    cs = [r for r in results if r["name"] == "cumsum"
          and r.get("speedup") is not None]
    wins = [r for r in cs if r["speedup"] > 1.1]
    packed = [r for r in results if r["name"] == "argsort_packed"]
    best = max((r["speedup"] for r in packed), default=0)
    verdict = (
        (f"pallas cumsum wins {len(wins)}/{len(cs)} shapes on {platform}"
         if cs else f"pallas cumsum interpret-only on {platform}")
        + f"; packed-key sort up to {best}x vs lexsort")
    out = {"platform": platform, "recorded_unix": int(time.time()),
           "results": results, "verdict": verdict}
    with open(os.path.join(REPO, "BENCH_PALLAS.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"platform": platform, "verdict": verdict,
                      "n_results": len(results)}))


if __name__ == "__main__":
    main()
