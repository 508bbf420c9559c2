#!/usr/bin/env python
"""Telemetry-plane overhead gate (ISSUE 17 CI satellite).

Runs the representative streaming query slice twice in CHILD processes —
telemetry plane ON (flight-recorder ring + gauge sampler) vs OFF — and
gates the median wall-time delta at <= --budget-pct (default 2%).  Child
processes because the telemetry singleton is per-process: only a fresh
interpreter measures a true off state.

A relative gate on a sub-second query is noise-dominated, so the gate
passes when EITHER the relative overhead is within budget OR the
absolute delta is under --floor-s (default 80ms): a 3% blip on a 0.4s
query is scheduler jitter, not a regression.  Results land in
BENCH_OBS.json at the root of the repo.

Usage: python scripts/obs_overhead.py [--rows N] [--reps K]
       [--budget-pct P] [--floor-s S] [--out BENCH_OBS.json]
       (internal: --child on|off)
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def child(mode: str, rows: int, reps: int) -> None:
    """One measured process: warm the compile, then time `reps` runs of
    the query slice; emits one JSON line on stdout."""
    sys.path.insert(0, _REPO)
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.plan.logical import col, functions as F, lit

    conf = {
        "spark.rapids.sql.tpu.telemetry.enabled":
            "true" if mode == "on" else "false",
        # the gate targets ring+sampler; the http listener is one idle
        # accept thread and would only add port-collision flake here
        "spark.rapids.sql.tpu.telemetry.http.enabled": "false",
        # streaming path: per-operator spans make the journal tap hot
        "spark.rapids.sql.tpu.wholeStage.enabled": "false",
        "spark.rapids.sql.tpu.shuffle.partitions": "4",
        "spark.rapids.sql.variableFloatAgg.enabled": "true",
    }
    s = TpuSession(conf)
    if mode == "on":
        from spark_rapids_tpu.metrics.ring import get_telemetry
        assert get_telemetry() is not None, \
            "telemetry=on child has no live plane"
    fact = s.from_pydict({"k": [i % 7 for i in range(rows)],
                          "v": [float(i) for i in range(rows)],
                          "q": [i % 3 for i in range(rows)]})
    dim = s.from_pydict({"k": list(range(7)),
                         "name": [f"g{j}" for j in range(7)]})

    def run():
        df = (fact.join(dim, on="k")
              .filter(col("q") < 2)
              .group_by(col("name"))
              .agg(F.sum(col("v")).alias("sv"),
                   F.count(lit(1)).alias("c"))
              .order_by(col("name")))
        return df.collect()

    assert len(run()) == 7  # warm compile outside the timed region
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    print(json.dumps({"mode": mode, "times": times,
                      "median_s": statistics.median(times)}))


def measure(mode: str, rows: int, reps: int) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", mode,
         "--rows", str(rows), "--reps", str(reps)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS":
             os.environ.get("JAX_PLATFORMS", "cpu")})
    if proc.returncode != 0:
        raise RuntimeError(f"child ({mode}) failed:\n{proc.stderr}")
    # last stdout line is the payload (library banners may precede it)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", choices=["on", "off"])
    ap.add_argument("--rows", type=int, default=60_000)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--budget-pct", type=float, default=2.0)
    ap.add_argument("--floor-s", type=float, default=0.08)
    ap.add_argument("--out", default=os.path.join(_REPO,
                                                  "BENCH_OBS.json"))
    args = ap.parse_args()
    if args.child:
        child(args.child, args.rows, args.reps)
        return 0

    off = measure("off", args.rows, args.reps)
    on = measure("on", args.rows, args.reps)
    delta_s = on["median_s"] - off["median_s"]
    overhead_pct = 100.0 * delta_s / off["median_s"]
    within_budget = (overhead_pct <= args.budget_pct
                     or delta_s <= args.floor_s)
    result = {
        "bench": "telemetry-overhead",
        "rows": args.rows,
        "reps": args.reps,
        "off_median_s": round(off["median_s"], 5),
        "on_median_s": round(on["median_s"], 5),
        "delta_s": round(delta_s, 5),
        "overhead_pct": round(overhead_pct, 3),
        "budget_pct": args.budget_pct,
        "floor_s": args.floor_s,
        "pass": within_budget,
        "off_times": [round(t, 5) for t in off["times"]],
        "on_times": [round(t, 5) for t in on["times"]],
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"telemetry overhead: off={off['median_s']:.4f}s "
          f"on={on['median_s']:.4f}s delta={delta_s * 1000:.1f}ms "
          f"({overhead_pct:+.2f}%; budget {args.budget_pct}% or "
          f"<{args.floor_s * 1000:.0f}ms) -> "
          f"{'PASS' if within_budget else 'FAIL'}  [{args.out}]")
    if not within_budget:
        print("the always-on ring+sampler exceeded its overhead budget; "
              "profile metrics/ring.py (tap + tick cost) before raising "
              "the budget", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
