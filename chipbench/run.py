"""One run of one cell of `BENCHMARK.json`, in one process that holds the
cell's chips:

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (import, tables from the seed, the plain reference's answer, load to
the device, a fixed number of warm-up queries that take the compiled
programs from the persistent cache), then the measured loop (`loop.py`), or
with `--trace 1` a short profiled window of its own.  The last line of
standard output is one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics, or with `--trace 1` its per-layer
metrics), `device`, and in a traced run `breakdown`.

Without a TPU, or with fewer chips than the cell asks for, the run fails at
once and prints no result.  `--rows <n>` is the rehearsal that costs no
chip time: `JAX_PLATFORMS=cpu python chipbench/run.py --workload <name>
--rows 200000 ...` runs the whole control flow at `n` lineitem rows (four
virtual devices for a four-chip cell) and always ends `correct: false`, exit
1, with the CPU named in `device` and no number under a metric's name.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)                    # cells, loop, xplane, ...
sys.path.insert(1, os.path.dirname(BENCH_DIR))   # the program under test

import cells  # noqa: E402
import compare  # noqa: E402
import loop  # noqa: E402
import xplane  # noqa: E402

PLAN_SPAN = "chipbench:plan"
#: a time read from the host's clock spans at least this long (the clock is
#: off by some half a millisecond): the plan span repeats until it does
MIN_HOST_CLOCK_S = 0.3


@dataclass
class Evidence:
    """What one traced window leaves for the readers under `readers/`."""
    cell: cells.Cell
    rows: dict        # table -> rows in this run
    queries: int      # queries in the window
    counters: dict    # the session's metrics, summed over the window
    compiles: int     # compile requests and kernel-cache builds in it
    spans: dict       # harness spans on the host's clock: name -> (s, n)
    memory: list      # memory_stats() of each chip used, after the window
    trace: object     # xplane.Trace
    peaks: dict       # peaks.json's entry for this device kind


class CompileWatch:
    """XLA compile requests (eager programs included) plus the program's
    own kernel and stage builds: anything that compiles."""

    def __init__(self):
        import jax
        self.requests = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def count(self):
        from spark_rapids_tpu.utils import kernel_cache
        k = kernel_cache.stats()
        return self.requests + k["builds"] + k["stage_compiles"]


class Run:
    """A cell set up and warm: tables from the seed, the reference's
    answer, the session, the query's DataFrame."""

    def __init__(self, cell, seed, lineitem_rows, scratch):
        from spark_rapids_tpu.engine import TpuSession
        self.cell = cell
        self.rows = cell.rows(lineitem_rows)
        tables = cells.make_tables(cell, seed % 2**32, self.rows)
        self.expected = cell.query.reference(tables)
        self.watch = CompileWatch()
        self.session = TpuSession(dict(cell.config["conf"]))
        self.df = cell.query.build(
            self.session, self._frames(tables, scratch))
        self.worst_err = 0.0
        self.warm_ok = True
        answer = None
        for _ in range(cell.traffic["warmup_queries"]):
            answer = self.df.collect()
        if answer is not None:
            self.warm_ok = self.check(answer)

    def _frames(self, tables, scratch):
        residency = self.cell.traffic["residency"]
        if residency == "device":
            # a memory scan: the first warm-up query loads it into the
            # device scan cache, every later one hits the cache
            return {t: self.session.from_arrow(tb)
                    for t, tb in tables.items()}
        if residency == "parquet":
            import pyarrow.parquet as papq
            frames = {}
            for t, tb in tables.items():
                path = os.path.join(scratch, t + ".parquet")
                papq.write_table(tb, path, compression="snappy")
                frames[t] = self.session.read.parquet(path)
            return frames
        raise ValueError(f"unknown residency {residency!r}")

    def check(self, answer):
        ok, worst = compare.rows_match(answer, self.expected)
        if worst == worst:
            self.worst_err = max(self.worst_err, worst)
        return ok

    def counters(self):
        return dict(self.session.query_metrics_total)


def measured_run(run, seconds):
    """`--trace 0`: the window, and the cell's end-to-end metrics."""
    compiles = run.watch.count()
    setup_s = time.perf_counter() - T_START
    ns, attempted, failed = loop.measure(
        run.df.collect, run.check, seconds,
        min_queries=run.cell.traffic["min_queries"])
    values = dict(loop.order_statistics(ns), setup_s=setup_s)
    extra = {"queries": len(ns),
             "window_compiles": run.watch.count() - compiles}
    return values, attempted, failed, extra


def traced_run(run, seconds, devices, trace_dir, device):
    """`--trace 1`: a short profiled window of its own, reduced to the
    cell's per-layer metrics, the breakdown, and `busy_s`/`window_s` put
    into `device`."""
    import jax
    traffic = run.cell.traffic
    seconds = min(seconds, traffic["trace_seconds"])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # host spans, not every Python call
    options.enable_hlo_proto = False
    before, compiles = run.counters(), run.watch.count()
    answers = []
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        t0 = time.perf_counter()
        while (time.perf_counter() - t0 < seconds
               or len(answers) < traffic["trace_min_queries"]):
            with jax.profiler.TraceAnnotation(xplane.QUERY_SPAN):
                answers.append(run.df.collect())
    finally:
        jax.profiler.stop_trace()
    after, compiles = run.counters(), run.watch.count() - compiles
    failed = sum(not run.check(a) for a in answers)

    # the planner alone, on the warm path and the host's clock, many plans
    # to a reading
    plans, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < MIN_HOST_CLOCK_S:
        run.session.plan(run.df.plan)
        plans += 1
    spans = {PLAN_SPAN: (time.perf_counter() - t0, plans)}

    kind = devices[0].device_kind
    with open(os.path.join(run.cell.bench_dir, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks and devices[0].platform == "tpu":
        raise KeyError(f"peaks.json has no device kind {kind!r}")
    [pb] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    trace = xplane.load(pb)
    ev = Evidence(
        cell=run.cell, rows=run.rows,
        queries=len(answers),
        counters={k: v - before.get(k, 0) for k, v in after.items()},
        compiles=compiles, spans=spans,
        memory=[d.memory_stats() or {} for d in devices[:run.cell.chips]],
        trace=trace, peaks=peaks.get(kind, {}))
    values = {}
    for metric, spec in run.cell.per_layer:
        reader = cells.load_module(run.cell.bench_dir, "readers",
                                   spec["reader"])
        value = reader.read(ev, **spec.get("args", {}))
        if value is not None:   # nothing to read: left out of the line
            values[metric["name"]] = value
    busy = xplane.busy_per_chip(trace, run.cell.chips)
    device.update(busy_s=sum(busy) / len(busy) / 1e9 if busy else 0.0,
                  window_s=trace.window_s)
    breakdown = {"device_ops": [], "idle_gaps": []}
    if busy:   # off the chip the trace has no device plane
        busiest = trace.devices[busy.index(max(busy))]
        breakdown = {"device_ops": xplane.top_ops(trace, busiest),
                     "idle_gaps": xplane.attribute_gaps(trace, busiest)[:10]}
    extra = {"queries": len(answers), "window_compiles": compiles,
             "breakdown": breakdown,
             # every timer and counter the session moved, per query: what a
             # `session_metric` reader can be pointed at
             "session_metrics_per_query": {
                 k: v / len(answers) for k, v in sorted(ev.counters.items())
                 if v}}
    return values, len(answers), failed, extra


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help="lineitem rows for the rehearsal off the chip "
                         "(default: the configuration's); such a run "
                         "always ends correct: false")
    ap.add_argument("--trace-dir", default="",
                    help="keep the traced run's profile here (default: a "
                         "temporary directory, removed)")
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)

    if args.rows and cell.chips > 1 \
            and os.environ.get("JAX_PLATFORMS") == "cpu":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = device["platform"] == "tpu" and len(devices) >= cell.chips
    if not on_chip:
        print(f"chipbench: {cell.name} needs {cell.chips} tpu device(s), "
              f"JAX reports {device}", file=sys.stderr, flush=True)
        if not args.rows:
            return 2
    # the program under test: in a directory that holds only the benchmark
    # the run dies here, before anything that reads as a result
    import spark_rapids_tpu  # noqa: F401

    with tempfile.TemporaryDirectory(prefix="chipbench_") as scratch:
        run = Run(cell, args.seed, args.rows, scratch)   # under TMPDIR
        if args.trace:
            trace_dir = args.trace_dir or os.path.join(scratch, "trace")
            values, attempted, failed, extra = traced_run(
                run, args.seconds, devices, trace_dir, device)
            metrics = [m for m, _ in cell.per_layer]
        else:
            values, attempted, failed, extra = measured_run(
                run, args.seconds)
            metrics = cell.end_to_end
        fallbacks = run.counters().get("numCpuFallbacks", 0)
    device["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices[:cell.chips])
    real = on_chip and not args.rows
    correct = bool(real and run.warm_ok and attempted > 0 and failed == 0
                   and fallbacks == 0)
    reported = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in metrics if m["name"] in values}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": reported, "device": device}
    if not real:
        # off the chip no number stands under a metric's name
        line["metrics"] = {}
        line["rehearsal"] = {"would_report": sorted(reported),
                             "answers_right": bool(run.warm_ok
                                                   and failed == 0)}
        extra.pop("breakdown", None)
    line.update(extra, workload=cell.name, seed=args.seed,
                warm_answer_right=run.warm_ok, numCpuFallbacks=fallbacks,
                worst_double_rel_err=run.worst_err)
    print(json.dumps(line), flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
