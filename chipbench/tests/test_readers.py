"""The two reader kinds that read the program's own names: `module_time`
(executables named `jit_<layer>.<role>` on the `XLA Modules` line) and
`idle_outside_spans` (the `srt:` spans of the querying thread against the
chip's idle gaps), on made-up events, on the trace recorded before the
program had such names (`data/q6_small.xplane.pb.gz`, PR 24), and through
the metric files that point at them."""
import json
import os
import subprocess
import sys

import pytest

import cells
import run
import xplane

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "q6_small.xplane.pb.gz")
with open(os.path.join(os.path.dirname(cells.BENCH_DIR),
                       "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def read(metric, ev):
    spec = cells.load_json(cells.BENCH_DIR, "layer_metrics", metric)
    reader = cells.load_module(cells.BENCH_DIR, "readers", spec["reader"])
    return reader.read(ev, **spec.get("args", {}))


def evidence(trace, workload="tpch_q6_resident", counters=None):
    cell = cells.load_cell(workload)
    return run.Evidence(cell=cell, rows=cell.rows(), queries=trace.queries,
                        counters=counters or {}, compiles=0, spans={},
                        memory=[], trace=trace, peaks={})


def made_up_trace():
    """Two queries of 1000 ns; the chip busy 300-500 and 1300-1500."""
    thread = [(0, 1000, xplane.QUERY_SPAN), (1000, 2000, xplane.QUERY_SPAN),
              (10, 100, "srt:plan"), (100, 900, "srt:execute"),
              (200, 520, "srt:agg_whole_stage"),
              (210, 230, "PjitFunction(agg.whole_stage)"),
              (600, 850, "srt:d2h"), (610, 840, "np.asarray(jax.Array)"),
              (900, 960, "srt:finish"), (910, 950, "srt:metrics_fold"),
              (1010, 1110, "srt:plan"), (1110, 1900, "srt:execute"),
              (1210, 1230, "PjitFunction(agg.whole_stage)"),
              (1212, 1228, "PjitFunction(agg.whole_stage)"),   # jax's twin
              (1600, 1800, "srt:d2h"), (1900, 1980, "srt:finish")]
    launches = [(300, 500, "jit_agg.whole_stage(77)"),
                (1300, 1500, "jit_agg.whole_stage(77)"),
                (520, 530, "jit_convert_element_type(5)"),
                (930, 940, "jit__reduce_sum(9)"),
                (1520, 1530, "jit_convert_element_type(5)"),
                (1400, 1450, "jit_scan.pq_bp(3)"),
                (2500, 2600, "jit_agg.whole_stage(77)")]   # after the window
    return xplane.Trace(
        devices=[xplane.Device(0, ops=[(300, 500, "fusion.1"),
                                       (1300, 1500, "fusion.1")],
                               launches=launches)],
        threads=[sorted(thread), [(0, 2000, "srt:scan_decode")]],
        t0=0, t1=2000, queries=2)


def test_module_time_sums_the_named_programs_per_query():
    ev = evidence(made_up_trace())
    assert read("agg_device_ms", ev) == pytest.approx(400 / 1e6 / 2)
    assert read("scan_device_ms", ev) == pytest.approx(50 / 1e6 / 2)
    assert read("join_device_ms", ev) is None       # nothing so named
    # three launches are no program of the kernel cache's: eager ops
    assert read("eager_dispatches_per_query", ev) == 1.5
    reader = cells.load_module(cells.BENCH_DIR, "readers", "module_time")
    assert reader.read(ev, r"^jit_agg\.", what="count") == 1.0
    with pytest.raises(ValueError):
        reader.read(ev, r"^jit_agg\.", what="seconds")


def test_the_spmd_programs_count_for_their_operator():
    t = made_up_trace()
    t.devices[0].launches = [(100, 400, "jit_dist.join_probe(1)"),
                             (400, 450, "jit_join.hashjoin_build(2)"),
                             (500, 600, "jit_dist.agg_partial(3)"),
                             (700, 710, "jit_dist.sort(4)")]
    ev = evidence(t)
    assert read("join_device_ms", ev) == pytest.approx(350 / 1e6 / 2)
    assert read("agg_device_ms", ev) == pytest.approx(100 / 1e6 / 2)
    assert read("eager_dispatches_per_query", ev) == 0.0   # none: a count


def test_idle_outside_spans_is_the_idle_time_no_program_span_covers():
    ev = evidence(made_up_trace())
    # idle: 0-300, 500-1300, 1500-2000 = 1600 ns; outside any srt: span on
    # the querying thread (the other thread's span does not count): 0-10,
    # 960-1010, 1980-2000 = 80 ns
    assert read("host_unattributed_share", ev) == pytest.approx(
        100 * 80 / 1600)
    t = made_up_trace()
    t.threads[0] = [e for e in t.threads[0] if not e[2].startswith("srt:")]
    assert read("host_unattributed_share", evidence(t)) is None


def test_span_metrics_read_the_programs_spans_by_name():
    ev = evidence(made_up_trace())
    assert read("plan_span_ms", ev) == pytest.approx((90 + 100) / 2 / 1e6)
    assert read("d2h_ms", ev) == pytest.approx((250 + 200) / 2 / 1e6)
    assert read("finish_ms", ev) == pytest.approx((60 + 80) / 2 / 1e6)
    ev = evidence(made_up_trace(), "tpch_q6_parquet",
                  {"scanTime": 0.5, "iciBytesMoved": 3e6})
    assert read("scan_host_ms", ev) == pytest.approx(250.0)
    assert read("ici_mb_per_query", ev) == pytest.approx(1.5)


def test_the_trace_recorded_before_the_names_reads_as_all_eager():
    """PR 24's trace: `jit_whole`, `jit__reduce_sum`, ... and an
    `agg_whole_stage` span without the prefix.  What the new readers give
    on a program without the names, as the parent commit is."""
    ev = evidence(xplane.load(RECORDED))
    assert read("eager_dispatches_per_query", ev) == 6.0
    assert read("eager_dispatches_per_query", ev) == \
        read("dispatches_per_query", ev)
    for metric in ("agg_device_ms", "scan_device_ms", "join_device_ms",
                   "host_unattributed_share", "plan_span_ms", "d2h_ms",
                   "finish_ms", "scan_host_ms", "ici_mb_per_query"):
        assert read(metric, ev) is None, metric


def test_no_device_plane_leaves_the_metrics_out():
    t = made_up_trace()
    t.devices = []
    ev = evidence(t)
    for metric in ("eager_dispatches_per_query", "agg_device_ms",
                   "host_unattributed_share"):
        assert read(metric, ev) is None


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_the_metrics_listed_for_it(workload):
    cell = cells.load_cell(workload)
    reported = {m["name"] for m, _ in cell.per_layer}
    assert reported >= {"plan_span_ms", "d2h_ms", "finish_ms",
                        "host_unattributed_share",
                        "eager_dispatches_per_query"}
    listed = {m["name"] for m in BENCH["per_layer"]
              if workload in m.get("workloads", ())}
    assert listed <= reported
    assert ("join_device_ms" in reported) == (workload ==
                                              "tpch_q3_join_mesh4")
    assert ("scan_device_ms" in reported) == (workload == "tpch_q6_parquet")


def test_the_mesh_cell_is_in_benchmark_json_as_it_waited():
    with open(os.path.join(cells.BENCH_DIR, "pending",
                           "tpch_q3_join_mesh4.json")) as f:
        pending = json.load(f)
    for key in ("configs", "workloads", "per_layer"):
        for entry in pending[key]:
            assert entry in BENCH[key], entry["name"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_the_mesh_cell_rehearses_from_the_real_benchmark_json():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(cells.BENCH_DIR))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", "tpch_q3_join_mesh4", "--seed", str(2**31 + 29),
         "--seconds", "0.5", "--trace", "1", "--rows", "50000"],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 1, p.stderr[-2000:]
    line = json.loads(p.stdout.splitlines()[-1])
    assert line["device"] == {**line["device"], "platform": "cpu",
                              "count": 4}
    assert line["rehearsal"]["answers_right"] is True
    assert line["numCpuFallbacks"] == 0 and line["window_compiles"] == 0
    assert set(line["rehearsal"]["would_report"]) >= {
        "ici_mb_per_query", "d2h_mb_per_query", "plan_span_ms", "d2h_ms",
        "finish_ms"}
    assert line["session_metrics_per_query"]["iciBytesMoved"] > 0


def test_breakdown_attributes_idle_time_and_device_time_by_name():
    import breakdown
    b = breakdown.breakdown(made_up_trace())
    assert b["traced_query_ms_median"] == pytest.approx(1000 / 1e6)
    # idle 0-300, 500-1300, 1500-2000 by the innermost program span
    assert b["idle_by_program_span_s"] == {
        "srt:execute": pytest.approx((100 + 80 + 50 + 190 + 100 + 100) / 1e9),
        "srt:d2h": pytest.approx((250 + 200) / 1e9),
        "srt:agg_whole_stage": pytest.approx((100 + 20) / 1e9),
        "srt:plan": pytest.approx((90 + 100) / 1e9),
        "srt:finish": pytest.approx((20 + 80) / 1e9),
        "srt:metrics_fold": pytest.approx(40 / 1e9)}
    assert b["idle_outside_program_spans_s"] == pytest.approx(80 / 1e9)
    assert b["idle_s"] == pytest.approx(1600 / 1e9)
    assert b["idle_by_any_span_s"]["np.asarray(jax.Array)"] == \
        pytest.approx(230 / 1e9)
    assert b["device_ms_per_query_by_program"] == {
        "jit_agg.whole_stage": pytest.approx(400 / 1e6 / 2),
        "jit_scan.pq_bp": pytest.approx(50 / 1e6 / 2),
        "jit_convert_element_type": pytest.approx(20 / 1e6 / 2),
        "jit__reduce_sum": pytest.approx(10 / 1e6 / 2)}
    assert b["launches_per_query_by_program"]["jit_agg.whole_stage"] == 1.0
    # the host's calls, by the span they were made in; each launch starts
    # 90 ns after its call (300 against 210, 1300 against 1210)
    assert b["jit_calls_per_query_by_program_span"] == {
        "srt:agg_whole_stage": {"agg.whole_stage": 0.5},
        "srt:execute": {"agg.whole_stage": 0.5}}
    assert b["launch_after_call_ms"] == {"min": pytest.approx(90 / 1e6),
                                         "median": pytest.approx(90 / 1e6)}
    assert b["program_spans"]["srt:scan_decode"] == {
        "ms_mean": pytest.approx(2000 / 1e6), "per_query": 0.5}
    old = breakdown.breakdown(xplane.load(RECORDED))
    assert old["idle_by_program_span_s"] == {}
    assert old["launches_per_query_by_program"]["jit_whole"] == 1.0
