"""The reader kind `launch_owner` on made-up events: a launch on the chip
goes to the span of the program its HOST CALL was made in, on the calling
thread, however late it starts; and the five metrics that read the
operators' pull spans load for the cells that list them."""
import json
import os

import pytest

import cells
import xplane
from test_readers import evidence, read

with open(os.path.join(os.path.dirname(cells.BENCH_DIR),
                       "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
READER = cells.load_module(cells.BENCH_DIR, "readers", "launch_owner")

AGG, JOIN, SCAN = ("srt:op:TpuHashAggregateExec@1",
                   "srt:op:TpuBroadcastHashJoinExec@2",
                   "srt:op:TpuFileScanExec@4")


def made_up_trace():
    """One query of 2000 ns.  The querying thread pulls the aggregate, which
    pulls the join, which pulls the scan; the scan's two columns decode on
    a pool thread under `srt:scan_column`.  The chip runs everything late:
    the join's probe starts after the join's pull has ended, inside the
    aggregate's own time."""
    query = [(0, 2000, xplane.QUERY_SPAN), (10, 1900, "srt:execute"),
             (20, 1500, AGG), (30, 600, JOIN), (40, 300, SCAN),
             (310, 330, "PjitFunction(join.hashjoin_probe)"),
             (312, 328, "PjitFunction(join.hashjoin_probe)"),  # jax's twin
             (400, 410, "PjitFunction(_take)"),                # the join's
             (700, 900, "srt:agg_update"),
             (710, 730, "PjitFunction(agg.hashaggregate_bucket)"),
             (1000, 1010, "PjitFunction(_take)"),              # the agg's
             (1600, 1700, "srt:d2h"),
             (1910, 1990, "srt:finish"), (1920, 1980, "srt:metrics_fold"),
             (1930, 1940, "PjitFunction(_reduce_sum)"),
             (1995, 1998, "PjitFunction(convert_element_type)")]
    pool = [(50, 150, "srt:scan_column"), (160, 280, "srt:scan_column"),
            (60, 70, "PjitFunction(_take)"),                   # the scan's
            (170, 180, "PjitFunction(scan.pq_sdict)")]
    launches = [(100, 200, "jit__take(5)"),
                (200, 260, "jit_scan.pq_sdict(7)"),
                (800, 1100, "jit_join.hashjoin_probe(77)"),   # join closed
                (1100, 1140, "jit__take(5)"),
                (1140, 1400, "jit_agg.hashaggregate_bucket(3)"),
                (1400, 1450, "jit__take(5)"),
                (1950, 1960, "jit__reduce_sum(9)"),
                (1999, 2010, "jit_convert_element_type(1)")]
    return xplane.Trace(
        devices=[xplane.Device(0, ops=[(s, e, "fusion.1")
                                       for s, e, _ in launches],
                               launches=launches)],
        threads=[sorted(query), sorted(pool)], t0=0, t1=2000, queries=1)


def owners(trace):
    return {(ln.start, ln.program): ln.owner
            for ln in READER.owned_launches(trace)}


def test_calls_on_two_threads_match_one_chips_launches_in_order():
    got = owners(made_up_trace())
    # the three `_take` are called at 60 (pool), 400 and 1000 (querying
    # thread) and run at 100, 1100 and 1400: matched in order over ALL
    # threads, each to its own thread's span
    assert got[(100, "jit__take")] == "srt:scan_column"
    assert got[(1100, "jit__take")] == JOIN
    assert got[(1400, "jit__take")] == AGG
    # a pool thread's call is the pool thread's, whatever the querying
    # thread was in (the scan's pull) when it was made
    assert got[(200, "jit_scan.pq_sdict")] == "srt:scan_column"


def test_a_launch_that_starts_after_its_callers_span_closed_is_still_its():
    got = owners(made_up_trace())
    # called at 310 inside the join's pull (30-600), runs 800-1100 while
    # the querying thread is in the aggregate's `srt:agg_update`
    assert got[(800, "jit_join.hashjoin_probe")] == JOIN
    # a hand-placed span inside an operator's pull does not take the
    # launch from the operator
    assert got[(1140, "jit_agg.hashaggregate_bucket")] == AGG
    # outside every operator: the innermost other span of the program
    assert got[(1950, "jit__reduce_sum")] == "srt:metrics_fold"
    # inside nothing but the harness's span: no owner
    assert got[(1999, "jit_convert_element_type")] == "unowned"


def test_the_metrics_read_device_time_by_owner_per_query():
    ev = evidence(made_up_trace(), "tpch_q1_parquet")
    assert read("agg_owned_device_ms", ev) == pytest.approx(
        (260 + 50) / 1e6)
    assert read("scan_owned_device_ms", ev) == pytest.approx(
        (100 + 60) / 1e6)
    ev = evidence(made_up_trace(), "tpcds_q52_star_sf10")
    assert read("join_owned_device_ms", ev) == pytest.approx(
        (300 + 40) / 1e6)
    # the last launch starts inside the window and counts whole
    total = 100 + 60 + 300 + 40 + 260 + 50 + 10 + 11
    assert read("unowned_launch_share", ev) == pytest.approx(
        100 * 11 / total)
    # idle 0-100, 260-800, 1450-1950, 1960-1999 = 1179 ns; the querying
    # thread is outside every span but `srt:execute` for 0-20, 1500-1600,
    # 1700-1910 and 1990-1999: of the idle time 20 + 100 + 210 + 9
    assert read("operator_unattributed_share", ev) == pytest.approx(
        100 * 339 / 1179)
    assert read("host_unattributed_share", ev) == pytest.approx(
        100 * (10 + 10 + 9) / 1179)


def test_eager_and_count_and_an_unknown_what():
    ev = evidence(made_up_trace())
    assert READER.read(ev, "Aggregate", "count") == 2.0
    assert READER.read(ev, "Aggregate", "count", eager=True) == 1.0
    assert READER.read(ev, "Aggregate", "device_ms", eager=False) == \
        pytest.approx(260 / 1e6)
    assert READER.read(ev, "^srt:", "count", eager=True) == 4.0
    assert READER.read(ev, "no such owner") == 0.0
    with pytest.raises(ValueError):
        READER.read(ev, "Aggregate", "seconds")


def test_unequal_counts_leave_a_program_unowned():
    t = made_up_trace()
    # one `_take` more on the chip than the host called: none of them can
    # be matched, every other program still is
    t.devices[0].launches.append((1460, 1470, "jit__take(5)"))
    got = owners(t)
    assert [got[k] for k in sorted(got) if k[1] == "jit__take"] == \
        ["unowned"] * 4
    assert got[(800, "jit_join.hashjoin_probe")] == JOIN
    ev = evidence(t, "tpcds_q52_star_sf10")
    assert read("join_owned_device_ms", ev) == pytest.approx(300 / 1e6)
    assert read("unowned_launch_share", ev) > 20


def test_an_executable_shared_by_two_functions_is_matched_with_them():
    t = made_up_trace()
    # the compile cache hands `_reduce_sum` over one element the executable
    # `broadcast_in_dim` compiled: the launch shows under the other's name.
    # `_reduce_sum` then has a call more than launches, `broadcast_in_dim`
    # a launch more than calls; together they agree and match in order
    t.threads[0] = sorted(t.threads[0] + [
        (1100, 1110, "PjitFunction(broadcast_in_dim)"),    # the agg's
        (1200, 1210, "PjitFunction(_reduce_sum)")])         # the agg's
    t.devices[0].launches += [(1451, 1452, "jit_broadcast_in_dim(4)"),
                              (1453, 1454, "jit_broadcast_in_dim(4)")]
    got = owners(t)
    assert got[(1451, "jit_broadcast_in_dim")] == AGG
    assert got[(1453, "jit_broadcast_in_dim")] == AGG
    assert got[(1950, "jit__reduce_sum")] == "srt:metrics_fold"
    assert READER.report(t)["unmatched_programs_calls_launches"] == {
        "jit__reduce_sum": [2, 1], "jit_broadcast_in_dim": [1, 2]}


def test_two_unrelated_programs_each_off_by_one_read_unowned(monkeypatch):
    t = made_up_trace()
    # a `_take` called before the trace began runs inside it, and a
    # `_where` called at its end runs after it: a launch more than calls,
    # a call more than launches, and together the numbers agree.  Matched
    # in order together every `_take` goes to the call AFTER its own
    t.devices[0].launches.insert(0, (5, 8, "jit__take(5)"))
    t.threads[0] = sorted(t.threads[0] + [(1996, 1997,
                                            "PjitFunction(_where)")])
    got = owners(t)
    takes = sorted(k for k in got if k[1] == "jit__take")
    # 100 <- the call at 400, 1100 <- 1000, 1400 <- 1996: none starts
    # between the launches of its thread's matched calls around it (the
    # probe's 310 -> 800, the bucket's 710 -> 1140, 1995 -> 1999)
    assert [got[k] for k in takes[1:]] == ["unowned"] * 3
    # 5 <- the pool thread's call at 60: it starts before its call.  The
    # chips' clock runs ahead of the host's by an offset of the trace, so
    # how early is too early is read from the trace's own launches: here a
    # third thread's 400, each 3 ns after its call
    assert got[takes[0]] == "srt:scan_column"
    t.threads.append([(10 * i, 10 * i + 2, "PjitFunction(_iota)")
                      for i in range(400)])
    t.devices[0].launches += [(10 * i + 3, 10 * i + 4, "jit__iota(6)")
                              for i in range(400)]
    monkeypatch.setattr(READER, "CLOCK_JITTER_NS", 10)
    got = owners(t)
    assert [got[k] for k in takes] == ["unowned"] * 4
    # the 400 found their calls (and no span of the program around them)
    assert READER.report(t)["launches_no_call_found"] == 4.0
    # what was matched by its own name is as it was
    assert got[(800, "jit_join.hashjoin_probe")] == JOIN
    assert got[(1140, "jit_agg.hashaggregate_bucket")] == AGG
    ev = evidence(t, "tpcds_q52_star_sf10")
    assert read("join_owned_device_ms", ev) == pytest.approx(300 / 1e6)


def test_equal_numbers_out_of_call_order_read_unowned():
    t = made_up_trace()
    # `sort.window` lost a call before the trace and a launch after it:
    # two calls, two launches, each launch the call's BEFORE.  The chip
    # runs one thread's launches in the order they were called: 790 cannot
    # be the call at 500's when 800 and 1100 were called before it, nor
    # 1110 the call at 1200's, and which side of such a pair is wrong the
    # trace does not say
    t.threads[0] = sorted(t.threads[0] + [
        (500, 505, "PjitFunction(sort.window)"),
        (1200, 1205, "PjitFunction(sort.window)")])
    t.devices[0].launches += [(790, 795, "jit_sort.window(2)"),
                              (1110, 1115, "jit_sort.window(2)")]
    got = owners(t)
    assert {got[k] for k in got if k[1] == "jit_sort.window"} == {"unowned"}
    assert got[(800, "jit_join.hashjoin_probe")] == "unowned"
    assert got[(1140, "jit_agg.hashaggregate_bucket")] == "unowned"
    # the pool thread's and what came after are in order
    assert got[(200, "jit_scan.pq_sdict")] == "srt:scan_column"
    assert got[(1950, "jit__reduce_sum")] == "srt:metrics_fold"


def test_a_function_jitted_under_its_own_name_matches_its_launches():
    t = made_up_trace()
    t.threads[0] = sorted(t.threads[0] + [
        (1300, 1310, "PjitFunction(jit(stage.wholeStage-4))")])
    t.devices[0].launches.append((1455, 1460, "jit_stage.wholeStage-4(9)"))
    assert owners(t)[(1455, "jit_stage.wholeStage_4")] == AGG


def test_a_trace_without_operator_spans_or_device_reads_nothing():
    t = made_up_trace()
    t.threads[0] = [e for e in t.threads[0]
                    if not e[2].startswith("srt:op:")]
    ev = evidence(t, "tpch_q1_parquet")
    for metric in ("unowned_launch_share", "agg_owned_device_ms",
                   "scan_owned_device_ms"):
        assert read(metric, ev) is None, metric
    # the parent: `srt:execute` is still there, so this one is read
    assert read("operator_unattributed_share", ev) is not None
    t = made_up_trace()
    t.devices = []
    ev = evidence(t, "tpch_q1_parquet")
    for metric in ("unowned_launch_share", "agg_owned_device_ms",
                   "operator_unattributed_share"):
        assert read(metric, ev) is None, metric


def test_the_windows_edges_clip_launches_by_their_start_on_the_chip():
    t = made_up_trace()
    t.t0, t.t1 = 150, 1400
    ev = evidence(t, "tpch_q1_parquet")
    # 100-200 started before the window; 1400-1450 starts at its end; the
    # match itself is made over the whole trace, so the `_take` inside
    # still finds its call
    assert read("scan_owned_device_ms", ev) == pytest.approx(60 / 1e6)
    assert read("agg_owned_device_ms", ev) == pytest.approx(260 / 1e6)
    assert READER.read(ev, "Join", "count", eager=True) == 1.0


def test_the_report_for_people_sums_to_all_launches():
    out = READER.report(made_up_trace())
    assert out["device_ms_owners_sum"] == pytest.approx(
        out["device_ms_all_launches"])
    assert out["owners"][JOIN]["eager_launches"] == 1.0
    assert out["owners"][JOIN]["launches"] == 2.0
    assert out["owners"]["srt:scan_column"]["host_call_ms"] == \
        pytest.approx(20 / 1e6)
    assert out["unmatched_programs_calls_launches"] == {}
    assert out["launch_after_call_ms"]["min"] == pytest.approx(4 / 1e6)
    assert sum(out["idle_s_by_owner"].values()) == pytest.approx(1179 / 1e9)
    # idle while the querying thread is in the join's own pull (30-40 and
    # 300-600; the scan's pull 40-300 is the scan's)
    assert out["idle_s_by_owner"][JOIN] == pytest.approx(310 / 1e9)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_the_five_metrics_load_for_the_cells_that_list_them(workload):
    reported = {m["name"]: spec for m, spec in
                cells.load_cell(workload).per_layer}
    assert {"operator_unattributed_share", "unowned_launch_share"} <= \
        set(reported)
    assert reported["unowned_launch_share"]["reader"] == "launch_owner"
    assert reported["operator_unattributed_share"]["reader"] == \
        "idle_outside_spans"
    for owned, named in (("agg_owned_device_ms", "agg_device_ms"),
                         ("join_owned_device_ms", "join_device_ms"),
                         ("scan_owned_device_ms", "scan_device_ms")):
        assert (owned in reported) == (named in reported), (owned, workload)
        if owned in reported:
            assert reported[owned]["reader"] == "launch_owner"
