"""The trace reduction: interval arithmetic on made-up events, and the whole
reduction on a small trace recorded on a v5e (`data/q6_small.xplane.pb.gz`:
`tpch_q6_resident` at 200,000 rows, a window of a few queries; chip run,
PR 24)."""
import os

import pytest

import xplane

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "q6_small.xplane.pb.gz")


def make_trace(ops, thread, launches=()):
    spans = [e for e in thread if e[2] == xplane.QUERY_SPAN]
    return xplane.Trace(
        devices=[xplane.Device(0, ops=sorted(ops), launches=list(launches))],
        threads=[sorted(thread)], t0=min(s[0] for s in spans),
        t1=max(s[1] for s in spans), queries=len(spans))


def test_merged_is_a_union_clipped_to_the_window():
    ivs = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (35, 36, "d"),
           (90, 200, "e")]
    assert xplane.merged(ivs, 2, 100) == [(2, 20), (30, 40), (90, 100)]


def test_busy_is_the_union_not_the_sum():
    thread = [(0, 100, xplane.QUERY_SPAN)]
    t = make_trace([(10, 30, "fusion.1"), (20, 40, "all-to-all.2"),
                    (90, 150, "fusion.1")], thread)
    assert xplane.busy_ns(t, t.devices[0]) == 40     # 10-40 and 90-100
    assert xplane.busy_ns(t, t.devices[0], "^all-to-all") == 20
    assert xplane.idle_gaps(t, t.devices[0]) == [(0, 10), (40, 90)]


def test_innermost_segments_flatten_nested_spans():
    thread = [(0, 100, "outer"), (10, 40, "mid"), (20, 30, "inner"),
              (60, 70, "late")]
    assert xplane.innermost_segments(thread) == [
        (0, 10, "outer"), (10, 20, "mid"), (20, 30, "inner"),
        (30, 40, "mid"), (40, 60, "outer"), (60, 70, "late"),
        (70, 100, "outer")]


def test_idle_gaps_go_to_what_the_host_was_in():
    thread = [(0, 100, xplane.QUERY_SPAN), (10, 50, "np.asarray(x)"),
              (100, 130, "harness"), (130, 200, xplane.QUERY_SPAN)]
    t = make_trace([(40, 60, "fusion.3"), (150, 200, "fusion.3")], thread,
                   launches=[(39, 60, "jit_whole(1)"), (149, 200, "jit_whole(1)"),
                             (300, 310, "outside")])
    gaps = dict(xplane.attribute_gaps(t, t.devices[0]))
    # idle 0-40 and 60-150: 10+40+20 in collect, 30 in np.asarray, 30 harness
    assert gaps == {"chipbench_collect": pytest.approx(70e-9),
                    "np.asarray_x": pytest.approx(30e-9),
                    "harness": pytest.approx(30e-9)}
    assert sum(gaps.values()) == pytest.approx(
        (200 - xplane.busy_ns(t, t.devices[0])) / 1e9)
    assert xplane.launches(t, t.devices[0]) == 2
    assert xplane.top_ops(t, t.devices[0]) == [
        ["fusion_x2", pytest.approx(70e-9)]]


def test_names_keep_to_a_metric_names_characters():
    assert xplane.clean("PjitFunction(reduce sum)") == \
        "PjitFunction_reduce_sum"
    assert len(xplane.clean("x" * 200)) <= 56


def write_tpu_layout_trace(path):
    """A two-chip trace file in the layout a TPU's profile has (planes
    `/device:TPU:<n>` with lines `XLA Ops` and `XLA Modules`, host threads
    under `/host:CPU`), built from the profiler's own protobuf."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()

    def add_line(plane, name, events):
        line = plane.lines.add(name=name, id=len(plane.lines) + 1,
                               timestamp_ns=1000)
        for start_ns, dur_ns, event_name in events:
            mid = next((k for k, v in plane.event_metadata.items()
                        if v.name == event_name), None)
            if mid is None:
                mid = len(plane.event_metadata) + 1
                plane.event_metadata[mid].id = mid
                plane.event_metadata[mid].name = event_name
            line.events.add(metadata_id=mid, offset_ps=start_ns * 1000,
                            duration_ps=dur_ns * 1000)

    host = space.planes.add(name="/host:CPU", id=1)
    add_line(host, "python3", [(0, 1000, xplane.QUERY_SPAN),
                               (100, 500, "np.asarray(jax.Array)"),
                               (1100, 1000, xplane.QUERY_SPAN)])
    add_line(host, "other thread", [(0, 5000, "background")])
    for d in range(2):
        dev = space.planes.add(name=f"/device:TPU:{d}", id=2 + d)
        add_line(dev, "XLA Modules", [(200, 300, "jit_whole(123)"),
                                      (1300, 300, "jit_whole(123)")])
        add_line(dev, "XLA Ops", [(200, 100, "fusion.1"),
                                  (350, 100 + 50 * d, "all-to-all.2"),
                                  (1300, 300, "fusion.1")])
        add_line(dev, "Steps", [(0, 5000, "0")])   # not an op: ignored
    space.planes.add(name="/device:TPU:0 SparseCore 0", id=9)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_a_file_in_the_tpu_layout_reduces_chip_by_chip(tmp_path,
                                                       pending_bench_dir):
    import cells
    import run
    path = str(tmp_path / "two_chips.xplane.pb")
    write_tpu_layout_trace(path)
    t = xplane.load(path)
    assert (t.queries, t.t0, t.t1) == (2, 1000, 3100)
    assert [d.index for d in t.devices] == [0, 1]
    assert xplane.busy_per_chip(t, 2) == [500, 550]
    assert xplane.busy_per_chip(t, 2, "^all-to-all") == [100, 150]
    assert xplane.launches(t, t.devices[0]) == 2
    assert dict(xplane.attribute_gaps(t, t.devices[1])) == {
        "chipbench_collect": pytest.approx(1.2e-6),
        "np.asarray_jax.Array": pytest.approx(0.25e-6),
        "no_host_span": pytest.approx(0.1e-6)}
    # the readers over it: the shares the mesh cell reports
    cell = cells.load_cell("tpch_q3_join_mesh4", bench_dir=pending_bench_dir)
    ev = run.Evidence(cell=cell, rows=cell.rows(), queries=t.queries,
                      counters={}, compiles=0, spans={}, memory=[], trace=t,
                      peaks={"hbm_bytes_per_s": 819e9})
    got = {}
    for metric, spec in cell.per_layer:
        reader = cells.load_module(cells.BENCH_DIR, "readers",
                                   spec["reader"])
        got[metric["name"]] = reader.read(ev, **spec.get("args", {}))
    window = 2100
    assert got["collective_share"] == pytest.approx(100 * 150 / window)
    assert got["device_idle_share"] == pytest.approx(100 - 100 * 500 / window)
    assert got["dispatches_per_query"] == 1.0
    assert got["window_compiles"] == 0.0
    assert got["d2h_mb_per_query"] is None   # nothing to read: left out
    # over the two chips the file has
    least_s = cell.query.bytes_needed(cell.rows()) / (819e9 * 2)
    assert got["hbm_roofline_share"] == pytest.approx(
        100 * least_s / (525e-9 / 2))


def test_the_recorded_v5e_trace_reduces_to_what_was_read_by_hand():
    t = xplane.load(RECORDED)
    [dev] = t.devices
    assert (t.queries, len(dev.ops), len(dev.async_ops)) == (7, 721, 21)
    assert t.window_s == pytest.approx(0.035358988)
    # busy union: 0.309 ms of a 35.4 ms window, the device idle 99% at
    # 200,000 rows; gaps and busy time make up the window
    busy = xplane.busy_ns(t, dev)
    assert busy == 308797
    gaps = xplane.idle_gaps(t, dev)
    assert sum(e - s for s, e in gaps) + busy == t.t1 - t.t0
    # six executables a query: the whole-stage program and five eager ones
    assert xplane.launches(t, dev) / t.queries == 6
    assert {name.split("(")[0] for _, _, name in dev.launches} >= {
        "jit_whole", "jit__reduce_sum", "jit_convert_element_type"}
    # op naming: kind, custom-call target, result shape without layout
    labels = {op[2] for op in dev.ops}
    assert "custom-call:X64SplitHigh f32[262144]" in labels
    assert "and_and_fusion pred[262144]" in labels
    assert not any("{" in label or label.startswith("%") for label in labels)
    top = xplane.top_ops(t, dev)
    assert top[0][0] == "custom-call_X64SplitLow_f32_262144_x21"
    assert top[0][1] == pytest.approx(3.8496e-05)
    # idle attribution: the host waits in the collect and in np.asarray
    attributed = xplane.attribute_gaps(t, dev)
    assert [n for n, _ in attributed[:2]] == ["chipbench_collect",
                                              "np.asarray_jax.Array"]
    assert sum(s for _, s in attributed) == pytest.approx(
        (t.t1 - t.t0 - busy) / 1e9)
    # asynchronous copies are in flight, not busy time; no collective here
    assert xplane.busy_ns(t, dev, "^copy") == 81505
    assert xplane.busy_ns(t, dev, "^(all-to-all|all-reduce)") == 0
