"""What a profiler trace shows of a query (utils/tracing.named_range,
utils/kernel_cache.named_jit): the collect path's phases as `srt:` spans on
the profiler's clock, every compiled program under `<layer>.<role>`, and
the two counters the spans and the SPMD operators feed (`scanTime`,
`iciBytesMoved`)."""
import collections
import glob
import os
import re
import statistics
import sys
from pathlib import Path

import jax
import numpy as np
import pyarrow as pa
import pyarrow.parquet as papq
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from spark_rapids_tpu import types as T  # noqa: E402
from spark_rapids_tpu.engine import TpuSession  # noqa: E402
from spark_rapids_tpu.metrics import names as MN  # noqa: E402
from spark_rapids_tpu.parallel.distributed import default_quota  # noqa: E402
from spark_rapids_tpu.plan.logical import Window, col, functions as F  # noqa: E402
from spark_rapids_tpu.utils import kernel_cache as KC  # noqa: E402
from spark_rapids_tpu.utils.tracing import SPAN_PREFIX, named_range  # noqa: E402

pytestmark = pytest.mark.tracing

COLLECT = "test:collect"
PROGRAM_NAME = re.compile(r"^(scan|agg|join|sort|stage|dist|mem|expr|obs)\.")
ROWS = 400_000


def lineitem(n=ROWS, seed=5):
    rng = np.random.default_rng(seed)
    return pa.table({
        "l_quantity": rng.integers(1, 51, n).astype(np.float64),
        "l_extendedprice": rng.uniform(900.0, 105000.0, n),
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_shipdate": rng.integers(8036, 10562, n).astype(np.int64)})


def q6(frame):
    return (frame.filter((col("l_shipdate") >= 8766)
                         & (col("l_shipdate") < 9131)
                         & (col("l_discount") >= 0.05)
                         & (col("l_discount") <= 0.07)
                         & (col("l_quantity") < 24.0))
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))


def traced(df, tmp_path, queries=5):
    """Warm `df`, then `queries` collects under the profiler, each inside a
    `test:collect` annotation.  -> per host thread, its events as
    (start_ns, end_ns, name, stats) by start."""
    for _ in range(2):
        df.collect()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(queries):
            with jax.profiler.TraceAnnotation(COLLECT):
                df.collect()
    finally:
        jax.profiler.stop_trace()
    [pb] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(pb)
    [host] = [p for p in data.planes if p.name == "/host:CPU"]
    return [sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                    e.name, dict(e.stats)) for e in line.events)
            for line in host.lines]


def q6_plan():
    session = TpuSession({"spark.rapids.sql.variableFloatAgg.enabled": "true"})
    return session, q6(session.from_arrow(lineitem())), 5


def streamed_plan(grouped=True):
    """An input past half of batchSizeBytes, so the aggregate streams."""
    session = TpuSession({
        "spark.rapids.sql.variableFloatAgg.enabled": "true",
        "spark.rapids.sql.reader.batchSizeRows": "65536",
        "spark.rapids.sql.batchSizeBytes": "12m"})
    frame = session.from_arrow(lineitem())
    df = (frame.filter(col("l_quantity") < 24.0).group_by(col("l_discount"))
          .agg(F.sum(col("l_extendedprice") * col("l_shipdate")).alias("v"))
          if grouped else q6(frame))
    return session, df, 3


def broadcast_join_plan(builds=1):
    rng = np.random.default_rng(31)
    session = TpuSession({})
    n = 20_000
    facts = session.from_arrow(pa.table({
        "a": rng.integers(0, 40, n).astype(np.int64),
        "b": rng.integers(0, 30, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64)}))
    dims = [session.from_arrow(pa.table({
        key: np.arange(50, dtype=np.int64),
        key + "_w": np.arange(50, dtype=np.int64) * 3}))
        for key in ("ka", "kb")[:builds]]
    df = facts.join(dims[0].filter(col("ka") < 25), on=col("a") == col("ka"))
    if builds == 2:
        df = df.join(dims[1], on=col("b") == col("kb"))
    return session, df.agg(F.sum(col("v")).alias("s")), 3


PLANS = {"q6": q6_plan, "streamed_grouped": streamed_plan,
         "broadcast_join": broadcast_join_plan}


@pytest.fixture(scope="module")
def traced_plan(tmp_path_factory):
    """-> f(name): (session, the host threads' events) of ONE traced run of
    `PLANS[name]`, shared by every test that reads that plan's trace: a
    profiler session and its queries are what these tests cost, and the
    other workers' tests feel it.  Nothing collects on the session after
    the trace, so `last_execution` is the last traced query's."""
    made = {}

    def get(name):
        if name not in made:
            session, df, queries = PLANS[name]()
            made[name] = session, traced(
                df, tmp_path_factory.mktemp(name), queries)
        return made[name]
    return get


def phases(threads):
    """-> per traced collect, (the collect's span, the program's spans
    inside it on the querying thread), and every thread's span names."""
    [thread] = [th for th in threads if any(e[2] == COLLECT for e in th)]
    out = []
    for c in (e for e in thread if e[2] == COLLECT):
        out.append((c, [e for e in thread if e[2].startswith(SPAN_PREFIX)
                        and c[0] <= e[0] and e[1] <= c[1]]))
    return out, {e[2] for th in threads for e in th}


def inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def check_phases(collects, session):
    for collect, spans in collects:
        by = {}
        for e in spans:
            by.setdefault(e[2], []).append(e)
        plan, begin, execute, finish, rows = (
            by["srt:" + n][0] for n in ("plan", "begin", "execute",
                                        "finish", "rows"))
        # one after the other, and the operators' spans inside the drain
        order = [plan, begin, execute, finish, rows]
        assert all(a[1] <= b[0] for a, b in zip(order, order[1:]))
        for name in ("srt:semaphore", "srt:d2h", "srt:agg_whole_stage"):
            assert all(inside(e, execute) for e in by[name]), name
        assert by["srt:semaphore"][0][1] <= by["srt:agg_whole_stage"][0][0]
        assert by["srt:agg_whole_stage"][0][1] <= by["srt:d2h"][0][0]
        for e in by.get("srt:metrics_fold", []):
            assert inside(e, finish)
        # host-known counts ride in the annotation
        assert int(by["srt:d2h"][0][3]["bytes"]) > 0
        assert int(rows[3]["rows"]) == 1
        assert execute[3]["q"] == finish[3]["q"]
    # the spans of one query share the journal's id
    last = collects[-1][1]
    assert {int(e[3]["q"]) for e in last if "q" in e[3]} == {
        session.last_execution.query_id}
    covered = []
    for collect, spans in collects:
        top = [e for e in spans
               if not any(o is not e and inside(e, o) for o in spans)]
        covered.append(sum(e[1] - e[0] for e in top)
                       / (collect[1] - collect[0]))
    assert statistics.median(covered) >= 0.95, covered


def test_a_warm_query_lies_under_the_programs_phase_spans(traced_plan):
    session, threads = traced_plan("q6")
    collects, names = phases(threads)
    assert len(collects) == 5
    check_phases(collects, session)
    # the one program of the query runs under its layer's name; what jax
    # names by itself is an eager op
    assert "PjitFunction(agg.whole_stage)" in names
    assert not {n for n in names
                if re.match(r"PjitFunction\((k|kern|whole\w*)\)", n)}


def test_a_parquet_query_adds_the_scans_span_and_timer(tmp_path):
    path = str(tmp_path / "lineitem.parquet")
    papq.write_table(lineitem(), path, compression="snappy")
    session = TpuSession({"spark.rapids.sql.variableFloatAgg.enabled": "true"})
    threads = traced(q6(session.read.parquet(path)), tmp_path / "trace")
    collects, names = phases(threads)
    check_phases(collects, session)
    decodes = [e for th in threads for e in th if e[2] == "srt:scan_decode"]
    assert decodes
    # each decode ends before the query that asked for it does
    assert all(any(c[0] <= e[0] and e[1] <= c[1] for c, _ in collects)
               for e in decodes if e[0] >= collects[0][0][0])
    agg = session.last_execution.aggregate()
    assert agg[MN.NUM_DEVICE_DECODED_COLUMNS] > 0
    # the timer is the span's own: one query's is part of the five's spans
    assert 0 < agg[MN.SCAN_TIME] <= sum(e[1] - e[0] for e in decodes) / 1e9


@pytest.mark.parametrize("grouped", [False, True], ids=["keyless", "grouped"])
def test_a_streamed_aggregate_marks_its_bail_and_spans_each_batch(
        tmp_path, traced_plan, grouped):
    """An input past half of batchSizeBytes: the whole-stage probe leaves a
    zero-length `srt:agg_whole_stage_bail` that says why.  The GROUPED
    streaming loop shrinks each batch under `srt:agg_shrink`, before and
    outside its `srt:agg_update`; the loop of an aggregate with no grouping
    keys has no shrink to span: one `srt:agg_update` a batch around one
    `agg.stream_step`, and no host read."""
    if grouped:
        session, threads = traced_plan("streamed_grouped")
    else:
        session, df, _ = streamed_plan(grouped=False)
        threads = traced(df, tmp_path, queries=2)
    collects, names = phases(threads)
    assert "PjitFunction(agg.whole_stage)" not in names
    assert ("PjitFunction(agg.stream_step)" in names) == (not grouped)
    batches = -(-ROWS // 65536)
    for collect, spans in collects:
        by = {}
        for e in spans:
            by.setdefault(e[2], []).append(e)
        [execute] = by["srt:execute"]
        [bail] = by["srt:agg_whole_stage_bail"]
        assert bail[3]["reason"] == "bytes" and int(bail[3]["batches"]) == 3
        shrinks, updates = by.get("srt:agg_shrink", []), by["srt:agg_update"]
        assert len(updates) == batches
        assert len(shrinks) == (batches if grouped else 0)
        assert all(inside(e, execute) for e in [bail] + shrinks + updates)
        assert bail[1] <= (shrinks or updates)[0][0]
        assert all(s[1] <= u[0] for s, u in zip(shrinks, updates))
    moved = session.last_execution.aggregate()
    assert moved[MN.AGG_STREAMED_BATCHES] == batches
    if grouped:
        # a live-row read and a bucket check a batch, and the last fold's
        # one read of its parts' counts
        assert moved[MN.AGG_HOST_SYNCS] == 2 * batches + 1
        assert moved[MN.AGG_FUSED_FOLDS] == 1
        assert moved.get(MN.AGG_SYNC_FREE_BATCHES, 0) == 0
    else:
        assert moved[MN.AGG_HOST_SYNCS] == 0
        assert moved[MN.AGG_SYNC_FREE_BATCHES] == batches


@pytest.mark.parametrize("builds", [1, 2], ids=["one_build", "two_builds"])
def test_a_broadcast_join_spans_its_collect_and_its_upload(
        tmp_path, traced_plan, builds):
    """A build side under `spark.sql.autoBroadcastJoinThreshold`: its
    exchange collects the child to the host under `srt:broadcast_collect`
    and hands it back to the device under `srt:broadcast_upload`, once a
    build side and query (every `collect()` plans anew), both inside
    `srt:execute` and before the join's own `srt:join_build`."""
    if builds == 1:
        session, threads = traced_plan("broadcast_join")
    else:
        session, df, _ = broadcast_join_plan(builds)
        threads = traced(df, tmp_path, queries=2)
    collects, names = phases(threads)
    assert [n for n in names if n.startswith("PjitFunction(join.")
            and n.endswith("_probe)")], names
    for collect, spans in collects:
        by = {}
        for e in spans:
            by.setdefault(e[2], []).append(e)
        [execute] = by["srt:execute"]
        gathers = by["srt:broadcast_collect"]
        uploads = by["srt:broadcast_upload"]
        assert len(gathers) == len(uploads) == builds
        assert all(inside(e, execute) for e in gathers + uploads)
        joins = by["srt:join_build"]
        assert len(joins) == builds
        for gather, upload, build in zip(gathers, uploads, joins):
            assert gather[1] <= upload[0] and upload[1] <= build[0]
            # the host form's bytes ride in the annotation: at capacity
            assert int(upload[3]["bytes"]) >= 64 * (2 * 9 + 1)
    moved = session.last_execution.aggregate()
    assert moved[MN.BROADCAST_ROWS] == (25 + 50 if builds == 2 else 25)
    assert moved[MN.BROADCAST_BYTES] == moved[MN.DATA_SIZE] > 0
    # per build side its live-row count, per join the probe's scalars
    assert moved[MN.JOIN_HOST_SYNCS] == 2 * builds


def test_a_window_over_a_rollup_spans_its_launch_and_counts_at_capacity(
        tmp_path):
    """Query 36's shape in small: the window kernel's one launch a query
    sits in a `srt:window` span (opened after the child has been drained,
    so the aggregate's spans lie before it, not inside), with `rows` at
    capacity and `batches` in the annotation; the Expand's fan-out, the
    bucket update's batches and the window's rows are host counters."""
    rng = np.random.default_rng(33)
    session = TpuSession({"spark.rapids.sql.variableFloatAgg.enabled":
                          "true"})
    n = 6_000
    facts = session.from_arrow(pa.table({
        # 40 x 10 pairs and the subtotals: 441 groups, which the bucket
        # whole-stage program answers in 14 dense passes
        "a": [f"a{i}" for i in rng.integers(0, 40, n)],
        "b": [f"b{i}" for i in rng.integers(0, 10, n)],
        "v": rng.uniform(1, 2, n)}))
    level = F.grouping("a") + F.grouping("b")
    w = Window.partition_by(
        level, F.when(F.grouping("b") == 0, col("a"))).order_by(col("s"))
    df = (facts.rollup("a", "b").agg(F.sum(col("v")).alias("s"))
          .select(col("a"), col("b"), col("s"), level.alias("level"),
                  F.rank().over(w).alias("r")))
    collects, names = phases(traced(df, tmp_path, queries=2))
    assert "PjitFunction(sort.window)" in names, names
    for collect, spans in collects:
        by = {}
        for e in spans:
            by.setdefault(e[2], []).append(e)
        [execute] = by["srt:execute"]
        [window] = by["srt:window"]
        assert inside(window, execute)
        [agg] = by["srt:agg_whole_stage_bucket"]
        assert agg[1] <= window[0]
        assert int(window[3]["batches"]) == 1
        # the capacity the aggregate hands on: one batch's bucket state
        assert int(window[3]["rows"]) == 1024
    moved = session.last_execution.aggregate()
    assert moved[MN.EXPAND_OUTPUT_ROWS] == 3 * 8192
    assert moved[MN.EXPAND_BATCHES] == 1
    assert moved[MN.AGG_BUCKET_BATCHES] == 1
    assert not moved.get(MN.AGG_SORT_PATH_BATCHES)
    assert moved[MN.WINDOW_ROWS] == 1024
    assert moved[MN.WINDOW_BATCHES] == 1
    assert len(df.collect()) == 40 * 10 + 40 + 1


def test_named_range_is_a_span_and_a_timer_and_never_a_sync():
    from spark_rapids_tpu.metrics.registry import DEVICE_SYNCS, Metrics
    m = Metrics()
    before = DEVICE_SYNCS.count
    with named_range("scan_decode", m, MN.SCAN_TIME, rows=3):
        pass
    with named_range("agg_update", m):      # unregistered name: recorded
        pass
    assert m.values[MN.SCAN_TIME] > 0 and "agg_update" in m.values
    assert DEVICE_SYNCS.count == before
    import inspect
    from spark_rapids_tpu.utils import tracing
    src = inspect.getsource(tracing.named_range)
    assert not re.search(r"block_until_ready|device_get|np\.asarray", src)


# -- operator pull spans -------------------------------------------------------

OP = SPAN_PREFIX + "op:"


def op_spans(spans):
    return [e for e in spans if e[2].startswith(OP)]


def enclosing(e, spans):
    """The innermost of `spans` that holds `e` (not `e` itself)."""
    around = [o for o in spans if o is not e and inside(e, o)
              and (o[0], -o[1]) <= (e[0], -e[1])]
    return max(around, key=lambda o: (o[0], -o[1]), default=None)


def straddling(spans):
    """Pairs of `spans` that overlap without one holding the other (a span
    held open across a `yield` does that to its operator's pull spans)."""
    spans = sorted(spans, key=lambda e: (e[0], -e[1]))
    return [(a[2], b[2]) for i, a in enumerate(spans) for b in spans[i + 1:]
            if b[0] < a[1] < b[1]]


@pytest.mark.parametrize("plan, operators", [
    ("q6", {"DeviceToHostExec", "TpuHashAggregateExec", "TpuScanMemoryExec"}),
    ("broadcast_join", {"TpuBroadcastHashJoinExec", "TpuHashAggregateExec"}),
    ("streamed_grouped", {"TpuHashAggregateExec", "TpuScanMemoryExec"}),
], ids=["q6", "broadcast_join", "streamed_grouped_aggregate"])
def test_every_launch_of_a_query_lies_in_an_operators_pull_span(
        traced_plan, plan, operators):
    """`exec/base.py` wraps every operator's iterator once: each pull is a
    `srt:op:<ClassName>@<node id>` span with the query's id, a child's pull
    lies inside its parent's, so every jitted call the querying thread makes
    inside `srt:execute` has an operator, and the operators' self times
    (a span less the operator spans right inside it) and the semaphore's
    wait are `srt:execute`'s."""
    session, threads = traced_plan(plan)
    collects, names = phases(threads)
    [thread] = [th for th in threads if any(e[2] == COLLECT for e in th)]
    qe = session.last_execution
    ancestors = {}
    for nid, parent in qe._parent_of.items():
        chain = []
        while parent is not None:
            chain.append(parent)
            parent = qe._parent_of[parent]
        ancestors[nid] = chain
    shares = []
    for collect, spans in collects:
        [execute] = [e for e in spans if e[2] == "srt:execute"]
        ops = op_spans(spans)
        assert {e[2][len(OP):].split("@")[0] for e in ops} >= operators
        assert all(inside(e, execute) for e in ops)
        assert not straddling(spans)
        # every jitted call of the drain is some operator's
        calls = [e for e in thread if e[2].startswith("PjitFunction(")
                 and inside(e, execute)]
        assert calls
        for call in calls:
            assert enclosing(call, ops) is not None, call
        # the spans carry the node's id and the query's, and nest as the
        # plan does: an operator is pulled from inside an ancestor's pull
        self_ns = {}
        for e in ops:
            assert e[3]["q"] == execute[3]["q"]
            name, nid = e[2][len(OP):].split("@")
            assert name == type(qe.nodes[int(nid)]).__name__
            outer = enclosing(e, ops)
            if outer is None:
                assert int(nid) == 0
            else:
                onid = int(outer[2].split("@")[1])
                assert onid in ancestors[int(nid)], (e[2], outer[2])
                self_ns[onid] = self_ns.get(onid, 0) - (e[1] - e[0])
            self_ns[int(nid)] = self_ns.get(int(nid), 0) + e[1] - e[0]
        assert all(ns >= 0 for ns in self_ns.values()), self_ns
        # beside the root's pulls `srt:execute` holds the semaphore's wait
        [semaphore] = [e for e in spans if e[2] == "srt:semaphore"]
        shares.append((sum(self_ns.values()) + semaphore[1] - semaphore[0])
                      / (execute[1] - execute[0]))
    assert int(collects[-1][1][2][3]["q"]) == qe.query_id
    # within 2% (a loaded host only ever lowers a query's share)
    assert 0.98 <= max(shares) <= 1.0 and statistics.median(shares) >= 0.95, \
        shares


def toy_operators():
    from spark_rapids_tpu.exec.base import TpuExec

    class ToySourceExec(TpuExec):
        """Five batches, and whether its generator was closed."""
        closed = False

        def execute(self, ctx):
            try:
                yield from range(5)
            finally:
                self.closed = True

    class ToyHeadExec(TpuExec):
        """Stops after one batch of its child, as a LIMIT does."""

        def execute(self, ctx):
            for batch in self.children[0].execute(ctx):
                yield batch
                return

    class ToyRaisingExec(TpuExec):
        def execute(self, ctx):
            for batch in self.children[0].execute(ctx):
                if batch == 2:
                    raise ValueError("batch 2")
                yield batch

    return ToySourceExec, ToyHeadExec, ToyRaisingExec


def traced_block(tmp_path, body):
    """`body()` under the profiler -> this thread's (start, end, name)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(COLLECT):
            body()
        with jax.profiler.TraceAnnotation("test:after"):
            pass
    finally:
        jax.profiler.stop_trace()
    [pb] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(pb)
    [host] = [p for p in data.planes if p.name == "/host:CPU"]
    [thread] = [sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                        e.name) for e in line.events)
                for line in host.lines
                if any(e.name == COLLECT for e in line.events)]
    return thread


def test_a_limit_that_stops_pulling_closes_the_child_and_every_span(tmp_path):
    Source, Head, _ = toy_operators()
    source = Source()
    head = Head(source)
    # a class's own entry points are wrapped when the class is made
    assert hasattr(Source.execute, "__wrapped__")
    got = []
    thread = traced_block(tmp_path, lambda: got.extend(head.execute(None)))
    assert got == [0] and source.closed
    names = [n for _, _, n in thread if n.startswith(OP)]
    # no id before a query numbers the nodes; two pulls of the head (the
    # second ends it), one of the source, each a finished span
    assert sorted(names) == [OP + "ToyHeadExec"] * 2 + [OP + "ToySourceExec"]
    [after] = [e for e in thread if e[2] == "test:after"]
    assert all(e[1] <= after[0] for e in thread if e[2].startswith(OP))
    # the same through a plan: the scan's batches past the limit are never
    # pulled and its generator is closed with the query
    session = TpuSession({"spark.rapids.sql.reader.batchSizeRows": "4096"})
    df = session.from_arrow(lineitem(40_000)).limit(10)
    collects, _ = phases(traced(df, tmp_path / "plan", queries=1))
    scans = [e for e in op_spans(collects[0][1]) if "ScanMemory" in e[2]]
    assert 1 <= len(scans) < 40_000 // 4096
    assert len(df.collect()) == 10


def test_an_operator_that_raises_leaves_no_span_open(tmp_path):
    Source, _, Raising = toy_operators()
    source = Source()
    got = []

    def body():
        with pytest.raises(ValueError, match="batch 2"):
            got.extend(Raising(source).execute(None))
    thread = traced_block(tmp_path, body)
    assert got == [0, 1] and source.closed
    ops = [e for e in thread if e[2].startswith(OP)]
    # the raising pull's span is finished too: three pulls each
    assert len(ops) == 6
    [after] = [e for e in thread if e[2] == "test:after"]
    assert all(e[1] <= after[0] for e in ops)
    # and a generator closed by hand closes its child
    source = Source()
    pulled = Raising(source).execute(None)
    assert next(pulled) == 0
    pulled.close()
    assert source.closed


def test_an_entry_point_reached_inside_the_nodes_own_pull_opens_no_twin(
        tmp_path):
    """A subclass that hands on `super().execute(ctx)` (the SPMD join's
    fallback onto the hash join), as a generator or as a plain method, and
    an `execute` that drains the node's own `execute_partitions`: one span
    a pull of the NODE, under the class's own name."""
    Source, _, _ = toy_operators()

    class ToyYieldFromExec(Source):
        def execute(self, ctx):
            yield from super().execute(ctx)

    class ToyHandsOnExec(Source):
        def execute(self, ctx):
            return super().execute(ctx)

    class ToyPartitionsExec(Source):
        def execute_partitions(self, ctx):
            yield from enumerate(range(5))

        def execute(self, ctx):
            for _, batch in self.execute_partitions(ctx):
                yield batch

    nodes = [ToyYieldFromExec(), ToyHandsOnExec(), ToyPartitionsExec()]
    got = []
    thread = traced_block(
        tmp_path, lambda: got.extend(list(n.execute(None)) for n in nodes))
    assert got == [list(range(5))] * 3 and nodes[0].closed
    names = collections.Counter(n for _, _, n in thread if n.startswith(OP))
    # five batches and the pull that ends it, each node
    assert names == {OP + type(n).__name__: 6 for n in nodes}
    assert not any(n._in_pull for n in nodes)
    # from outside a pull the partitions are an entry point of their own
    assert len(list(nodes[2].execute_partitions(None))) == 5


def test_a_parquet_columns_launches_lie_in_its_pool_threads_span(tmp_path):
    """The launching threads of a Parquet scan are the column pool's: each
    opens `srt:scan_column` around one column of one row-group chunk, so a
    launch made there has an owner on ITS thread (string columns decode on
    the device on every backend)."""
    rng = np.random.default_rng(41)
    n = 30_000
    path = str(tmp_path / "t.parquet")
    papq.write_table(pa.table({
        "s": [f"k{i}" for i in rng.integers(0, 20, n)],
        "t": [f"w{i}" for i in rng.integers(0, 7, n)],
        "v": rng.integers(0, 1000, n).astype(np.int64)}), path,
        compression="snappy", use_dictionary=True)
    session = TpuSession({})
    df = session.read.parquet(path).group_by("s", "t").agg(
        F.sum(col("v")).alias("x"))
    threads = traced(df, tmp_path / "trace", queries=2)
    pool = [th for th in threads
            if any(e[2] == "srt:scan_column" for e in th)]
    assert pool and not any(e[2] == COLLECT for th in pool for e in th)
    columns = [e for th in pool for e in th if e[2] == "srt:scan_column"]
    assert {e[3]["column"] for e in columns} == {"s", "t", "v"}
    assert {int(e[3]["rows"]) for e in columns} == {n}
    calls = 0
    for th in pool:
        spans = [e for e in th if e[2] == "srt:scan_column"]
        for e in th:
            if e[2].startswith("PjitFunction("):
                calls += 1
                assert enclosing(e, spans) is not None, e
    assert calls > 0


def test_the_spmd_joins_span_closes_before_it_yields(tmp_path):
    """`srt:dist_join` is one span a probe chunk, inside ONE pull of the
    join: held open across the `yield` it clocked the consumer too and
    straddled the join's pull spans, which no reader can flatten."""
    rows = 1000
    session = TpuSession({
        "spark.rapids.sql.tpu.mesh.devices": "4",
        "spark.sql.autoBroadcastJoinThreshold": "-1"})
    left = session.from_arrow(pa.table({
        "k": np.arange(rows, dtype=np.int32),
        "v": np.arange(rows, dtype=np.int64)}))
    right = session.from_arrow(pa.table({
        "k": np.arange(rows, dtype=np.int32),
        "w": np.arange(rows, dtype=np.int64) * 3}))
    df = left.join(right, on="k").group_by("k").agg(
        F.sum(col("v") + col("w")).alias("s"))
    collects, _ = phases(traced(df, tmp_path, queries=2))
    for _, spans in collects:
        pulls = [e for e in spans
                 if e[2].startswith(OP + "TpuDistributedJoinExec@")]
        chunks = [e for e in spans if e[2] == "srt:dist_join"]
        assert pulls and chunks
        assert all(enclosing(e, pulls) is not None for e in chunks)
        assert not straddling(spans)
    assert len(df.collect()) == rows


def test_a_named_range_leaves_the_name_stack_alone():
    """No `jax.named_scope`: what is traced inside a span is named as
    outside it (and the persistent compile cache's keys do not move with a
    span's name)."""
    from jax._src import source_info_util
    outside = str(source_info_util.current_name_stack())
    with named_range("agg_update", rows=3):
        assert str(source_info_util.current_name_stack()) == outside
        lowered = jax.jit(lambda x: x + 1).lower(1.0).as_text(
            debug_info=True)
    assert "agg_update" not in lowered


# -- program names -----------------------------------------------------------

def run_program_zoo(tmp_path):
    """Aggregate (fused and streaming), join, sort, window, Parquet scan and
    write, and the SPMD operators: the programs of the query, join, sort,
    scan and mesh paths."""
    rng = np.random.default_rng(11)
    n = 5000
    facts = pa.table({"k": rng.integers(0, 50, n).astype(np.int32),
                      "v": rng.integers(0, 1000, n).astype(np.int64),
                      "d": rng.uniform(0, 1, n)})
    dims = pa.table({"k": np.arange(50, dtype=np.int32),
                     "w": np.arange(50, dtype=np.int64)})
    path = str(tmp_path / "facts.parquet")
    papq.write_table(facts, path, compression="snappy",
                     use_dictionary=True)
    for conf in ({}, {"spark.rapids.sql.tpu.wholeStage.enabled": "false"},
                 {"spark.rapids.sql.tpu.fusion.enabled": "false"},
                 {"spark.rapids.sql.tpu.mesh.devices": "4",
                  "spark.sql.autoBroadcastJoinThreshold": "-1"}):
        s = TpuSession({"spark.rapids.sql.variableFloatAgg.enabled": "true",
                        **conf})
        f, d = s.from_arrow(facts), s.from_arrow(dims)
        (f.filter(col("v") > 10).group_by("k")
         .agg(F.sum(col("v")).alias("s"), F.avg(col("d")).alias("a"))
         .order_by("k").collect())
        f.join(d, on="k").order_by("v", "k").limit(7).collect()
        s.read.parquet(path).filter(col("v") < 500).group_by("k").agg(
            F.count(col("v")).alias("c")).collect()
        f.select((col("v") + 1).alias("v1"), col("k")).repartition(
            4, "k").collect()
    s.read.parquet(path).write.parquet(str(tmp_path / "out"))


def test_every_compiled_program_is_named_after_its_layer(tmp_path,
                                                         monkeypatch):
    built = []
    real = KC.named_jit

    def recording(builder, role, **kw):
        fn = real(builder, role, **kw)
        built.append(fn.__name__)
        return fn
    monkeypatch.setattr(KC, "named_jit", recording)
    KC.clear()
    run_program_zoo(tmp_path)
    assert len(set(built)) >= 15, sorted(set(built))
    assert all(PROGRAM_NAME.match(n) for n in built), sorted(set(built))
    layers = {n.split(".")[0] for n in built}
    assert layers >= {"scan", "agg", "join", "sort", "stage", "dist", "mem",
                      "expr"}, layers
    # what the cache holds is what was named: nothing jitted on the side
    assert {fn.__name__ for fn in KC._CACHE.values()} <= set(built)
    assert set(KC._LAYER_OF_MODULE.values()) <= set(
        PROGRAM_NAME.pattern[2:-3].split("|"))


def test_a_program_from_an_unmapped_module_is_an_error():
    def builder():
        return lambda x: x + 1
    with pytest.raises(KeyError, match="no layer for a program built in"):
        KC.cached_kernel(("unmapped_probe", 1), builder)
    with pytest.raises(KeyError, match="_LAYER_OF_MODULE"):
        KC.stage_executable(("unmapped_probe",), builder, (1,))
    builder.__module__ = "spark_rapids_tpu.io.some_new_reader"
    assert KC.program_layer(builder) == "scan"      # a package's modules
    assert ("unmapped_probe", 1) not in KC._CACHE


@pytest.mark.parametrize("key, role", [
    (("pq_bp", 3, 1024), "pq_bp"),
    (("contig_pack", (("a", "int"),)), "contig_pack"),
    (("TpuHashJoinExec", "inner", "packed", (1,), (2,), "probe", 4),
     "hashjoin_probe"),
    (("whole_stage", 2, 1024, (), "treedef", "TpuHashAggregateExec", "xla",
      ((1,),), "bucket"), "whole_stage_bucket"),
    (("TpuSortExec", "packed", "xla", (1,), (True,), (False,)), "sort"),
    (("TpuProjectExec", ((1,),), "/data/part-0.parquet"), "project"),
    (("join_probe", "TpuHashJoinExec", "inner", (1,), 4, 256, True),
     "join_probe"),
])
def test_a_cache_key_states_the_programs_role(key, role):
    assert KC.program_role(key) == role


def test_a_cache_key_without_a_role_is_an_error():
    with pytest.raises(ValueError, match="starts with the program's role"):
        KC.program_role(((1, 2), "late"))
    with pytest.raises(ValueError):
        KC.program_role(())


# -- iciBytesMoved -----------------------------------------------------------

def row_bytes(*dtypes):
    """Data + validity byte per column, + the selection mask's byte."""
    return sum(np.dtype(t).itemsize + 1 for t in dtypes) + 1


@pytest.mark.parametrize("allgather", [False, True],
                         ids=["all_to_all", "all_gather"])
def test_spmd_operators_declare_their_ici_bytes(allgather):
    n, rows = 4, 1000
    cap = 1024                       # bucket_rows(1000)
    local = cap // n
    session = TpuSession({
        "spark.rapids.sql.tpu.mesh.devices": str(n),
        "spark.rapids.sql.tpu.mesh.useAllGather": str(allgather).lower(),
        "spark.sql.autoBroadcastJoinThreshold": "-1"})
    left = session.from_arrow(pa.table({
        "k": np.arange(rows, dtype=np.int32),
        "v": np.arange(rows, dtype=np.int64)}))
    right = session.from_arrow(pa.table({
        "k": np.arange(rows, dtype=np.int32),
        "w": np.arange(rows, dtype=np.int64) * 3}))

    def moved(df, op):
        got = df.collect()
        [node] = [m for m in session.last_execution.node_metrics()
                  if m["op"] == op]
        return got, node["metrics"][MN.ICI_BYTES_MOVED]

    def expected(rows_per_peer_a2a, width, exchanges=1):
        per_peer = local if allgather else rows_per_peer_a2a
        return exchanges * n * (n - 1) * per_peer * width

    # sort: one exchange of the input rows, quota from factor 4
    got, ici = moved(left.order_by("v"), "TpuDistributedSortExec")
    assert [r[1] for r in got] == list(range(rows))
    assert ici == expected(default_quota(local, n, factor=4),
                           row_bytes("i4", "i8"))
    # join: the build side once, then the one probe chunk
    got, ici = moved(left.join(right, on="k"), "TpuDistributedJoinExec")
    assert len(got) == rows
    assert ici == expected(default_quota(local, n), row_bytes("i4", "i8"),
                           exchanges=2)
    # aggregate: the partial state (key, sum) of the one chunk
    agg_df = left.group_by("k").agg(F.sum(col("v")).alias("s"))
    got, ici = moved(agg_df, "TpuDistributedAggregateExec")
    assert sorted(got) == [(i, i) for i in range(rows)]
    [agg] = [x for x in session.last_execution.nodes
             if type(x).__name__ == "TpuDistributedAggregateExec"]
    width = sum(f.dtype.np_dtype.itemsize + 1
                for f in agg._state_schema) + 1
    assert ici == expected(default_quota(local, n), width)
    assert session.query_metrics_total[MN.ICI_BYTES_MOVED] >= ici
