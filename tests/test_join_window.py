"""The hash join's candidate window (utils/packed_sort.merge_windows): one
merge of build and stream hashes where a binary search a row ran (PR 30).

The helper against numpy's searchsorted, the structure of the programs
that call it (no loop that gathers from the build hashes, a gather count
that does not grow with the build side), and joined answers where many
different keys share one hash prefix.  And the structure of the gather
program behind it (PR 36): pairs placed from the output's side, no loop
that gathers or scatters over the stream batch.
"""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.plan.logical import col

from compare import assert_tpu_and_cpu_are_equal

_MAX = np.uint64(2**64 - 1)


def _prefix_bits(cap_b, cap_l):
    """The low bits the merge drops: r with 2^r >= cap_b + cap_l."""
    return max(1, (cap_b + cap_l - 1).bit_length())


def _window_case(kind, cap_b, cap_l, rng):
    """-> (h_sorted, h_query, live): build hashes ascending with
    duplicates, queries half present and half absent."""
    r = _prefix_bits(cap_b, cap_l)
    dead_b = cap_b // 4 if kind == "dead_both" else 0
    n_b = cap_b - dead_b
    pool = rng.integers(0, 2**63, max(1, n_b // 3), dtype=np.uint64) * 2
    if kind == "prefix_forged":
        # few prefixes, every value of the dropped low bits under each:
        # different hashes that the merge cannot tell apart
        pool = ((pool[:max(1, len(pool) // 8)] >> np.uint64(r))
                << np.uint64(r))
        low = rng.integers(0, 1 << r, n_b, dtype=np.uint64)
        hb = rng.choice(pool, n_b) | low
    else:
        hb = rng.choice(pool, n_b)                    # duplicates
    hs = np.sort(np.concatenate([hb, np.full(dead_b, _MAX, np.uint64)]))
    if kind == "prefix_forged":
        hq = rng.choice(pool, cap_l) \
            | rng.integers(0, 1 << r, cap_l, dtype=np.uint64)
    else:
        hq = np.where(rng.random(cap_l) < 0.5, rng.choice(hb, cap_l),
                      rng.integers(0, 2**63, cap_l, dtype=np.uint64) * 2 + 1)
    live = np.ones(cap_l, bool)
    if kind == "dead_both":
        live = rng.random(cap_l) < 0.7
        live[0] = False
        hq = np.where(live, hq, _MAX)     # dead rows hash to uint64 max
    return hs, hq.astype(np.uint64), live


@pytest.mark.parametrize("caps", [(1024, 1024), (512, 1024), (1024, 256),
                                  (1000, 300), (8, 1), (1, 8)],
                         ids=lambda c: f"{c[0]}x{c[1]}")
@pytest.mark.parametrize("kind", ["random_dups", "dead_both",
                                  "prefix_forged"])
def test_merge_windows_against_searchsorted(kind, caps):
    """`[lo, hi)` is searchsorted(left/right) over the kept hash prefix:
    it contains the equal-hash window always and equals it when no two
    different hashes share a prefix; `max_width` is the widest window of
    a LIVE query (a dead one's spans every dead build row)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.utils.packed_sort import merge_windows
    cap_b, cap_l = caps
    rng = np.random.default_rng(cap_b * 7 + cap_l + len(kind))
    hs, hq, live = _window_case(kind, cap_b, cap_l, rng)
    lo, hi, mw = jax.jit(merge_windows)(
        jnp.asarray(hs), jnp.asarray(hq), jnp.asarray(live))
    lo, hi = np.asarray(lo), np.asarray(hi)
    assert lo.dtype == hi.dtype == np.int32 and lo.shape == (cap_l,)
    r = np.uint64(_prefix_bits(cap_b, cap_l))
    np.testing.assert_array_equal(
        lo, np.searchsorted(hs >> r, hq >> r, "left"))
    np.testing.assert_array_equal(
        hi, np.searchsorted(hs >> r, hq >> r, "right"))
    elo = np.searchsorted(hs, hq, "left")
    ehi = np.searchsorted(hs, hq, "right")
    assert (lo <= elo).all() and (hi >= ehi).all()
    if kind == "prefix_forged":
        if cap_b >= 512:
            assert ((hi - lo) > (ehi - elo)).any()    # the case is forged
    else:
        np.testing.assert_array_equal(lo, elo)
        np.testing.assert_array_equal(hi, ehi)
    assert int(mw) == int(np.where(live, hi - lo, 0).max())
    if kind == "dead_both" and cap_b >= 512:
        assert int(mw) < (hi - lo)[~live].max() == cap_b // 4


# --------------------------------------------------------------------------
# structure: what the programs that find windows are made of
# --------------------------------------------------------------------------

_SUB_JAXPRS = ("jaxpr", "call_jaxpr", "body_jaxpr", "cond_jaxpr")


def _inner_jaxprs(eqn):
    for name in _SUB_JAXPRS:
        sub = eqn.params.get(name)
        if sub is not None:
            yield getattr(sub, "jaxpr", sub)
    for sub in eqn.params.get("branches", ()):
        yield getattr(sub, "jaxpr", sub)


def _gathers(jaxpr, trips=1):
    """Gathers the program issues: each `gather` equation times the trip
    counts of the scans around it (a `fori_loop` over static bounds is a
    scan); a `while` of unknown length counts as 1,000."""
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather":
            total += trips
        inner = trips * {"scan": eqn.params.get("length", 1),
                         "while": 1000}.get(name, 1)
        for sub in _inner_jaxprs(eqn):
            total += _gathers(sub, inner)
    return total


def _loops_reading(jaxpr, tainted):
    """`while`/`scan` equations that take one of `tainted` (variables of
    `jaxpr`) as an operand, followed through nested calls."""
    found = []
    for eqn in jaxpr.eqns:
        hit = [i for i, v in enumerate(eqn.invars)
               if not hasattr(v, "val") and v in tainted]
        if not hit:
            continue
        if eqn.primitive.name in ("while", "scan"):
            found.append(eqn.primitive.name)
        elif eqn.primitive.name in ("jit", "pjit", "closed_call",
                                    "core_call", "custom_jvp_call"):
            for sub in _inner_jaxprs(eqn):
                found += _loops_reading(sub, {sub.invars[i] for i in hit})
    return found


def _join_and_batches(cap_b, cap_l, how="left"):
    from spark_rapids_tpu.engine import TpuSession
    from spark_rapids_tpu.exec.join import TpuHashJoinExec
    s = TpuSession({})
    rng = np.random.default_rng(cap_b + cap_l)
    left = s.from_pydict(
        {"k": rng.integers(0, 50, cap_l).tolist(),
         "a": rng.integers(0, 9, cap_l).tolist()},
        T.Schema([T.StructField("k", T.LongType),
                  T.StructField("a", T.LongType)]))
    right = s.from_pydict(
        {"kr": rng.integers(0, 50, cap_b).tolist(),
         "b": rng.integers(0, 9, cap_b).tolist()},
        T.Schema([T.StructField("kr", T.LongType),
                  T.StructField("b", T.LongType)]))
    plan = s.plan(left.join(right, col("k") == col("kr"), how).plan)
    join = _find(plan, TpuHashJoinExec)
    from spark_rapids_tpu.exec.base import ExecContext
    ctx = ExecContext(s.conf, s.runtime)
    (lb,) = list(join.children[0].execute(ctx))
    (rb,) = list(join.children[1].execute(ctx))
    assert (lb.capacity, rb.capacity) == (cap_l, cap_b)
    return join, lb, rb


def _find(node, cls):
    if isinstance(node, cls):
        return node
    for c in node.children:
        got = _find(c, cls)
        if got is not None:
            return got
    return None


def _traced(cap_b, cap_l, which):
    """-> (jaxpr, the jaxpr's variables that hold the build hashes)."""
    import jax
    # a left join: the planner keeps the right side as the build side
    join, lb, rb = _join_and_batches(cap_b, cap_l)
    build, bkeys, h1s = join._build_kernel(rb)
    if which == "window":
        closed = jax.make_jaxpr(
            lambda b, h: join._window_kernel(b, h))(lb, h1s)
    else:
        closed = jax.make_jaxpr(
            lambda b, bd, bk, h: join._probe_kernel(8, b, bd, bk, h))(
                lb, build, bkeys, h1s)
    return closed.jaxpr, {closed.jaxpr.invars[-1]}


@pytest.mark.parametrize("which", ["window", "probe"])
def test_window_programs_have_no_search_loop(which):
    """The finding PR 30 pins: on the v5e a 1M-row gather costs 13.8 ms,
    and `searchsorted` chains 2 x ceil(log2(cap_b + 1)) of them a side in
    a scan over the build hashes.  The window of `_window_kernel` (and of
    the probe program around it) comes from a merge: no loop reads the
    build hashes, and the gathers issued do not grow with the build
    side (the probe's are its count walk's, `max_dup` a step)."""
    small, tainted = _traced(1024, 1024, which)
    assert _loops_reading(small, tainted) == []
    large, tainted = _traced(8192, 1024, which)
    assert _loops_reading(large, tainted) == []
    assert _gathers(small) == _gathers(large)
    if which == "window":
        assert _gathers(small) == 0


def test_structure_checks_see_a_binary_search():
    """The two checks above do fire on the form that went."""
    import jax
    import jax.numpy as jnp

    def search(h1s, h1):
        return jnp.searchsorted(h1s, h1, side="left")
    h = jnp.zeros(1024, jnp.uint64)
    small = jax.make_jaxpr(search)(h, h).jaxpr
    large = jax.make_jaxpr(search)(jnp.zeros(8192, jnp.uint64), h).jaxpr
    assert _loops_reading(small, {small.invars[0]})
    assert _gathers(large) > _gathers(small) >= 10


def _indexed_ops(jaxpr, in_loop=False):
    """[(primitive, inside a loop?, rows of its indices)] for every gather
    and scatter of the program, nested calls and loop bodies included."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            found.append((name.split("-")[0], in_loop,
                          eqn.invars[1].aval.shape[0]))
        looping = in_loop or name in ("while", "scan")
        for sub in _inner_jaxprs(eqn):
            found += _indexed_ops(sub, looping)
    return found


def _walk_gather(join, max_dup, out_cap, lbatch, build, bkeys, lo, hi,
                 counts, starts, total):
    """The gather that went (`_gather_kernel` until PR 36), letter for
    letter: every step of the walk gathers the build side's keys over the
    whole STREAM batch, compares them again, and scatters three arrays of
    stream capacity into the output slots."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.exec.join import _row_equal
    from spark_rapids_tpu.types import Schema
    lkeys = [e.eval(lbatch) for e in join.left_keys]
    cap_b = build.capacity
    live = lbatch.sel
    blive = build.sel

    l_idx = jnp.zeros(out_cap, jnp.int32)
    b_idx = jnp.zeros(out_cap, jnp.int32)
    matched = jnp.zeros(out_cap, jnp.bool_)
    b_hit = jnp.zeros(cap_b, jnp.bool_)
    rows = jnp.arange(lbatch.capacity, dtype=jnp.int32)

    def body(d, carry):
        l_out, b_out, m_out, bh, rank = carry
        bidx = jnp.clip(lo + d, 0, cap_b - 1)
        ok = live & ((lo + d) < hi) & jnp.take(blive, bidx, mode="clip")
        for lk, bk in zip(lkeys, bkeys):
            ok &= _row_equal(lk, bk, bidx)
        if join.condition is not None:
            ok &= join._pair_condition_ok(lbatch, build, bidx)
        slot = jnp.where(ok, starts + rank, out_cap)  # out_cap = dropped
        l_out = l_out.at[slot].set(rows, mode="drop")
        b_out = b_out.at[slot].set(bidx, mode="drop")
        m_out = m_out.at[slot].set(True, mode="drop")
        bh = bh.at[jnp.where(ok, bidx, cap_b)].set(True, mode="drop")
        return l_out, b_out, m_out, bh, rank + ok.astype(jnp.int32)

    zero_rank = jnp.zeros(lbatch.capacity, jnp.int32)
    l_idx, b_idx, matched, b_hit, _ = jax.lax.fori_loop(
        0, max_dup, body, (l_idx, b_idx, matched, b_hit, zero_rank))
    if join.join_type in ("left", "full"):
        slot = jnp.where(live, starts, out_cap)
        already = jnp.take(matched, jnp.clip(slot, 0, out_cap - 1),
                           mode="clip")
        slot = jnp.where(already, out_cap, slot)
        l_idx = l_idx.at[slot].set(rows, mode="drop")

    sel = jnp.arange(out_cap, dtype=jnp.int32) < total
    lcols = [c.take(l_idx) for c in lbatch.columns]
    rcols = []
    for c in build.columns:
        taken = c.take(b_idx)
        rcols.append(taken.with_valid(taken.valid & matched)
                     .mask_invalid())
    lfields, rfields = join._joined_fields(lbatch.schema, build.schema)
    joined = ColumnarBatch(lcols + rcols, sel, Schema(lfields + rfields))
    out = ColumnarBatch(joined.columns, joined.sel, join._schema)
    if join.join_type == "full":
        return out, b_hit
    return out


_STREAM_CAP = 4096


def _gather_programs(how, max_dup, out_cap=1 << 17):
    """-> (the gather program's jaxpr, the walk form's, a function that
    runs both on one batch): keys of 0..49 duplicate about twenty times
    a side, so a walk of `max_dup` 64 covers every window and the output
    is some 80,000 rows of a 4,096-row stream batch."""
    import jax
    join, lb, rb = _join_and_batches(1024, _STREAM_CAP, how)
    build, bkeys, h1s = jax.jit(join._build_kernel)(rb)
    lo, hi, counts, starts, hits, scalars = jax.jit(
        lambda *a: join._probe_kernel(max_dup, *a))(lb, build, bkeys, h1s)
    total = scalars[1]
    assert max_dup < 64 or int(scalars[0]) <= 64 and int(total) <= out_cap

    def placed(lb, build, lo, counts, starts, total, hits):
        return join._gather_kernel(out_cap, lb, build, lo, counts, starts,
                                   total, hits)

    def walked(lb, build, bkeys, lo, hi, counts, starts, total):
        return _walk_gather(join, max_dup, out_cap, lb, build, bkeys, lo,
                            hi, counts, starts, total)
    new_args = (lb, build, lo, counts, starts, total, hits)
    old_args = (lb, build, bkeys, lo, hi, counts, starts, total)
    return (jax.make_jaxpr(placed)(*new_args).jaxpr,
            jax.make_jaxpr(walked)(*old_args).jaxpr,
            lambda: (jax.jit(placed)(*new_args), jax.jit(walked)(*old_args)))


@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_gather_program_places_pairs_without_a_walk(how):
    """The finding PR 36 pins: one step of the gather walk cost 60 ms a
    1M-row stream batch whatever the join put out (a 1M-row gather is 7.5
    ms a `u32`, a scatter 5.6).  The gather program places its pairs from
    the output's side: no gather or scatter inside any loop, as many of
    them at a window width of 8 as at 1, and one scatter at most whose
    indices have the stream batch's capacity (the rows' first slots)."""
    narrow, _, _ = _gather_programs(how, 1)
    wide, _, _ = _gather_programs(how, 8)
    for jaxpr in (narrow, wide):
        ops = _indexed_ops(jaxpr)
        assert ops and not [op for op in ops if op[1]], ops
        assert len([op for op in ops
                    if op[0] == "scatter" and op[2] == _STREAM_CAP]) <= 1, ops
    assert _indexed_ops(narrow) == _indexed_ops(wide)
    assert _gathers(narrow) == _gathers(wide)


@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_structure_checks_see_a_gather_walk(how):
    """The checks above do fire on the form that went, and it was the
    same join: both forms give the same rows in the same slots."""
    import numpy as np
    placed_jaxpr, narrow, _ = _gather_programs(how, 1)
    _, wide, run = _gather_programs(how, 64)
    in_loop = [op for op in _indexed_ops(wide) if op[1]]
    assert len([op for op in in_loop if op[0] == "gather"]) >= 3
    assert len([op for op in in_loop
                if op[0] == "scatter" and op[2] == _STREAM_CAP]) >= 3
    assert _gathers(wide) >= _gathers(narrow) + 63 * 3
    assert _gathers(placed_jaxpr) < _gathers(narrow)
    placed, walked = run()
    if how == "full":
        np.testing.assert_array_equal(np.asarray(placed[1]),
                                      np.asarray(walked[1]))
        placed, walked = placed[0], walked[0]
    assert int(placed.num_rows()) > 1024
    assert placed.to_pylist() == walked.to_pylist()


# --------------------------------------------------------------------------
# answers where many different keys share a hash prefix
# --------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["inner", "left", "full", "left_semi",
                                 "left_anti"])
def test_join_answers_under_prefix_collisions(how, monkeypatch):
    """Every key's hash forged onto one of 4 prefixes with the low bits
    free: each stream row's window holds about a quarter of the build
    side, nearly all of it other keys, and the key comparison of the
    count and gather walks must reject them; the answer is
    ops/cpu_eval's."""
    import jax.numpy as jnp
    from spark_rapids_tpu.exec import join as J
    real = J.hash_columns_double

    def forged(cols, sel):
        h1, h2 = real(cols, sel)
        h = ((h1 >> jnp.uint64(62)) << jnp.uint64(62)) \
            | (h1 & jnp.uint64(0xFF))
        return jnp.where(sel, h, jnp.uint64(_MAX)), h2
    monkeypatch.setattr(J, "hash_columns_double", forged)
    seen = []
    real_window = J.TpuHashJoinExec._window_kernel

    def spy(self, lbatch, h1s):
        lo, hi, md = real_window(self, lbatch, h1s)
        seen.append(md)
        return lo, hi, md
    monkeypatch.setattr(J.TpuHashJoinExec, "_window_kernel", spy)

    def q(s):
        rng = np.random.default_rng(30)
        n_l, n_r = 300, 200
        lk = [None if rng.random() < 0.1 else int(rng.integers(0, 60))
              for _ in range(n_l)]
        rk = [None if rng.random() < 0.1 else int(rng.integers(20, 90))
              for _ in range(n_r)]
        # names of this test's own: a kernel cached under another test's
        # key would have been traced with the real hashes
        left = s.from_pydict(
            {"kpfx": lk, "apfx_" + how: list(range(n_l))},
            T.Schema([T.StructField("kpfx", T.LongType),
                      T.StructField("apfx_" + how, T.LongType)]))
        right = s.from_pydict(
            {"krpfx": rk, "bpfx": list(range(n_r))},
            T.Schema([T.StructField("krpfx", T.LongType),
                      T.StructField("bpfx", T.LongType)]))
        return left.join(right, col("kpfx") == col("krpfx"), how)
    assert_tpu_and_cpu_are_equal(q)
    assert seen, "the device join did not run"


def test_merged_window_counter_counts_probe_batches():
    """`joinMergedWindowBatches`: one per stream batch through the probe."""
    from spark_rapids_tpu.engine import TpuSession
    s = TpuSession({"spark.rapids.sql.reader.batchSizeRows": "128"})
    left = s.from_pydict({"k": list(range(500))},
                         T.Schema([T.StructField("k", T.LongType)]))
    right = s.from_pydict({"kr": list(range(0, 500, 5))},
                          T.Schema([T.StructField("kr", T.LongType)]))
    before = dict(s.query_metrics_total)
    rows = left.join(right, col("k") == col("kr"), "inner").collect()
    assert len(rows) == 100
    moved = s.query_metrics_total["joinMergedWindowBatches"] \
        - before.get("joinMergedWindowBatches", 0)
    assert moved >= 1


@pytest.mark.parametrize("how", ["inner", "left_semi"])
def test_output_space_and_walk_step_counters(how):
    """`joinOutputSpaceBatches` plus `joinPassThroughBatches` is
    `joinMergedWindowBatches` where pairs are placed (inner, left, full)
    and both are 0 for a semi join; here every build key is unique and the
    output's capacity bucket (1,024) is the stream batches', so every
    batch passes through.  `joinWalkSteps` sums the step counts the count
    walks were asked for: the first batch probes at 8 and reads a window
    width of 1, the three after it at 1."""
    from spark_rapids_tpu.engine import TpuSession
    s = TpuSession({"spark.rapids.sql.reader.batchSizeRows": "128"})
    left = s.from_pydict({"kc": list(range(500))},
                         T.Schema([T.StructField("kc", T.LongType)]))
    right = s.from_pydict({"krc": list(range(0, 500, 5))},
                          T.Schema([T.StructField("krc", T.LongType)]))
    before = dict(s.query_metrics_total)
    rows = left.join(right, col("kc") == col("krc"), how).collect()
    assert len(rows) == 100

    def moved(name):
        return s.query_metrics_total.get(name, 0) - before.get(name, 0)
    batches = moved("joinMergedWindowBatches")
    assert batches >= 1
    assert moved("joinOutputSpaceBatches") == 0
    assert moved("joinPassThroughBatches") == \
        (batches if how == "inner" else 0)
    assert moved("joinWalkSteps") == 8 + (batches - 1)
