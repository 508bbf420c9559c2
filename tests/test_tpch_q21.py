"""TPC-H query 21 as `chipbench`'s cell `tpch_q21_resident` runs it, here at
a small size on the CPU.  Tables, query, reference and comparison are the
benchmark's own files (`chipbench/tables/lineitem_supplied.py`,
`orders_status.py`, `supplier.py`, `nation.py`, `queries/q21.py`,
`compare.py`), and the session's conf is the configuration file's; the
shuffled form and the reader's batch size are set IN THE TEST, where a test
asks for them.

What no other test holds together: `EXISTS` and `NOT EXISTS` as a
`left_semi` and a `left_anti` self-join of LINEITEM, each on the order key
with a supplier inequality evaluated inside the count walk (a key every
build row repeats 1 to 7 times), broadcast and shuffled; the programs
`jit_join.hashjoin_probe_residual`, `hashjoin_rest_residual` and
`hashjoin_count_residual`; the counters `joinResidualBatches` and
`joinAntiBatches`.
"""
import json
import os

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.engine import TpuSession
from spark_rapids_tpu.exec.join import (TpuHashJoinExec,
                                        TpuShuffledHashJoinExec)
from spark_rapids_tpu.plan.logical import col
from spark_rapids_tpu.utils import kernel_cache
from test_agg_streaming import BENCH, _bench_module

LINES = 60_000
SIZES = {"lineitem_supplied": LINES, "orders_status": LINES // 4,
         "supplier": 100, "nation": 25}
#: LINEITEM in eight batches of capacity 8,192 (the eighth holds 2,656
#: rows); the late lines keep the batches, so each conditional join probes
#: eight stream batches
BATCH = 8_192
LINE_BATCHES = 8
#: every join shuffled over the planner's hash exchanges
SHUFFLED = {"spark.sql.autoBroadcastJoinThreshold": "-1",
            "spark.rapids.sql.tpu.join.partitioned.threshold": "1"}

COMPARE = _bench_module("", "compare")
Q21 = _bench_module("queries", "q21")
SUPPLIED = _bench_module("tables", "lineitem_supplied")
NATION = _bench_module("tables", "nation")

with open(os.path.join(BENCH, "configs", "tpch-sf1-q21-1chip.json")) as _f:
    CELL_CONF = json.load(_f)["conf"]


def q21_tables(seed, sizes=SIZES):
    """The cell's four tables from `seed`, as `cells.make_tables` makes
    them."""
    tables = {}
    for table, columns in Q21.TABLES.items():
        drawn = _bench_module("tables", table).generate(
            sizes[table], seed, sizes)
        tables[table] = pa.table({c: drawn[c] for c in columns})
    return tables


def session(extra=None):
    return TpuSession({**CELL_CONF, **(extra or {})})


def q21(s, tables):
    return Q21.build(s, {t: s.from_arrow(tb) for t, tb in tables.items()})


def collect_moved(s, df):
    """(rows, the counters the query moved)."""
    before = dict(s.query_metrics_total)
    rows = df.collect()
    return rows, {k: v - before.get(k, 0)
                  for k, v in s.query_metrics_total.items()}


def walk(node):
    yield node
    for child in node.children:
        yield from walk(child)


@pytest.fixture
def roles(monkeypatch):
    """The program role of every cached-kernel lookup (one a launch site a
    batch, cache hit or miss) while the test runs."""
    seen = []
    real = kernel_cache.cached_kernel

    def recording(key, builder, **kw):
        seen.append(kernel_cache.program_role(key))
        return real(key, builder, **kw)
    monkeypatch.setattr(kernel_cache, "cached_kernel", recording)
    return seen


@pytest.mark.parametrize("form", ["broadcast", "shuffled"])
@pytest.mark.parametrize("seed", [3_700_000_017, 2**31 + 9])
def test_q21_equals_the_plain_reference(seed, form):
    tables = q21_tables(seed % 2**32)
    want = Q21.reference(tables)
    # a handful of Saudi suppliers out of 100, every one that waited
    assert 1 <= len(want) <= 10
    s = session(SHUFFLED if form == "shuffled" else None)
    got, moved = collect_moved(s, q21(s, tables))
    ok, worst = COMPARE.rows_match(got, want)
    assert ok, (got, want)
    assert moved.get("numCpuFallbacks", 0) == 0
    assert moved["joinResidualBatches"] == moved["joinSemiBatches"] > 0


@pytest.mark.parametrize("form", ["broadcast", "shuffled"])
def test_the_plan_holds_a_conditional_semi_and_anti_join_on_l1(form):
    plan = q21(session(SHUFFLED if form == "shuffled" else None),
               q21_tables(5)).physical_plan()
    assert not [n for n in walk(plan) if type(n).__name__.startswith("Cpu")]
    joins = [n for n in walk(plan) if isinstance(n, TpuHashJoinExec)]
    by_type = {}
    for j in joins:
        by_type.setdefault(j.join_type, []).append(j)
    assert sorted(by_type) == ["inner", "left_anti", "left_semi"]
    [semi], [anti] = by_type["left_semi"], by_type["left_anti"]
    assert len(by_type["inner"]) == 3
    # each on the order key, the supplier inequality as its residual
    for j in (semi, anti):
        assert len(j.left_keys) == 1 and j.condition is not None
    assert all(j.condition is None for j in by_type["inner"])
    # the anti join streams what the semi join kept, below every inner join
    assert semi in list(walk(anti.children[0]))
    assert all(anti in list(walk(j)) for j in by_type["inner"])
    shuffled = all(isinstance(j, TpuShuffledHashJoinExec) for j in joins)
    assert shuffled == (form == "shuffled")


def test_the_counters_and_programs_of_the_conditional_walk(roles):
    tables = q21_tables(11)
    want = Q21.reference(tables)
    s = session({"spark.rapids.sql.reader.batchSizeRows": str(BATCH)})
    df = q21(s, tables)
    first, _ = collect_moved(s, df)
    roles.clear()
    got, moved = collect_moved(s, df)
    for rows in (first, got):
        ok, worst = COMPARE.rows_match(rows, want)
        assert ok, (rows, want)
    assert moved.get("numCpuFallbacks", 0) == 0
    # the semi and the anti join each probe LINEITEM's eight late-line
    # batches, every one through the residual walk and the membership mask
    assert moved["joinResidualBatches"] == 2 * LINE_BATCHES
    assert moved["joinSemiBatches"] == 2 * LINE_BATCHES
    assert moved["joinAntiBatches"] == LINE_BATCHES
    # the conditional joins' probes carry a name of their own; the three
    # inner joins' keep the equi join's
    assert roles.count("hashjoin_probe_residual") == 2 * LINE_BATCHES
    assert "hashjoin_probe" in roles
    assert roles.count("hashjoin_semi") == 2 * LINE_BATCHES
    assert moved["joinMergedWindowBatches"] == (
        moved["joinSemiBatches"] + moved.get("joinOutputSpaceBatches", 0)
        + moved.get("joinPassThroughBatches", 0))


def test_an_equi_join_launches_the_plain_probe_and_no_residual_program(
        roles):
    tables = q21_tables(3)
    s = session()
    f = {t: s.from_arrow(tb) for t, tb in tables.items()}
    df = (f["lineitem_supplied"]
          .join(f["supplier"], on=col("l_suppkey") == col("s_suppkey"))
          .join(f["orders_status"],
                on=col("o_orderkey") == col("l_orderkey")))
    rows, moved = collect_moved(s, df)
    assert len(rows) == LINES
    assert "hashjoin_probe" in roles
    assert not [r for r in roles if r.endswith("_residual")]
    assert moved["joinResidualBatches"] == 0
    assert moved.get("joinAntiBatches", 0) == 0


def _wide_window_join(s, how, stream_rows):
    """A build key 20 times over (suppliers 5 eight times, then 6), so
    every window outgrows the probe's speculative 8 steps; a stream row of
    supplier 5 has no match among its first 8 candidates."""
    okey = np.repeat(np.arange(40, dtype=np.int64), 20)
    supp = np.tile(np.array([5] * 8 + [6] * 12, dtype=np.int64), 40)
    build = s.from_arrow(pa.table({"b_key": okey, "b_supp": supp}))
    skey = np.arange(stream_rows, dtype=np.int64) % 42   # 40, 41: no row
    ssupp = np.where(np.arange(stream_rows) % 2 == 0, 5, 6)
    stream = s.from_arrow(pa.table({"s_key": skey, "s_supp": ssupp}))
    on = (col("s_key") == col("b_key")) & (col("b_supp") != col("s_supp"))
    return stream.join(build, on=on, how=how)


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
@pytest.mark.parametrize("stream_rows,walks", [
    # a few undecided rows walk on alone at 32 steps; the guess stays
    (84, ["hashjoin_rest_residual"]),
    # past 1,024 undecided rows the whole batch is recounted at 32 steps
    (4_200, ["hashjoin_rest_residual", "hashjoin_count_residual"])])
def test_a_window_past_the_guess_walks_on(roles, how, stream_rows, walks):
    s = session()
    before = dict(s.query_metrics_total)
    got = _wide_window_join(s, how, stream_rows).collect()
    moved = {k: v - before.get(k, 0)
             for k, v in s.query_metrics_total.items()}
    # every row of keys 0..39 has a match (supplier 6 at candidate 8 for
    # a row of supplier 5, supplier 5 for one of 6); keys 40, 41 have none
    matched = sum(k % 42 < 40 for k in range(stream_rows))
    assert len(got) == (matched if how == "left_semi"
                        else stream_rows - matched)
    assert [r for r in roles if r.startswith("hashjoin_")
            and r.endswith("_residual")] == ["hashjoin_probe_residual"] + walks
    assert moved["joinWalkSteps"] == 8 + 32 * len(walks)


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
def test_a_wide_window_after_a_narrow_batch_walks_from_the_lower_guess(
        roles, how):
    """The rest walk's program depends on the guess the probe walked, not
    only on the window: after a narrow batch drops the guess to 1, a row
    whose one match is the fifth of six candidates is still undecided,
    even where a rest walk at guess 8 and the same width was built
    before."""
    s = session({"spark.rapids.sql.reader.batchSizeRows": "64"})
    # key 0: 20 candidates, supplier 6 from the ninth; key 1: 6
    # candidates, supplier 6 from the fifth; key 2: one candidate
    okey = np.repeat(np.arange(3, dtype=np.int64), [20, 6, 1])
    supp = np.array([5] * 8 + [6] * 12 + [5] * 4 + [6] * 2 + [7],
                    dtype=np.int64)
    build = s.from_arrow(pa.table({"b_key": okey, "b_supp": supp}))

    def join(skey, ssupp):
        stream = s.from_arrow(pa.table({"s_key": skey, "s_supp": ssupp}))
        on = (col("s_key") == col("b_key")) & (col("b_supp") != col("s_supp"))
        return stream.join(build, on=on, how=how).collect()

    # one batch at the first guess, 8: the rest walk at width 32 is built
    wide = np.zeros(64, dtype=np.int64)
    join(wide, np.full(64, 5, dtype=np.int64))
    assert roles.count("hashjoin_rest_residual") == 1
    # a batch of key 2 alone (window 1, the guess falls to 1), then one
    # of keys 0 and 1 from supplier 5: every row has a match, past the
    # first candidate
    skey = np.r_[np.full(64, 2), np.arange(64) % 2].astype(np.int64)
    ssupp = np.r_[np.full(64, 8), np.full(64, 5)].astype(np.int64)
    got = sorted(tuple(r) for r in join(skey, ssupp))
    # key 2's only candidate is supplier 7, which every row's 8 differs from
    assert got == (sorted(zip(skey.tolist(), ssupp.tolist()))
                   if how == "left_semi" else [])
    assert roles.count("hashjoin_rest_residual") == 2


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_the_generators_keep_the_specifications_rules(seed):
    tables = q21_tables(seed % 2**32)
    li, o, sup, nat = (tables[t] for t in ("lineitem_supplied",
                                           "orders_status", "supplier",
                                           "nation"))
    assert li.num_rows == LINES
    # LINEITEM clustered by order, 1 to 7 lines each, dbgen's sparse keys
    keys = o["o_orderkey"].to_numpy()
    lkeys = li["l_orderkey"].to_numpy()
    assert np.all(np.diff(lkeys) >= 0)
    owners, counts = np.unique(lkeys, return_counts=True)
    assert np.array_equal(owners, keys)
    assert counts.min() == 1 and counts.max() == 7
    # a line's supplier is one of its part's four (dbgen's rule), and all
    # four slots are taken somewhere
    supp = li["l_suppkey"].to_numpy()
    assert supp.min() >= 1 and supp.max() <= SIZES["supplier"]
    parts = np.arange(1, 20 * SIZES["supplier"] + 1, dtype=np.int64)
    four = np.stack([SUPPLIED.supplier_of(parts, i, SIZES["supplier"])
                     for i in range(4)])
    assert all(len(set(four[:, p])) == 4 for p in range(len(parts)))
    assert len(np.unique(supp)) == SIZES["supplier"]
    # the date chain: commit 30..90 days and receipt 2..151 days after the
    # order, so some two thirds of the lines arrive late
    commit = li["l_commitdate"].to_numpy()
    receipt = li["l_receiptdate"].to_numpy()
    late = receipt > commit
    assert abs(late.mean() - Q21.LATE_SHARE) < 0.02
    assert np.all(receipt - commit <= 151 - 30)
    assert np.all(commit - receipt <= 90 - 2)
    assert commit.min() >= SUPPLIED.FIRST_DATE + 30
    assert receipt.max() <= SUPPLIED.LAST_DATE + 151
    # the status: an order due after CURRENTDATE is still open, one whose
    # last line could ship by then...
    status = np.asarray(o["o_orderstatus"].to_pylist())
    assert set(status) == {"F", "O", "P"}
    earliest = np.minimum.reduceat(commit - 90, np.r_[0, np.cumsum(counts)[:-1]])
    assert np.all(status[earliest > SUPPLIED.CURRENT_DATE] == "O")
    assert 0.45 < (status == "F").mean() < 0.52
    # SUPPLIER and NATION
    names = sup["s_name"].to_pylist()
    assert names[0] == "Supplier#000000001" and all(len(n) == 18
                                                    for n in names)
    nk = sup["s_nationkey"].to_numpy()
    assert nk.min() >= 0 and nk.max() <= 24
    assert nat["n_name"].to_pylist()[20] == "SAUDI ARABIA"
    assert nat["n_nationkey"].to_pylist() == list(range(25))
    again = q21_tables(seed % 2**32)
    assert all(again[t].equals(tables[t]) for t in tables)
    with pytest.raises(AssertionError):
        NATION.generate(24, seed, SIZES)


def test_the_reference_is_the_subqueries_row_by_row():
    """The reference's distinct-supplier counts against EXISTS / NOT EXISTS
    written out per late line, at a small size."""
    sizes = {"lineitem_supplied": 4_000, "orders_status": 1_000,
             "supplier": 40, "nation": 25}
    tables = q21_tables(8, sizes)
    li = tables["lineitem_supplied"]
    okey, supp = li["l_orderkey"].to_numpy(), li["l_suppkey"].to_numpy()
    late = li["l_receiptdate"].to_numpy() > li["l_commitdate"].to_numpy()
    blamed = []
    for i in np.flatnonzero(late):
        same = okey == okey[i]
        other = same & (supp != supp[i])
        if other.any() and not (other & late).any():
            blamed.append(i)
    o = tables["orders_status"]
    final = dict(zip(o["o_orderkey"].to_pylist(),
                     o["o_orderstatus"].to_pylist()))
    sup = tables["supplier"]
    name = dict(zip(sup["s_suppkey"].to_pylist(), sup["s_name"].to_pylist()))
    nation = dict(zip(sup["s_suppkey"].to_pylist(),
                      sup["s_nationkey"].to_pylist()))
    counts = {}
    for i in blamed:
        if final[okey[i]] == "F" and nation[supp[i]] == 20:
            counts[name[supp[i]]] = counts.get(name[supp[i]], 0) + 1
    want = sorted(counts.items(), key=lambda r: (-r[1], r[0]))[:100]
    assert Q21.reference(tables) == want
    # every other nation too, so the check is not of an empty answer
    total = sum(len(Q21.reference(tables, n)) for n in NATION.NAMES)
    assert total > 10
