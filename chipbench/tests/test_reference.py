"""Each query's plain reference against the engine's host session
(`spark.rapids.sql.enabled=false`: pyarrow executors and ops/cpu_eval.py,
what `chip_smoke.py` compares with) and against the device path on the CPU
backend, at 200,000 lineitem rows."""
import pytest

import cells
import compare

ROWS = 200_000


@pytest.mark.parametrize("workload, query", [
    ("tpch_q6_resident", "q6"), ("tpch_q1_resident", "q1"),
    ("tpch_q3_join_mesh4", "q3_shape")])
def test_reference_equals_host_session(workload, query, pending_bench_dir):
    from spark_rapids_tpu.engine import TpuSession
    cell = cells.load_cell(workload, bench_dir=pending_bench_dir)
    assert cell.traffic["query"] == query
    tables = cells.make_tables(cell, 24, cell.rows(ROWS))
    want = cell.query.reference(tables)
    assert want and all(len(r) == len(want[0]) for r in want)
    conf = {k: v for k, v in cell.config["conf"].items()
            if "mesh" not in k}
    for session in (TpuSession({"spark.rapids.sql.enabled": "false"}),
                    TpuSession(conf)):
        frames = {t: session.from_arrow(tb) for t, tb in tables.items()}
        got = cell.query.build(session, frames).collect()
        ok, worst = compare.rows_match(got, want)
        assert ok, (worst, got[:2], want[:2])


def test_tables_repeat_from_the_seed_and_match_bulk(pending_bench_dir):
    """The copied generators draw what `benchmarks/tpch/bulk.py` draws."""
    from benchmarks.tpch import bulk
    cell = cells.load_cell("tpch_q3_join_mesh4", bench_dir=pending_bench_dir)
    rows = cell.rows(ROWS)
    a = cells.make_tables(cell, 7, rows)
    b = cells.make_tables(cell, 7, rows)
    c = cells.make_tables(cell, 8, rows)
    assert a["lineitem"].equals(b["lineitem"])
    assert not a["lineitem"].equals(c["lineitem"])
    theirs = bulk.make_lineitem(ROWS, seed=7, n_orders=rows["orders"])
    assert a["lineitem"].equals(theirs.select(a["lineitem"].column_names))
    assert a["orders"].equals(
        bulk.make_orders(rows["orders"], seed=7).select(
            a["orders"].column_names))


@pytest.mark.parametrize("got, want, ok", [
    ([(1, "A", 1.0)], [(1, "A", 1.0 + 1e-12)], True),
    ([(1, "A", 1.0)], [(1, "A", 1.0 + 1e-9)], False),
    ([(1, "A", 1.0)], [(2, "A", 1.0)], False),
    ([(1, "A", 1.0)], [(1, "B", 1.0)], False),
    ([(1, 2.0), (3, 4.0)], [(3, 4.0), (1, 2.0)], False),   # row order
    ([(1, 2.0)], [(1, 2.0), (3, 4.0)], False),             # row count
    ([(1,)], [(1.0,)], False),                             # int is not double
    ([(float("nan"),)], [(float("nan"),)], True),
])
def test_rows_match(got, want, ok):
    assert compare.rows_match(got, want)[0] is ok
