"""TPC-H ORDERS for query 18, the four columns it reads of the 9
published: `o_orderkey` (dbgen's sparse keys, the first 8 of every 32, in
key order), `o_custkey` (uniform over the customers whose key is not a
multiple of 3), `o_orderdate` (uniform over 1992-01-01 to 1998-08-02,
int64 days) and `o_totalprice` (the sum over the order's own lines, to
the cent).  Drawn with LINEITEM from one stream:
`lineitem_clustered.orders_and_lines`."""
import importlib.util
import os


def _lines_module():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lineitem_clustered.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_tables_lineitem_clustered", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def generate(n, seed, sizes):
    assert n == sizes["orders_priced"]
    return _lines_module().orders_and_lines(seed, sizes)[0]
