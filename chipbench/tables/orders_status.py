"""TPC-H ORDERS for query 21, the two columns it reads of the 9
published: `o_orderkey` (dbgen's sparse keys, the first 8 of every 32, in
key order) and `o_orderstatus` ('F', 'O' or 'P' from the order's own
lines' ship dates, clause 4.2.3).  Drawn with LINEITEM from one stream:
`lineitem_supplied.orders_and_lines`."""
import importlib.util
import os


def _lines_module():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lineitem_supplied.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_tables_lineitem_supplied", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def generate(n, seed, sizes):
    assert n == sizes["orders_status"]
    return _lines_module().orders_and_lines(seed, sizes)[0]
