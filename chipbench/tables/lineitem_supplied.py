"""TPC-H LINEITEM for query 21, the four columns it reads of the 16
published: `l_orderkey`, `l_suppkey`, `l_commitdate` and `l_receiptdate`,
in the order dbgen writes the table, CLUSTERED by order (every order's 1 to
7 lines together, orders in key order: `lineitem_clustered.order_keys` and
`line_counts`, loaded from that file).

`orders_and_lines(seed, sizes)` draws ORDERS and LINEITEM together, from
one `RandomState(seed + 21)`, so that `orders_status.py`'s
`o_orderstatus` follows the order's OWN lines, as in dbgen.  By clause
4.2.3, with uniform draws rather than dbgen's streams:

- `l_suppkey`: a line's part `p` uniform over 1..P and `i` over 0..3, then
  dbgen's part-to-supplier rule `(p + i * (S/4 + (p-1)/S)) % S + 1` in
  integer arithmetic, S the suppliers (10,000 at SF1) and P = 20 S the
  parts (200,000); `l_partkey` is drawn and not kept;
- `o_orderdate` uniform over 1992-01-01..1998-08-02 (days 8,035..10,440);
  `l_shipdate` = orderdate + U[1, 121], `l_commitdate` = orderdate +
  U[30, 90], `l_receiptdate` = shipdate + U[1, 30]; `l_shipdate` is drawn
  and not kept (only the order's status reads it);
- `o_orderstatus`: 'F' where every line's shipdate is on or before
  CURRENTDATE 1995-06-17 (day 9,298), 'O' where none is, 'P' otherwise.

Every date is an int64 count of days since 1970-01-01."""
import importlib.util
import os

import numpy as np

FIRST_DATE, LAST_DATE = 8035, 10440   # 1992-01-01, 1998-08-02 (days)
CURRENT_DATE = 9298                   # 1995-06-17
PARTS_PER_SUPPLIER = 20               # P = 200,000 and S = 10,000 at SF1


def _clustered_module():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lineitem_clustered.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_tables_lineitem_clustered", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def supplier_of(partkey, i, suppliers):
    """dbgen's supplier of the `i`-th (0..3) supplier slot of a part."""
    s = np.int64(suppliers)
    return (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1


def orders_and_lines(seed, sizes):
    """(orders, lines): column name -> numpy array, from one stream."""
    clustered = _clustered_module()
    rng = np.random.RandomState((seed + 21) % 2**32)
    n_orders, n_lines = sizes["orders_status"], sizes["lineitem_supplied"]
    suppliers = sizes["supplier"]
    counts = clustered.line_counts(rng, n_orders, n_lines)
    keys = clustered.order_keys(n_orders)
    owner = np.repeat(np.arange(n_orders), counts)
    orderdate = rng.randint(FIRST_DATE, LAST_DATE + 1,
                            n_orders).astype(np.int64)[owner]
    partkey = rng.randint(1, PARTS_PER_SUPPLIER * suppliers + 1,
                          n_lines).astype(np.int64)
    slot = rng.randint(0, 4, n_lines).astype(np.int64)
    shipdate = orderdate + rng.randint(1, 122, n_lines)
    commitdate = orderdate + rng.randint(30, 91, n_lines)
    receiptdate = shipdate + rng.randint(1, 31, n_lines)
    shipped = np.bincount(owner, weights=shipdate <= CURRENT_DATE,
                          minlength=n_orders).astype(np.int64)
    status = np.where(shipped == counts, "F",
                      np.where(shipped == 0, "O", "P"))
    orders = {"o_orderkey": keys, "o_orderstatus": status}
    lines = {"l_orderkey": keys[owner],
             "l_suppkey": supplier_of(partkey, slot, suppliers),
             "l_commitdate": commitdate.astype(np.int64),
             "l_receiptdate": receiptdate.astype(np.int64)}
    return orders, lines


def generate(n, seed, sizes):
    assert n == sizes["lineitem_supplied"]
    return orders_and_lines(seed, sizes)[1]
