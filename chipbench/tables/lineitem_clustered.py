"""TPC-H LINEITEM for query 18, the two columns it reads of the 16
published: `l_orderkey` and `l_quantity`, in the order dbgen writes the
table, CLUSTERED by order (every order's lines together, orders in key
order).  An order has 1 to 7 lines drawn uniformly (clause 4.2.3); the
table has exactly `n` rows: the difference between `n` and the drawn line
count is spread one line at a time over random orders, staying within
1..7.  `l_quantity` is uniform over 1..50 (a decimal, here a double).

`orders_and_lines(seed, sizes)` draws ORDERS and LINEITEM together, from
one `RandomState(seed + 5)`, so that `orders_priced.py`'s `o_totalprice`
is the sum over the order's OWN lines of `extendedprice x (1 + tax) x
(1 - discount)`, as in dbgen: big orders are dear ones.  A line's
extended price is its quantity times its part's retail price, drawn
uniformly over [900, 2,100] (dbgen derives it from the part key), its
discount over 0.00..0.10 and its tax over 0.00..0.08.  Uniform draws, not
dbgen's streams.  `lineitem.py` and `orders.py` (uniform order keys, no
`o_totalprice`) stay as they are."""
import numpy as np

MAX_LINES = 7
FIRST_DATE, LAST_DATE = 8035, 10440   # 1992-01-01, 1998-08-02 (days)


def order_keys(n):
    """dbgen's sparse keys: the first 8 of every 32 (1..8, 33..40, ...)."""
    i = np.arange(n, dtype=np.int64)
    return i // 8 * 32 + i % 8 + 1


def line_counts(rng, orders, lines):
    """1..7 lines an order, uniform, nudged to sum to exactly `lines`."""
    if not orders <= lines <= MAX_LINES * orders:
        raise ValueError(f"{lines} lines do not fit {orders} orders of "
                         f"1 to {MAX_LINES} lines")
    counts = rng.randint(1, MAX_LINES + 1, orders).astype(np.int64)
    while short := lines - int(counts.sum()):
        # a line to each of as many random orders as have room (one pass
        # at any real size)
        room = np.flatnonzero(counts < MAX_LINES if short > 0
                              else counts > 1)
        picked = rng.choice(room, min(abs(short), len(room)), replace=False)
        counts[picked] += np.sign(short)
    return counts


def orders_and_lines(seed, sizes):
    """(orders, lines): column name -> numpy array, from one stream."""
    rng = np.random.RandomState((seed + 5) % 2**32)
    n_orders, n_lines = sizes["orders_priced"], sizes["lineitem_clustered"]
    counts = line_counts(rng, n_orders, n_lines)
    keys = order_keys(n_orders)
    # o_custkey: uniform over the customers whose key is not a multiple
    # of 3 (clause 4.2.3: a third of the customers place no order)
    active = sizes["customer"] - sizes["customer"] // 3
    k = rng.randint(0, active, n_orders).astype(np.int64)
    custkey = k // 2 * 3 + k % 2 + 1
    orderdate = rng.randint(FIRST_DATE, LAST_DATE + 1,
                            n_orders).astype(np.int64)
    quantity = rng.randint(1, 51, n_lines).astype(np.float64)
    retail = np.round(rng.uniform(900.0, 2100.0, n_lines), 2)
    extended = np.round(quantity * retail, 2)
    discount = rng.randint(0, 11, n_lines) / 100.0
    tax = rng.randint(0, 9, n_lines) / 100.0
    owner = np.repeat(np.arange(n_orders), counts)
    charge = extended * (1.0 + tax) * (1.0 - discount)
    totalprice = np.round(np.bincount(owner, weights=charge,
                                      minlength=n_orders), 2)
    orders = {"o_orderkey": keys, "o_custkey": custkey,
              "o_orderdate": orderdate, "o_totalprice": totalprice}
    lines = {"l_orderkey": keys[owner], "l_quantity": quantity}
    return orders, lines


def generate(n, seed, sizes):
    assert n == sizes["lineitem_clustered"]
    return orders_and_lines(seed, sizes)[1]
