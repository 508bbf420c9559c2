"""TPC-H LINEITEM, the eight columns Q6, Q1 and the Q3 shape read.

Copied from `benchmarks/tpch/bulk.py` (`make_lineitem`) so that a later PR
may change that file and not this yardstick.  The draws come in the
original's order from one `RandomState(seed)`, so a seed gives the same
values there and here.  Widths are the published ones (64-bit integers and
doubles, 1-char flags); the value distributions are uniform draws, not
dbgen's (listed under `assumed` in the configuration files).
"""
import numpy as np


def generate(n, seed, sizes):
    """Column name -> numpy array of `n` values; `sizes` holds every
    table's row count in this run (l_orderkey points into orders)."""
    rng = np.random.RandomState(seed)
    price = rng.uniform(900.0, 105000.0, n)
    discount = rng.choice(np.arange(0.0, 0.11, 0.01), n)
    quantity = rng.randint(1, 51, n).astype(np.int64)
    shipdate = rng.randint(8035, 10592, n).astype(np.int64)
    returnflag = rng.randint(0, 3, n)
    linestatus = rng.randint(0, 2, n)
    tax = np.round(rng.uniform(0.0, 0.08, n), 2)
    orderkey = rng.randint(1, sizes["orders"] + 1, n).astype(np.int64)
    return {
        "l_extendedprice": price,
        "l_discount": discount,
        "l_quantity": quantity.astype(np.float64),
        "l_shipdate": shipdate,
        "l_returnflag": np.array(["A", "N", "R"])[returnflag],
        "l_linestatus": np.array(["F", "O"])[linestatus],
        "l_tax": tax,
        "l_orderkey": orderkey,
    }
