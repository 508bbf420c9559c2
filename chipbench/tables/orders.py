"""TPC-H ORDERS, integers and dates only: a dense unique o_orderkey in
[1, n].  Copied from `benchmarks/tpch/bulk.py` (`make_orders`), same draws
from `RandomState(seed + 1)`."""
import numpy as np


def generate(n, seed, sizes):
    rng = np.random.RandomState((seed + 1) % 2**32)
    return {
        "o_orderkey": rng.permutation(n).astype(np.int64) + 1,
        "o_custkey": rng.randint(1, max(2, n // 10) + 1, n).astype(np.int64),
        "o_orderdate": rng.randint(8035, 10441, n).astype(np.int64),
        "o_shippriority": np.zeros(n, dtype=np.int64),
    }
