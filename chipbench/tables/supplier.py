"""TPC-H SUPPLIER, the three columns query 21 reads of the 7 published: a
dense `s_suppkey` in [1, n], `s_name`, "Supplier#" and the key in nine
digits (18 bytes, clause 4.2.3), and `s_nationkey` uniform over the 25
nations' keys 0..24, drawn from `RandomState(seed + 22)`."""
import numpy as np

NATIONS = 25


def generate(n, seed, sizes):
    keys = np.arange(1, n + 1, dtype=np.int64)
    rng = np.random.RandomState((seed + 22) % 2**32)
    return {"s_suppkey": keys,
            "s_name": np.char.add("Supplier#",
                                  np.char.zfill(keys.astype(str), 9)),
            "s_nationkey": rng.randint(0, NATIONS, n).astype(np.int64)}
