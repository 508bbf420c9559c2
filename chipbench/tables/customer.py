"""TPC-H CUSTOMER, the two columns query 18 reads of the 8 published: a
dense `c_custkey` in [1, n] and `c_name`, "Customer#" and the key in nine
digits (18 bytes, clause 4.2.3).  Nothing is drawn and `seed` is not
used."""
import numpy as np


def generate(n, seed, sizes):
    keys = np.arange(1, n + 1, dtype=np.int64)
    return {"c_custkey": keys,
            "c_name": np.char.add("Customer#",
                                  np.char.zfill(keys.astype(str), 9))}
