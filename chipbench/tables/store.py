"""TPC-DS STORE, the two columns query 36 reads of the 29 published: a dense
`s_store_sk` in [1, n] and `s_state` (char(2)), uniform over ten states of
which the query's substitution list names eight.  Uniform draws from
`RandomState(seed + 4)`, not `dsdgen`'s (whose SF10 stores all lie in a
few states); no NULL."""
import numpy as np

STATES = np.array(["TN", "SD", "AL", "GA", "MI", "OH", "TX", "CA", "NY",
                   "FL"])


def generate(n, seed, sizes):
    rng = np.random.RandomState((seed + 4) % 2**32)
    return {
        "s_store_sk": np.arange(1, n + 1, dtype=np.int64),
        "s_state": STATES[rng.randint(0, len(STATES), n)],
    }
