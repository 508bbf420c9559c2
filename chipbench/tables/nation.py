"""TPC-H NATION, the two columns query 21 reads of the 4 published: the
specification's 25 fixed rows (clause 4.2.3), `n_nationkey` 0..24 and
`n_name`.  Nothing is drawn and `seed` is not used; the table has 25 rows
at every scale."""
import numpy as np

NAMES = ("ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
         "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
         "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA",
         "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM",
         "UNITED STATES")


def generate(n, seed, sizes):
    assert n == len(NAMES), f"NATION has {len(NAMES)} rows, not {n}"
    return {"n_nationkey": np.arange(n, dtype=np.int64),
            "n_name": np.array(NAMES)}
