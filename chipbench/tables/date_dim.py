"""TPC-DS DATE_DIM, the three columns the short reporting class (queries 3,
42, 52, 55) reads: one row a day from 1900-01-02, `d_date_sk` the Julian
day number from 2,415,022, as the specification's `dsdgen` makes it (73,049
rows: up to 2100-01-01).  Nothing is drawn: the calendar is the calendar,
and `seed` is not used.  A run with fewer rows keeps the first `n` days;
STORE_SALES draws its sold dates from 1998 to 2003 whatever `n` is, so a
DATE_DIM cut below 37,621 rows matches only a part of them."""
import numpy as np

FIRST_DATE_SK = 2_415_022            # 1900-01-02
FIRST_DAY = np.datetime64("1900-01-02", "D")


def generate(n, seed, sizes):
    days = FIRST_DAY + np.arange(n, dtype=np.int64)
    months = days.astype("datetime64[M]").astype(np.int64)   # since 1970-01
    return {
        "d_date_sk": FIRST_DATE_SK + np.arange(n, dtype=np.int64),
        "d_year": months // 12 + 1970,
        "d_moy": months % 12 + 1,
    }
