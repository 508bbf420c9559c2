"""TPC-DS STORE_SALES, the three columns the short reporting class reads of
the 23 published: `ss_sold_date_sk` (a DATE_DIM key, NULL in 4.5% of the
rows, the share `dsdgen`'s output shows), `ss_item_sk` (an ITEM key, never
NULL) and `ss_ext_sales_price` (decimal(7,2), here a double rounded to
cents).  Uniform draws from `RandomState(seed)`, not `dsdgen`'s: sold dates
over 1998-01-02 to 2003-01-02 with no seasonal weight, items over
1..`sizes["item"]`, prices in [0, 20,000].  Vectorised: 28,800,991 rows in
some seconds."""
import numpy as np
import pyarrow as pa

FIRST_SOLD_SK = 2_450_816     # 1998-01-02
LAST_SOLD_SK = 2_452_642      # 2003-01-02
NULL_DATE_SHARE = 0.045


def generate(n, seed, sizes):
    rng = np.random.RandomState(seed % 2**32)
    sold = rng.randint(FIRST_SOLD_SK, LAST_SOLD_SK + 1, n).astype(np.int64)
    no_date = rng.random_sample(n) < NULL_DATE_SHARE
    item = rng.randint(1, sizes["item"] + 1, n).astype(np.int64)
    price = np.round(rng.uniform(0.0, 20_000.0, n), 2)
    return {
        "ss_sold_date_sk": pa.array(sold, mask=no_date),
        "ss_item_sk": item,
        "ss_ext_sales_price": price,
    }
