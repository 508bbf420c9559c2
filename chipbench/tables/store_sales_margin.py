"""TPC-DS STORE_SALES, the five columns query 36 reads of the 23 published:
`ss_sold_date_sk` (a DATE_DIM key, NULL in 4.5% of the rows, the share
`dsdgen`'s output shows), `ss_item_sk` and `ss_store_sk` (ITEM and STORE
keys, never NULL), `ss_net_profit` and `ss_ext_sales_price` (decimal(7,2),
here doubles rounded to cents).  Uniform draws from `RandomState(seed)`, not
`dsdgen`'s: sold dates over 1998-01-02 to 2003-01-02 with no seasonal
weight, items over 1..`sizes["item_hierarchy"]`, stores over
1..`sizes["store"]`, prices in [0, 20,000] as in `store_sales.py`, and the
profit in [-10,000, 1,400]: a margin near -0.43, as in the specification's
answer set, and sums that do not cancel (a profit symmetric about zero
would lose three digits in the grand total, and the comparison at 1e-10
would then judge the data, not the engine).  Vectorised: 28,800,991 rows
in some seconds.  `store_sales.py` (three columns) stays as it is."""
import numpy as np
import pyarrow as pa

FIRST_SOLD_SK = 2_450_816     # 1998-01-02
LAST_SOLD_SK = 2_452_642      # 2003-01-02
NULL_DATE_SHARE = 0.045
PROFIT = (-10_000.0, 1_400.0)
PRICE = (0.0, 20_000.0)


def generate(n, seed, sizes):
    rng = np.random.RandomState(seed % 2**32)
    sold = rng.randint(FIRST_SOLD_SK, LAST_SOLD_SK + 1, n).astype(np.int64)
    no_date = rng.random_sample(n) < NULL_DATE_SHARE
    item = rng.randint(1, sizes["item_hierarchy"] + 1, n).astype(np.int64)
    store = rng.randint(1, sizes["store"] + 1, n).astype(np.int64)
    return {
        "ss_sold_date_sk": pa.array(sold, mask=no_date),
        "ss_item_sk": item,
        "ss_store_sk": store,
        "ss_net_profit": np.round(rng.uniform(*PROFIT, n), 2),
        "ss_ext_sales_price": np.round(rng.uniform(*PRICE, n), 2),
    }
