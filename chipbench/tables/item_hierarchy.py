"""TPC-DS ITEM, the three columns query 36 reads of the 22 published: a
dense `i_item_sk` in [1, n] and the product hierarchy the query rolls up:
`i_category`, `dsdgen`'s ten names, and `i_class`, 16 names a category (160
pairs; `dsdgen` has 99 class names in all, not the same number under every
category).  Both are char(50) in the specification; at most 16 bytes are
used and nothing is padded.  Uniform draws from `RandomState(seed + 2)`,
not `dsdgen`'s; no NULL.  `item.py` (the brand columns) stays as it is."""
import numpy as np

CATEGORIES = np.array(["Books", "Children", "Electronics", "Home", "Jewelry",
                       "Men", "Music", "Shoes", "Sports", "Women"])
CLASSES = np.array(["accessories", "athletic", "business", "classical",
                    "computers", "cooking", "country", "dresses", "fiction",
                    "fragrances", "history", "infants", "mystery", "outdoor",
                    "reference", "travel"])


def generate(n, seed, sizes):
    rng = np.random.RandomState((seed + 2) % 2**32)
    return {
        "i_item_sk": np.arange(1, n + 1, dtype=np.int64),
        "i_category": CATEGORIES[rng.randint(0, len(CATEGORIES), n)],
        "i_class": CLASSES[rng.randint(0, len(CLASSES), n)],
    }
