"""TPC-DS ITEM, the four columns query 52 reads (and query 55): a dense
`i_item_sk` in [1, n], `i_manager_id` uniform in 1..100 and a brand of
`dsdgen`'s form: `i_brand_id` = category (1..10) x 1,000,000 + class (1..16)
x 1,000 + number (1..6), 960 ids, and `i_brand` a function of the id: two
syllable words and ` #<number>`, 12 to 22 bytes of the published char(50).
The draws are uniform from `RandomState(seed + 2)`, not `dsdgen`'s (listed
under `assumed` in the configuration file).  `benchmarks/tpcds/datagen.py`
is NOT copied: it has 160 brand ids and 40 managers."""
import numpy as np

CATEGORY_WORDS = np.array(["amalg", "importo", "exporti", "scholar",
                           "edu pack", "brand", "corp", "univ", "maxi",
                           "nameless"])
CLASS_WORDS = np.array(["amalgamalg", "importoimpo", "exportiexpo",
                        "scholarscho", "edu packedu", "brandbrand",
                        "corpcorp", "univuniv", "maximaxi", "namelessna",
                        "amalgimpo", "importoex", "exporti", "scholar",
                        "brand", "corpo"])
NUMBERS = 6
MANAGERS = 100


def brand_name(brand_id):
    """`i_brand` of an array of `i_brand_id`s."""
    category = brand_id // 1_000_000
    klass = brand_id // 1_000 % 1_000
    number = brand_id % 1_000
    name = np.char.add(CLASS_WORDS[klass - 1], CATEGORY_WORDS[category - 1])
    return np.char.add(np.char.add(name, " #"), number.astype(str))


def generate(n, seed, sizes):
    rng = np.random.RandomState((seed + 2) % 2**32)
    category = rng.randint(1, len(CATEGORY_WORDS) + 1, n).astype(np.int64)
    klass = rng.randint(1, len(CLASS_WORDS) + 1, n).astype(np.int64)
    number = rng.randint(1, NUMBERS + 1, n).astype(np.int64)
    brand_id = category * 1_000_000 + klass * 1_000 + number
    return {
        "i_item_sk": np.arange(1, n + 1, dtype=np.int64),
        "i_brand_id": brand_id,
        "i_brand": brand_name(brand_id),
        "i_manager_id": rng.randint(1, MANAGERS + 1, n).astype(np.int64),
    }
