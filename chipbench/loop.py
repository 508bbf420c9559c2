"""The measured loop and its order statistics.

Closed loop, one stream: the next query starts when the previous one's
rows are in hand, which is what a Spark task thread does.  The window holds
whole queries only: a query that has started is finished and counted, and
the window closes at the first query boundary at or after `seconds`, so no
part-query is counted and the window has no edge effect.  Every end-to-end
number is an order statistic of the per-query times; nothing is a mean or a
count over the window (a single closed stream has no throughput apart from
its latency: read it off a cell as `rows_in / query_s`).
"""
import time
import traceback

import numpy as np

#: a tail is reported only from this many samples: a 90th percentile then
#: has ten samples beyond it
TAIL_MIN_SAMPLES = 100
_CAPACITY = 1 << 20   # per-query slots, allocated before the window


def measure(query, check, seconds, min_queries=2, clock=time.perf_counter_ns):
    """Run `query()` back to back for `seconds` and at least `min_queries`
    times.  `check(answer)` says whether an answer is right and runs after
    the clock has stopped for that query; a query that raises is failed.
    Returns (nanoseconds of the queries that answered, attempted,
    failed)."""
    ns = np.empty(_CAPACITY, dtype=np.int64)
    limit = int(seconds * 1e9)
    done = failed = attempted = 0
    start = now = clock()
    while (now - start < limit or attempted < min_queries) \
            and done < _CAPACITY:
        attempted += 1
        t0 = clock()
        try:
            answer = query()
        except Exception:  # noqa: BLE001 - counted; the run goes on
            now = clock()
            if not failed:
                traceback.print_exc()   # the first failure says why
            failed += 1
            continue
        now = clock()
        ns[done] = now - t0
        done += 1
        if not check(answer):
            failed += 1
    return ns[:done].copy(), attempted, failed


def order_statistics(ns):
    """{"query_s": median, "query_p90_s": 90th percentile} in seconds; the
    tail only where the window held TAIL_MIN_SAMPLES queries."""
    out = {}
    if len(ns) == 0:
        return out
    s = np.sort(ns)
    out["query_s"] = float(np.median(s)) / 1e9
    if len(s) >= TAIL_MIN_SAMPLES:
        # the smallest sample with at least 90% of the samples at or
        # under it: a measured query time, not an interpolation
        out["query_p90_s"] = float(s[-(-len(s) * 9 // 10) - 1]) / 1e9
    return out
