"""One program's share of its HBM roofline: the least time the chip could
take to move what that program must move, over the device time its
launches took.  The bytes come from the cell's query module, the function
named by `bytes` called with the run's rows (`queries/q18.py
agg_sort_bytes_needed`: the input read once, one output row a group
written once); the peak HBM rate from `peaks.json`; the device time is the
sum over chip 0's launches that start in the traced window and whose name
matches `pattern` (`^jit_agg\\.whole_stage\\(` takes the grouped sort program
and not `jit_agg.whole_stage_bucket`), per query.  In percent: it cannot
pass 100 unless the bytes are counted too high.  `None` where no launch
matches (the program took another path, or has no such name) or the query
module has no such function."""
import re


def read(ev, pattern, bytes):
    trace = ev.trace
    needed = getattr(ev.cell.query, bytes, None)
    if needed is None or not trace.devices or not ev.queries:
        return None
    rx = re.compile(pattern)
    ns = sum(e - s for s, e, name in trace.devices[0].launches
             if trace.t0 <= s < trace.t1 and rx.search(name))
    if not ns:
        return None
    least_s = needed(ev.rows) / ev.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9 / ev.queries)
