"""The least time the chips could take to read what the query must read,
over the time their ops took: bytes from the query's `bytes_needed(rows)`
(columns touched x rows x published width), over the chips' summed peak HBM
rate from `peaks.json`, over the traced device-busy time per query (the
union of op intervals, averaged over the chips used).  In percent: it
cannot pass 100 unless the bytes are counted too high or the trace misses
ops."""
import xplane


def read(ev):
    busy = xplane.busy_per_chip(ev.trace, ev.cell.chips)
    if not sum(busy) or not ev.queries:
        return None
    busy_s_per_query = sum(busy) / len(busy) / 1e9 / ev.queries
    least_s = (ev.cell.query.bytes_needed(ev.rows)
               / (ev.peaks["hbm_bytes_per_s"] * len(busy)))
    return 100.0 * least_s / busy_s_per_query
