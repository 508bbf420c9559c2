"""Device time, or the number of launches, of the executables whose name
matches `pattern`: the events of chip 0's `XLA Modules` line that start
inside the traced window, per query.  `what` is "ms" (the sum of their
device durations, milliseconds) or "count"; `negate` takes the launches
whose name does NOT match instead.

The program names every executable it compiles `jit_<layer>.<role>`
(`utils/kernel_cache.py`: `jit_agg.whole_stage`, `jit_scan.pq_bp`,
`jit_dist.join_probe`); a trace shows the name with a fingerprint after it,
`jit_agg.whole_stage(1657...)`.  What matches no `jit_<layer>.` is an eager
`jnp` op outside any compiled program, so the negated count of that pattern
is the eager launches a query pays for.  `None` where the trace has no
device, or where no launch matches a pattern that is not negated (a program
without such names: nothing to read)."""
import re


def read(ev, pattern, what="ms", negate=False):
    trace = ev.trace
    if not trace.devices or not ev.queries:
        return None
    rx = re.compile(pattern)
    launches = [(s, e, name) for s, e, name in trace.devices[0].launches
                if trace.t0 <= s < trace.t1]
    picked = [(s, e) for s, e, name in launches
              if bool(rx.search(name)) != negate]
    if not launches or not (picked or negate):
        return None
    if what == "count":
        return len(picked) / ev.queries
    if what == "ms":
        return sum(e - s for s, e in picked) / 1e6 / ev.queries
    raise ValueError(f"module_time: what is 'ms' or 'count', not {what!r}")
