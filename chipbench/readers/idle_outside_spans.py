"""The share, in percent, of the busiest chip's idle time in the traced
window during which the querying thread was inside NO span whose name
matches `pattern`: the host time that holds the chip back and that no span
of the program accounts for.

The program opens every span through `utils/tracing.named_range`, which
prefixes the profiler annotation with `srt:` (`srt:plan`, `srt:execute`,
`srt:d2h`, `srt:finish`, ...), so `^srt:` separates the program's spans
from JAX's runtime spans and from the harness's `chipbench:collect`.  Idle
time is `xplane.idle_gaps` of the chip with most busy time; the thread's
matching spans are flattened with `xplane.innermost_segments`
(`breakdown.idle_by_span`, which `chipbench/breakdown.py` prints by span).  `None`
where the trace has no device, the chip was never idle, or the thread has
no matching span (a program that opens none: nothing to read)."""
import re

import breakdown
import xplane


def read(ev, pattern):
    trace = ev.trace
    busy = xplane.busy_per_chip(trace, ev.cell.chips)
    if not busy:
        return None
    rx = re.compile(pattern)
    thread = next(th for th in trace.threads
                  if any(e[2] == xplane.QUERY_SPAN for e in th))
    inside = [e for e in thread if rx.search(e[2])]
    gaps = xplane.idle_gaps(trace, trace.devices[busy.index(max(busy))])
    idle = sum(g1 - g0 for g0, g1 in gaps)
    if not inside or not idle:
        return None
    covered = sum(breakdown.idle_by_span(gaps, inside).values())
    return 100.0 * (idle - covered) / idle
