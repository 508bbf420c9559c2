"""What `PERF.md` section 5 is written from, out of one traced run's profile:

    python chipbench/run.py --workload <name> --seed 1 --seconds 30 --trace 1 \
        --trace-dir <dir>
    python chipbench/breakdown.py <dir> [chips]

prints one JSON object: the query time inside the traced window, the busiest
chip's idle seconds by the program's innermost `srt:` span of the querying
thread (`idle_by_program_span_s`; what no such span covers is
`idle_outside_program_spans_s`) and by any innermost host span, chip 0's
device milliseconds and launches per query by executable name
(`jit_<layer>.<role>` for the program's own, anything else an eager op), the
querying thread's calls of jitted functions by the `srt:` span they were made
in, how long after its call a launch starts on the chip's clock, and
each `srt:` span's mean milliseconds and occurrences per query over all
threads.  A reduction for people: no metric of `BENCHMARK.json` reads it.
"""
import bisect
import collections
import glob
import json
import os
import re
import statistics
import sys

import xplane

PROGRAM_SPAN = re.compile(r"^srt:")
JIT_CALL = re.compile(r"^PjitFunction\((.*)\)$")


def idle_by_span(gaps, spans):
    """Nanoseconds of the disjoint, ordered `gaps` by the innermost of
    `spans` open at the time."""
    segments = xplane.innermost_segments(spans)
    starts = [seg[0] for seg in segments]
    total = collections.Counter()
    for g0, g1 in gaps:
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(segments) and segments[i][0] < g1:
            s, e, name = segments[i]
            if e > g0:
                total[name] += min(e, g1) - max(s, g0)
            i += 1
    return total


def breakdown(trace, chips=1):
    thread = next(th for th in trace.threads
                  if any(e[2] == xplane.QUERY_SPAN for e in th))
    out = {"queries": trace.queries, "window_s": trace.window_s,
           "traced_query_ms_median": statistics.median(
               (e - s) / 1e6 for s, e, n in thread
               if n == xplane.QUERY_SPAN)}
    spans = collections.defaultdict(list)
    for th in trace.threads:
        for s, e, name in th:
            if PROGRAM_SPAN.search(name) and trace.t0 <= s < trace.t1:
                spans[name].append((e - s) / 1e6)
    out["program_spans"] = {
        name: {"ms_mean": sum(ms) / len(ms),
               "per_query": len(ms) / trace.queries}
        for name, ms in sorted(spans.items())}
    busy = xplane.busy_per_chip(trace, chips)
    if not busy:   # off the chip the trace has no device plane
        return out
    gaps = xplane.idle_gaps(trace, trace.devices[busy.index(max(busy))])
    idle = sum(g1 - g0 for g0, g1 in gaps)
    program = idle_by_span(
        gaps, [e for e in thread if PROGRAM_SPAN.search(e[2])])
    out.update(
        busy_s_per_chip=[ns / 1e9 for ns in busy], idle_s=idle / 1e9,
        idle_by_program_span_s={n: ns / 1e9
                                for n, ns in program.most_common()},
        idle_outside_program_spans_s=(idle - sum(program.values())) / 1e9,
        idle_by_any_span_s={n: ns / 1e9 for n, ns in
                            idle_by_span(gaps, thread).most_common(12)})
    ms, launches = collections.Counter(), collections.Counter()
    started = collections.defaultdict(list)
    for s, e, name in trace.devices[0].launches:
        if trace.t0 <= s < trace.t1:
            name = re.sub(r"\(\d+\)$", "", name)   # the fingerprint
            ms[name] += (e - s) / 1e6
            launches[name] += 1
            started[name].append(s)
    out["device_ms_per_query_by_program"] = {
        n: v / trace.queries for n, v in ms.most_common()}
    out["launches_per_query_by_program"] = {
        n: v / trace.queries for n, v in launches.most_common()}
    # the host's side of the launches, exact on the host's clock: each call
    # of a jitted function (`PjitFunction(<name>)`, eager ops included) by
    # the innermost `srt:` span the querying thread made it in
    segments = xplane.innermost_segments(
        [e for e in thread if PROGRAM_SPAN.search(e[2])])
    seg_starts = [seg[0] for seg in segments]
    issued = collections.defaultdict(collections.Counter)
    called = collections.defaultdict(list)
    open_until = {}
    for s, e, name in thread:
        m = JIT_CALL.match(name)
        if not m or not trace.t0 <= s < trace.t1:
            continue
        if s < open_until.get(name, 0):
            continue   # jax nests a second span of the same call
        open_until[name] = e
        i = bisect.bisect_right(seg_starts, s) - 1
        inside = i >= 0 and s < segments[i][1]
        issued[segments[i][2] if inside else "outside"][m[1]] += 1
        called[m[1]].append(s)
    out["jit_calls_per_query_by_program_span"] = {
        span: {n: v / trace.queries for n, v in names.most_common()}
        for span, names in sorted(issued.items())}
    # how far chip 0's clock runs from the host's: a launch cannot start
    # before its call, so a negative lag is the two timelines' misalignment
    # (it blurs the idle attribution of spans shorter than it)
    lags = [(d - h) / 1e6 for name, hosts in called.items()
            if len(hosts) == len(started.get("jit_" + name, ()))
            for h, d in zip(hosts, started["jit_" + name])]
    if lags:
        out["launch_after_call_ms"] = {"min": min(lags),
                                       "median": statistics.median(lags)}
    return out


if __name__ == "__main__":
    [pb] = glob.glob(os.path.join(sys.argv[1], "plugins", "profile", "*",
                                  "*.xplane.pb"))
    print(json.dumps(breakdown(
        xplane.load(pb), int(sys.argv[2]) if len(sys.argv) > 2 else 1)))
