"""Whose launch is it: every executable launch on chip 0 under the span of
the program in which its HOST CALL was made.

A launch starts on the chip long after its call once the queue is deep, and
a pool thread's launch has nothing to do with what the querying thread is
in, so the span open at the launch's start on the chip says little.  The
trace holds the call: JAX leaves `PjitFunction(<name>)` on the calling
thread's line for every launch of `jit_<name>`, eager ops included, on the
clock the chips share.  The calls of ALL host threads (jax nests a twin of
each call inside it: dropped), in start order, are matched in order to the
launches of the program of that name over the whole trace.  The programs
whose calls and launches differ in number are matched in order TOGETHER if
together their numbers agree (an executable shared by two functions runs
under one's name); if not, they cannot be matched and their launches are
`unowned`.  Equal numbers alone prove nothing (a call lost at one edge of
the trace and a launch at the other agree too), so every pair is then held
to what the chip guarantees: a launch does not start before its call, and
one thread's launches run in the order it called them.  The chips' clock
runs ahead of the host's by an offset of the TRACE (0.2 to 1.3 ms in PR
35's), so "before its call" is read against the trace itself: a launch
that starts more than `CLOCK_JITTER_NS` earlier, against its call, than
all but a hundredth of the trace's launches do.  A pair of a program whose numbers agree that is out of order
with another such pair on its thread is `unowned`, both of them; a pair
matched together with other programs has to start between the launches of
its thread's nearest such calls before and after it, or is `unowned`.  The
owner of a matched launch is the innermost `srt:op:` span
(`exec/base.py`: one a pull of an operator, `srt:op:<ClassName>@<node
id>`) open on the CALLING thread when the call started; where that thread
has none open, its innermost `srt:` span other than `srt:execute` (a pool
thread's `srt:scan_column`, `srt:metrics_fold`); else `unowned`.  Launches
are then clipped to the traced window by their start on the chip.

`read(ev, owner, what, eager)`: the launches whose owner's name `owner` (a
pattern, searched) finds; `what` is "device_ms" (their device time, per
query), "count" (per query) or "share" (% of the device time of all the
window's launches); `eager` True / False takes only the launches not
named / named `jit_<layer>.` (`utils/kernel_cache.named_jit`).  `None`
where the trace has no device or no `srt:op:` span (a program without
them: nothing to read).

    python chipbench/readers/launch_owner.py <trace-dir> [chips]

prints one JSON object, for people: per owner its device ms, launches,
eager device ms and eager launches per query and by program, the host ms of
its calls, the busiest chip's idle seconds by the querying thread's owner,
the owners' sum against all launches, the programs whose numbers differ,
the launches a query for which no call was found, and how long after its
call a launch starts.
"""
import bisect
import collections
import itertools
import os
import re
import sys

try:
    import xplane
except ImportError:   # run as a script: `chipbench/` is one directory up
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import xplane

OP_SPAN = re.compile(r"^srt:op:")
PROGRAM_SPAN = re.compile(r"^srt:(?!execute$)")
JIT_CALL = re.compile(r"^PjitFunction\((.*)\)$")
NAMED_PROGRAM = re.compile(r"^jit_[a-z]+\.")   # jit_<layer>.<role>
UNOWNED = "unowned"
#: how far below the trace's own clock offset (the first percentile of
#: launch start less call start) a launch may start before it cannot be
#: that call's
CLOCK_JITTER_NS = 1_000_000

Launch = collections.namedtuple(
    "Launch", "start end program owner call_start call_end")


def _program(name):
    """A launch's or a call's name in the form both sides share: the
    fingerprint after a launch's name dropped, and the `jit(...)` around
    the name of a function that was jitted under a name of its own."""
    name = re.sub(r"^jit\((.*)\)$", r"\1", re.sub(r"\(\d+\)$", "", name))
    return re.sub(r"[^\w.]", "_", name)


def owner_segments(thread):
    """One host thread as disjoint (start, end, owner) by start: the
    innermost `srt:op:` span open, else the innermost other span of the
    program but `srt:execute` (a hand-placed span inside an operator's pull
    does not take the time from the operator)."""
    ops = xplane.innermost_segments(
        [e for e in thread if OP_SPAN.search(e[2])])
    spans = xplane.innermost_segments(
        [e for e in thread if PROGRAM_SPAN.search(e[2])])
    cuts = sorted({t for s, e, _ in ops + spans for t in (s, e)})
    op_at, span_at = _at(ops), _at(spans)
    found = ((a, b, op_at(a) or span_at(a)) for a, b in zip(cuts, cuts[1:]))
    return [seg for seg in found if seg[2]]


def _at(segments):
    """-> f(t): the name of the one of the disjoint `segments` that holds
    t, or None."""
    starts = [seg[0] for seg in segments]

    def at(t):
        i = bisect.bisect_right(starts, t) - 1
        return segments[i][2] if i >= 0 and t < segments[i][1] else None
    return at


def host_calls(threads):
    """Every call of a jitted function on any thread as (start, end,
    program, thread index), by start."""
    out = []
    for i, thread in enumerate(threads):
        open_until = {}
        for s, e, name in thread:
            m = JIT_CALL.match(name)
            if not m or s < open_until.get(name, 0):
                continue   # not a call, or the twin jax nests in one
            open_until[name] = e
            out.append((s, e, "jit_" + _program(m[1]), i))
    return sorted(out)


def owned_launches(trace):
    """Chip 0's launches of the whole trace as `Launch`es by start, or
    `None` where there is no device or no `srt:op:` span."""
    if not trace.devices or not any(
            OP_SPAN.search(e[2]) for th in trace.threads for e in th):
        return None
    calls = collections.defaultdict(list)
    for call in host_calls(trace.threads):
        calls[call[2]].append(call)
    launches = collections.defaultdict(list)
    for s, e, name in trace.devices[0].launches:
        program = _program(name)
        launches[program].append((s, e, program))
    pairs, odd_launches, odd_calls = [], [], []
    for program in sorted(set(launches) | set(calls)):
        found, made = launches.get(program, []), calls.get(program, [])
        if len(found) == len(made):
            pairs += zip(sorted(found), made)
        else:
            odd_launches += found
            odd_calls += made
    pooled = []
    if len(odd_launches) == len(odd_calls):
        # an executable the compile cache handed to two functions runs
        # under the name of the one that compiled it (`_reduce_sum` over one
        # element is `broadcast_in_dim`'s): such programs' counts differ
        # one by one and agree together, and match in order together
        pooled = list(zip(sorted(odd_launches), sorted(odd_calls)))
        odd_launches = []
    pairs, pooled, refused = in_call_order(pairs, pooled)
    owner_of = {}
    out = [Launch(s, e, program, UNOWNED, None, None)
           for s, e, program in odd_launches + refused]
    for (s, e, program), (cs, ce, _, th) in pairs + pooled:
        if th not in owner_of:
            owner_of[th] = _at(owner_segments(trace.threads[th]))
        out.append(Launch(s, e, program, owner_of[th](cs) or UNOWNED, cs, ce))
    return sorted(out)


def in_call_order(pairs, pooled):
    """(launch, call) pairs of the programs whose numbers agree, and of
    those matched together -> the pairs of each kind that can be right, and
    the launches of those that cannot: one that starts before its call
    (by the trace's own clock offset), a pair of `pairs` out of order with
    another of its thread, a pair of `pooled` that does not start between
    its thread's neighbours in what `pairs` keeps."""
    lags = sorted(launch[0] - call[0] for launch, call in pairs + pooled)
    floor = lags[len(lags) // 100] - CLOCK_JITTER_NS if lags else 0

    def early(launch, call):
        return launch[0] - call[0] < floor

    refused = [launch for launch, call in pairs + pooled
               if early(launch, call)]
    by_thread = collections.defaultdict(list)
    for launch, call in pairs:
        if not early(launch, call):
            by_thread[call[3]].append((launch, call))
    kept, anchors = [], {}
    for th, own in by_thread.items():
        own.sort(key=lambda pair: pair[1])
        starts = [launch[0] for launch, _ in own]
        # in order: after every launch called before, before every one after
        after = list(itertools.accumulate(starts[::-1], min))[::-1]
        before = list(itertools.accumulate(starts, max))
        ok = [(i == 0 or before[i - 1] < s)
              and (i + 1 == len(own) or s < after[i + 1])
              for i, s in enumerate(starts)]
        refused += [own[i][0] for i, good in enumerate(ok) if not good]
        good = [own[i] for i, good in enumerate(ok) if good]
        kept += good
        anchors[th] = ([call[0] for _, call in good],
                       [launch[0] for launch, _ in good])
    fitting = []
    for launch, call in pooled:
        if early(launch, call):
            continue
        called, started = anchors.get(call[3], ([], []))
        i = bisect.bisect_right(called, call[0])
        if (i == 0 or started[i - 1] < launch[0]) and (
                i == len(called) or launch[0] < started[i]):
            fitting.append((launch, call))
        else:
            refused.append(launch)
    return kept, fitting, refused


def in_window(trace, launches):
    return [ln for ln in launches if trace.t0 <= ln.start < trace.t1]


def is_eager(program):
    return not NAMED_PROGRAM.search(program)


def read(ev, owner, what="device_ms", eager=None):
    launches = owned_launches(ev.trace)
    if launches is None or not ev.queries:
        return None
    launches = in_window(ev.trace, launches)
    rx = re.compile(owner)
    picked = [ln for ln in launches if rx.search(ln.owner)
              and (eager is None or is_eager(ln.program) == eager)]
    if what == "count":
        return len(picked) / ev.queries
    ns = sum(ln.end - ln.start for ln in picked)
    if what == "device_ms":
        return ns / 1e6 / ev.queries
    if what == "share":
        total = sum(ln.end - ln.start for ln in launches)
        return 100.0 * ns / total if total else None
    raise ValueError("launch_owner: what is 'device_ms', 'count' or "
                     f"'share', not {what!r}")


def idle_by_owner(trace, chips):
    """Seconds of the busiest chip's idle gaps by the QUERYING thread's
    owner at the time (`srt:execute` itself and what no span covers read
    `unowned`)."""
    import breakdown
    busy = xplane.busy_per_chip(trace, chips)
    thread = next(th for th in trace.threads
                  if any(e[2] == xplane.QUERY_SPAN for e in th))
    gaps = xplane.idle_gaps(trace, trace.devices[busy.index(max(busy))])
    total = breakdown.idle_by_span(gaps, owner_segments(thread))
    total[UNOWNED] += sum(g1 - g0 for g0, g1 in gaps) - sum(total.values())
    return {n: ns / 1e9 for n, ns in total.most_common() if ns}


def report(trace, chips=1):
    import statistics
    launches = owned_launches(trace)
    out = {"queries": trace.queries, "window_s": trace.window_s}
    if launches is None:
        return out
    q = trace.queries
    every = in_window(trace, launches)
    by = collections.defaultdict(list)
    for ln in every:
        by[ln.owner].append(ln)

    def ms(lns):
        return sum(ln.end - ln.start for ln in lns) / 1e6 / q

    def by_program(lns):
        groups = collections.defaultdict(list)
        for ln in lns:
            groups[ln.program].append(ln)
        return {p: [ms(g), len(g) / q] for p, g in sorted(
            groups.items(), key=lambda kv: -ms(kv[1]))[:12]}

    out["owners"] = {
        owner: {"device_ms": ms(lns), "launches": len(lns) / q,
                "eager_device_ms": ms([ln for ln in lns
                                       if is_eager(ln.program)]),
                "eager_launches": sum(is_eager(ln.program)
                                      for ln in lns) / q,
                "host_call_ms": sum(ln.call_end - ln.call_start for ln in lns
                                    if ln.call_start is not None) / 1e6 / q,
                "ms_and_launches_by_program": by_program(lns)}
        for owner, lns in sorted(by.items(), key=lambda kv: -ms(kv[1]))}
    out["device_ms_all_launches"] = ms(every)
    out["device_ms_owners_sum"] = sum(o["device_ms"]
                                      for o in out["owners"].values())
    out["unowned_share"] = (100.0 * ms(by.get(UNOWNED, []))
                            / ms(every) if every else None)
    calls = collections.Counter(c[2] for c in host_calls(trace.threads))
    found = collections.Counter(ln.program for ln in launches)
    out["unmatched_programs_calls_launches"] = {
        p: [calls.get(p, 0), n] for p, n in sorted(found.items())
        if calls.get(p, 0) != n}
    out["launches_no_call_found"] = sum(ln.call_start is None
                                        for ln in every) / q
    lags = [(ln.start - ln.call_start) / 1e6 for ln in every
            if ln.call_start is not None]
    if lags:
        out["launch_after_call_ms"] = {"min": min(lags),
                                       "median": statistics.median(lags)}
    out["idle_s_by_owner"] = idle_by_owner(trace, chips)
    return out


if __name__ == "__main__":
    import glob
    import json
    [pb] = glob.glob(os.path.join(sys.argv[1], "plugins", "profile", "*",
                                  "*.xplane.pb"))
    print(json.dumps(report(
        xplane.load(pb), int(sys.argv[2]) if len(sys.argv) > 2 else 1)))
