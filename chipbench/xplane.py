"""The reduction from a profiler trace (`.xplane.pb`) to what the per-layer
metrics and the breakdown read: device busy time as a union of op
intervals, executable launches, idle gaps attributed to what the host was
doing, and the device ops that took most time.

    python chipbench/xplane.py <file.xplane.pb>      # what a trace holds

Read with `jax.profiler.ProfileData` and nothing else.  On a TPU every chip
is a plane `/device:TPU:<n>` whose line `XLA Ops` holds one event per device
op and whose line `XLA Modules` one per executable launch; host threads are
the lines of `/host:CPU`, where `jax.profiler.TraceAnnotation`s (the
harness's `chipbench:collect`, the program's own) and JAX's runtime spans
land.  All planes share one clock.  The traced window is the span from the
first `chipbench:collect` to the end of the last: what lies outside (the
profiler starting and stopping) is clipped away.
"""
import bisect
import gzip
import re
import sys
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, LAUNCH_LINE = "XLA Ops", "XLA Modules"
ASYNC_LINE = "Async XLA Ops"   # copies and collectives in flight
# a device op's event is named by its whole HLO instruction:
# `%fusion.200 = (f32[1025]{0:T(1024)S(1)}, ...) fusion(...), ...`
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<shape>.*?) [\w-]+\(")
HOST_PLANE = "/host:CPU"
QUERY_SPAN = "chipbench:collect"


@dataclass
class Device:
    index: int
    ops: list = field(default_factory=list)        # (start, end, label)
    async_ops: list = field(default_factory=list)  # (start, end, label)
    launches: list = field(default_factory=list)   # (start, end, name)


@dataclass
class Trace:
    devices: list    # of Device, by index
    threads: list    # per host thread, its (start, end, name), by start
    t0: int          # the traced window, nanoseconds on the trace's clock
    t1: int
    queries: int     # `chipbench:collect` spans in it

    @property
    def window_s(self):
        return (self.t1 - self.t0) / 1e9


def load(path):
    """`path` (.xplane.pb, or .xplane.pb.gz as the tests keep it) -> Trace."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, threads = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device(int(m.group(1)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = _events(line, op_label)
                elif line.name == ASYNC_LINE:
                    dev.async_ops = _events(line, op_label)
                elif line.name == LAUNCH_LINE:
                    dev.launches = _events(line)
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            threads = [_events(line) for line in plane.lines]
    spans = [e for th in threads for e in th if e[2] == QUERY_SPAN]
    if not spans:
        raise ValueError(f"{path}: no {QUERY_SPAN} span, so no window")
    return Trace(devices=sorted(devices, key=lambda d: d.index),
                 threads=threads, t0=min(s[0] for s in spans),
                 t1=max(s[1] for s in spans), queries=len(spans))


def _events(line, label=str):
    return sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                   label(e.name)) for e in line.events)


def op_label(text):
    """`<instruction, its number dropped>[:<custom-call target>] <result
    shape, layouts dropped>` of an HLO instruction's text, so that the ops
    of one kind and shape share a label: `fusion f32[1025], f32[1025]`,
    `custom-call:X64SplitHigh f32[1048576]`, `all-to-all-start ...`."""
    m = _HLO.match(text)
    if not m:
        return text
    kind = re.sub(r"\.\d+$", "", m["name"])
    target = re.search(r'custom_call_target="([^"]+)"', text)
    shape = re.sub(r"\{[^}]*\}", "", m["shape"]).strip("()")
    return f"{kind}{':' + target[1] if target else ''} {shape}"


def merged(intervals, t0, t1):
    """The union of (start, end, ...) intervals clipped to [t0, t1], as
    disjoint (start, end) in order."""
    out = []
    for iv in sorted((max(iv[0], t0), min(iv[1], t1)) for iv in intervals):
        if iv[1] <= iv[0]:
            continue
        if out and iv[0] <= out[-1][1]:
            if iv[1] > out[-1][1]:
                out[-1] = (out[-1][0], iv[1])
        else:
            out.append(iv)
    return out


def busy_ns(trace, device, pattern=None):
    """Nanoseconds of the window in which an op of `device` ran.  With
    `pattern`: in which an op whose label it matches ran or, where the op
    is asynchronous (a collective or copy between its start and done), was
    in flight."""
    ops = device.ops
    if pattern is not None:
        rx = re.compile(pattern)
        ops = [op for op in ops + device.async_ops if rx.search(op[2])]
    return sum(e - s for s, e in merged(ops, trace.t0, trace.t1))


def busy_per_chip(trace, chips, pattern=None):
    """`busy_ns` of each of the first `chips` devices, by index."""
    return [busy_ns(trace, d, pattern) for d in trace.devices[:chips]]


def launches(trace, device):
    """Executable launches of `device` that started inside the window."""
    return sum(1 for s, _, _ in device.launches if trace.t0 <= s < trace.t1)


def idle_gaps(trace, device):
    """The window's intervals in which no op of `device` ran."""
    gaps, at = [], trace.t0
    for s, e in merged(device.ops, trace.t0, trace.t1):
        if s > at:
            gaps.append((at, s))
        at = e
    if trace.t1 > at:
        gaps.append((at, trace.t1))
    return gaps


def innermost_segments(thread):
    """One host thread's nested spans, flattened to disjoint (start, end,
    name) labelled by the innermost span open at the time."""
    out, stack = [], []   # stack of (end, name)
    at = None

    def emit(until):
        nonlocal at
        if stack and until > at:
            out.append((at, until, stack[-1][1]))
        at = until

    for s, e, name in sorted(thread, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        if stack:
            emit(s)
        at = s
        stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def attribute_gaps(trace, device):
    """Seconds of `device`'s idle gaps by what the querying thread was in
    (the innermost host span), longest first."""
    thread = next(th for th in trace.threads
                  if any(e[2] == QUERY_SPAN for e in th))
    segments = innermost_segments(thread)
    starts = [s[0] for s in segments]
    total = {}
    for g0, g1 in idle_gaps(trace, device):
        covered = 0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(segments) and segments[i][0] < g1:
            s, e, name = segments[i]
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                total[name] = total.get(name, 0) + overlap
                covered += overlap
            i += 1
        if g1 - g0 > covered:
            total["no_host_span"] = (total.get("no_host_span", 0)
                                     + g1 - g0 - covered)
    return sorted(((clean(n), ns / 1e9) for n, ns in total.items()),
                  key=lambda kv: -kv[1])


def top_ops(trace, device, n=10):
    """The `n` kinds of op of `device` that took most of the window, as
    [label_xCOUNT, seconds]: ops of one label (`op_label`) are summed."""
    total, count = {}, {}
    for s, e, name in device.ops:
        s, e = max(s, trace.t0), min(e, trace.t1)
        if e > s:
            key = re.sub(r"\.\d+$", "", name)
            total[key] = total.get(key, 0) + e - s
            count[key] = count.get(key, 0) + 1
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[f"{clean(k)}_x{count[k]}", ns / 1e9] for k, ns in top]


def clean(name):
    """A trace's name in the characters a metric's name may have."""
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", name).strip("_")[:56]


def describe(path, events_per_line=3):
    """Print what a trace holds: planes, lines, and the first events of
    each line with their stats.  Look at one by hand before writing a
    reader against it."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for e in events[:events_per_line]:
                stats = {k: (v if len(str(v)) < 120 else str(v)[:120] + "...")
                         for k, v in e.stats}
                print(f"    {e.name!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} {stats}")


if __name__ == "__main__":
    describe(sys.argv[1])
