"""A host span by name, in milliseconds per occurrence: a span the harness
times on the host's clock (`chipbench:plan`), or else a span in the trace,
the harness's `chipbench:collect` or one of the program's own
`TraceAnnotation`s (`utils/tracing.py named_range`)."""


def read(ev, span):
    if span in ev.spans:
        seconds, n = ev.spans[span]
        return seconds / n * 1e3 if n else None
    found = [e - s for th in ev.trace.threads for s, e, name in th
             if name == span and ev.trace.t0 <= s < ev.trace.t1]
    return sum(found) / len(found) / 1e6 if found else None
