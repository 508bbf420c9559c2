"""Executable launches on chip 0 per query: the events of the trace's
`XLA Modules` line that start inside the traced window, over the queries
traced.  Every launch is one dispatch the host paid for."""
import xplane


def read(ev):
    if not ev.trace.devices or not ev.queries:
        return None
    return xplane.launches(ev.trace, ev.trace.devices[0]) / ev.queries
