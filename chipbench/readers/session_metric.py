"""A timer or counter of the session (`TpuSession.query_metrics_total`) by
name: its sum over the traced window, per query, times `scale`.  Counters
are sound as they are; timers are host clocks around enqueues, so sound for
host layers (`scanTime`), not for device time."""


def read(ev, name, scale=1.0):
    if name not in ev.counters or not ev.queries:
        return None
    return ev.counters[name] / ev.queries * scale
