"""The share of the traced window, in percent, in which a device op ran
(with `pattern`: an op whose label matches it ran or, if asynchronous, was in
flight): the union of those ops' intervals over the window, on each chip
used.  `pick` takes the chip where the share is largest ("max") or smallest
("min"); `complement` gives 100 minus it, so no pattern, pick "min",
complement true is the idle share of the chip that idles most."""
import xplane


def read(ev, pattern=None, pick="max", complement=False):
    trace = ev.trace
    busy = xplane.busy_per_chip(trace, ev.cell.chips, pattern)
    if not busy or trace.t1 <= trace.t0:
        return None
    shares = [100.0 * ns / (trace.t1 - trace.t0) for ns in busy]
    share = max(shares) if pick == "max" else min(shares)
    return 100.0 - share if complement else share
