"""A key of `device.memory_stats()` after the window, times `scale`, on
the chip where it is largest."""


def read(ev, key, scale=1.0):
    values = [m[key] for m in ev.memory if key in m]
    return max(values) * scale if values else None
