"""Whatever compiled inside the window: `jax.monitoring` compile requests
(eager programs included) plus the program's `kernel_cache.stats()`
`builds` and `stage_compiles`.  0 is the only good value."""


def read(ev):
    return float(ev.compiles)
