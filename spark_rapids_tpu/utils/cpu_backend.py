"""Pin a process to the JAX CPU backend before any backend initializes.

The test suite, the multichip dryrun and the bench's oracle children run
on the CPU even on a machine with a chip: a chip belongs to one process
at a time, and tier-1 runs several workers.  Public configuration only:
`JAX_PLATFORMS=cpu` (inherited by child processes), the same value in
jax's config (the variable alone is too late once jax is imported), and
XLA's virtual-device flag for multi-device meshes.
"""
from __future__ import annotations

import os
from typing import Optional


def force_cpu_backend(n_devices: Optional[int] = None) -> None:
    """Pin this process to the CPU backend; optionally provision `n_devices`
    virtual devices (only effective before the CPU backend initializes)."""
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    jax.config.update("jax_platforms", "cpu")
