"""Exclusive-mode TPU discovery (resource scheduler integration).

Reference analogue: ExclusiveModeGpuDiscoveryPlugin
(sql-plugin/.../ExclusiveModeGpuDiscoveryPlugin.scala + the
getGpusResource.sh discovery script): Spark's resource scheduler invokes a
discovery hook per worker that claims an unused accelerator and emits a
ResourceInformation JSON ({"name": ..., "addresses": [...]}).

TPU differences, deliberate:
  * exclusivity is enforced by the PLATFORM, not by this plugin — a TPU
    chip belongs to exactly one process at a time (libtpu's lock), so
    the reference's CUDA try-acquire-retry dance is unnecessary; the claim happens implicitly
    at backend initialization;
  * addresses are jax device ids on the local host; a multi-host slice
    exposes only this host's devices, matching Spark's per-worker
    discovery contract.

`main()` prints the ResourceInformation JSON, so this module doubles as
the discovery *script*:  `python -m spark_rapids_tpu.discovery`.
"""
from __future__ import annotations

import json
from typing import List, Optional


RESOURCE_NAME = "tpu"


def discover_addresses(platform: Optional[str] = None) -> List[str]:
    """Local accelerator device ids, claiming the backend (exclusive mode).

    `platform` pins the jax backend to probe (None = whatever the
    environment resolves; tests pass "cpu" so they never take a
    chip)."""
    import jax
    devices = jax.devices(platform) if platform else jax.devices()
    return [str(d.id) for d in devices]


def resource_information(platform: Optional[str] = None) -> dict:
    """Spark ResourceInformation shape (name + addresses)."""
    return {"name": RESOURCE_NAME,
            "addresses": discover_addresses(platform)}


def main() -> None:  # pragma: no cover - exercised via the function API
    print(json.dumps(resource_information()))


if __name__ == "__main__":  # pragma: no cover
    main()
