"""Shared persistent-XLA-compilation-cache setup.

ONE idempotent helper owns jax's persistent-cache configuration so the
knobs cannot drift between call sites: `engine.TpuSession`, the serving
tier's QueryScheduler (a restarted server replays kernels from disk) and
the executor worker bootstrap (shuffle/worker.py).

Where the cache lives (the path is part of jax's cache key, so a
directory that moves never hits):

  * `JAX_COMPILATION_CACHE_DIR` set in the environment: jax reads the
    variable itself; this helper leaves its handling alone and only
    lowers the write thresholds.  No code re-points the directory.
  * unset: the caller's path (`spark.rapids.sql.tpu.compilationCache.dir`)
    or, when that is empty, `.jax_cache/` at the root of this checkout.

Backend gate (force=False): on an accelerator a compile costs seconds to
minutes (64-bit sorts and scans compile slowest, see CHANGES.md PR 22)
and replays byte-identically, but XLA:CPU AOT replay warns about
machine-feature mismatches and the CPU test environment already fights
compile-cache memory pressure — so when the backend IN USE is the CPU
the cache stays off unless the caller forces it.  The gate asks the
backend itself: environment variables are empty on a machine that simply
has a chip.

Re-pointing (environment variable unset): a server picking up a conf
change, or a test pointing at a tmpdir, calls enable_compilation_cache
with the new path and jax follows.  `active_cache_dir()` reports what is
in effect and `reset_for_tests()` restores the pristine state.
"""
from __future__ import annotations

import os
import threading
from typing import Optional

#: fixed default inside the checkout (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# the directory this process's cache is known to use (None = never
# enabled by this helper); the lock serializes concurrent enables from
# scheduler construction vs. worker first-touch (TPU009)
_STATE = {"path": None}
_STATE_LOCK = threading.Lock()


def enable_compilation_cache(path: str = "", force: bool = False) -> bool:
    """Turn jax's persistent compilation cache on (idempotent per
    directory; returns True when THIS call enabled or re-pointed it).
    Keyed by HLO hash, shared across processes: a second session replays
    every kernel this one compiled."""
    import jax
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    target = env_dir or path or DEFAULT_CACHE_DIR
    if _STATE["path"] == target:
        return False  # already in effect — idempotent fast path
    if not force and jax.default_backend() == "cpu":
        # NOT latched: a later force=True call (bench child) may still
        # enable the cache in this process
        return False
    with _STATE_LOCK:
        if _STATE["path"] == target:
            return False  # a concurrent enabler won the race
        if not env_dir:
            jax.config.update("jax_compilation_cache_dir", target)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1)
        _STATE["path"] = target
    return True


def active_cache_dir() -> Optional[str]:
    """The directory this helper last enabled, or None."""
    return _STATE["path"]


def reset_for_tests() -> None:
    """Test-only: forget the active path and detach jax from it, so the
    next enable_compilation_cache() call can re-point cleanly from a
    known state."""
    _STATE["path"] = None
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", None)
