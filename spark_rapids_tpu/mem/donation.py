"""Buffer-donation safety registry.

Whole-stage programs (PR 6/PR 10) execute one compiled XLA program per
batch; without input/output aliasing every warm dispatch pays a fresh HBM
allocation for each output column while the dead input columns linger
until Python GC.  `jax.jit(donate_argnums=...)` lets XLA reuse the input
buffers for the outputs — but a donated buffer is DELETED after the call,
so donation is only legal when the dispatching operator is provably the
LAST consumer of the batch.

Static half of the proof: the fusion pass (plan/fusion.py) marks a stage
`donate_inputs` only when its source is a producer whose yielded batches
are fresh per-call device arrays referenced nowhere else (scan decode,
host->device adoption, an upstream whole stage).  Dynamic half: this
registry PINS batches that gained a second owner at runtime —

  * batches registered as spillable buffers (DeviceMemoryStore.add_batch:
    shuffle partition stores, broadcast builds, retry-block checkpoints —
    a later spill would device_get the donated arrays);
  * batches held by the memory-scan cache (re-served to later queries);

and `donatable(batch)` additionally refuses batches whose leaf list
contains duplicate arrays (donating the same buffer twice is an error)
or non-jax leaves.  Pins are held in a WeakSet so they vanish with the
batch object; pinning is monotonic (never unpinned while alive), which
can only cost a missed optimization, never a use-after-free.

Kill switch: `spark.rapids.sql.tpu.donation.enabled` (config.py) — off
restores the prior copy-per-column behavior byte-identically (donation
never changes results, only buffer reuse).
"""
from __future__ import annotations

import threading
import warnings
import weakref

# XLA reports inputs it could not alias into any output (dtype/layout
# mismatch) as a UserWarning per dispatch; the buffers are simply freed
# instead of reused, which is exactly the non-donated behavior — not
# actionable, and noisy at one warning per batch.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable",
    category=UserWarning)

_PINNED: "weakref.WeakSet" = weakref.WeakSet()
# batches whose leaves were handed to a donating dispatch: the arrays are
# DELETED (aliased into the program's outputs), so any later read —
# retry, split, checkpoint registration, de-fuse, CPU fallback — is a
# use-after-free.  Error paths consult consumed() before touching a
# batch that a failed donating dispatch may have eaten (tpulint TPU008).
_DONATED: "weakref.WeakSet" = weakref.WeakSet()
_LOCK = threading.Lock()

# process-wide counters (tests read donated_buffers around warm runs;
# mirrors kernel_cache._COUNTERS style)
_COUNTERS = {"donated_dispatches": 0, "donated_buffers": 0, "pinned": 0}


def pin(batch) -> None:
    """Mark `batch` as multi-owner: it must never be donated."""
    try:
        with _LOCK:
            _PINNED.add(batch)
            _COUNTERS["pinned"] += 1
    except TypeError:  # tpulint: disable=TPU006 non-weakref-able stand-in (tests pass host tables); never donated anyway since its leaves are not jax arrays
        pass


def is_pinned(batch) -> bool:
    with _LOCK:
        return batch in _PINNED


def consumed(batch) -> bool:
    """True when a donating dispatch already ran over `batch`'s leaves —
    its device buffers are gone.  Error-path contract (TPU008): check
    this BEFORE re-reading a batch whose dispatch may have donated."""
    with _LOCK:
        return batch in _DONATED


def donatable(batch) -> bool:
    """True when `batch` may be donated: unpinned, not already consumed
    by a previous donating dispatch, AND its leaves are distinct live
    jax arrays (duplicate leaves — e.g. one Column object projected into
    two slots — would donate one buffer twice)."""
    import jax
    if is_pinned(batch) or consumed(batch):
        return False
    leaves = jax.tree_util.tree_leaves(batch)
    seen = set()
    for leaf in leaves:
        if not isinstance(leaf, jax.Array):
            return False  # numpy/tracer leaf: donation undefined, refuse
        i = id(leaf)
        if i in seen:
            return False
        seen.add(i)
    return True


def record_donation(n_buffers: int) -> None:
    with _LOCK:
        _COUNTERS["donated_dispatches"] += 1
        _COUNTERS["donated_buffers"] += n_buffers


def record_donated_dispatch(batch_or_count, metrics=None) -> int:
    """One-stop bookkeeping for a dispatch that donates `batch_or_count`
    (a ColumnarBatch whose leaves are all donated, or an explicit leaf
    count): this registry's counters, the kernel-cache counter, and the
    dispatching operator's numDonatedBuffers metric.  Returns the leaf
    count."""
    if isinstance(batch_or_count, int):
        n = batch_or_count
    else:
        import jax
        n = len(jax.tree_util.tree_leaves(batch_or_count))
        try:
            with _LOCK:
                _DONATED.add(batch_or_count)
        except TypeError:  # tpulint: disable=TPU006 non-weakref-able stand-in (host tables in tests); those are never jax-donated so the consumed() registry has nothing to guard
            pass
    record_donation(n)
    from ..utils.kernel_cache import record_donated
    record_donated(n)
    if metrics is not None:
        from ..metrics import names as MN
        metrics.add(MN.NUM_DONATED_BUFFERS, n)
    return n


def stats() -> dict:
    with _LOCK:
        return dict(_COUNTERS, live_pins=len(_PINNED),
                    live_consumed=len(_DONATED))


def reset_for_tests() -> None:
    with _LOCK:
        for k in _COUNTERS:
            _COUNTERS[k] = 0
        # consumed-ness is a property of dead batch objects; clearing it
        # between tests is safe (pins stay: pinning is monotonic)
        _DONATED.clear()
