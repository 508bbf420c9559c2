"""Test harness setup: run everything on a virtual 8-device CPU mesh so
multi-chip sharding is exercised without TPU hardware (the driver separately
dry-runs the multichip path)."""
import os

# float64 columns are part of the supported type surface.  The variable
# only helps subprocesses once jax is imported, so the config is ALSO set
# below.
os.environ.setdefault("JAX_ENABLE_X64", "1")

# force CPU + 8 virtual devices: tests never use an accelerator, even on a
# machine that has one (a chip belongs to one process at a time, and the
# suite runs several workers)
from spark_rapids_tpu.utils.cpu_backend import force_cpu_backend  # noqa: E402

force_cpu_backend(n_devices=8)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

# XLA:CPU segfaults inside backend_compile after a few thousand compiled
# executables accumulate in one process (observed deterministically around
# ~80% of this suite, always inside a jit compile, regardless of which
# test compiles there; the same tests pass in a fresh process).  Dropping
# the compilation caches periodically bounds live executable count; the
# handful of retraces that follow cost seconds, a crashed suite costs
# everything.
_TESTS_PER_CACHE_CLEAR = 40
_test_count = {"n": 0}


@pytest.fixture(autouse=True)
def _bound_xla_code_memory():
    yield
    _test_count["n"] += 1
    if _test_count["n"] % _TESTS_PER_CACHE_CLEAR == 0:
        jax.clear_caches()
        # whole-stage AOT executables live OUTSIDE jax's caches (they
        # would survive clear_caches and defeat this bound)
        from spark_rapids_tpu.utils import kernel_cache
        kernel_cache.clear_stage_executables()


@pytest.fixture(autouse=True)
def _reset_fault_injector():
    """Disarm + zero the process-global fault injector around every test so
    the `faultinject` tier's ordinals are deterministic and no armed spec
    leaks into unrelated tests (the `adaptive` tier's discover-then-replay
    OOM tests rely on the same reset)."""
    from spark_rapids_tpu.utils import faults
    faults.INJECTOR.reset()
    yield
    faults.INJECTOR.reset()
