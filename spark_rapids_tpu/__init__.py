"""spark_rapids_tpu — a TPU-native columnar SQL acceleration framework.

A from-scratch JAX/XLA implementation of the capability surface of the
RAPIDS Accelerator for Apache Spark (plan rewrite -> columnar device operators
-> tiered device memory -> columnar file I/O -> device-resident shuffle),
designed for TPU: static-shape bucketed batches, whole-pipeline jit
compilation, sort-based joins/aggregations, and ICI all-to-all shuffle over a
`jax.sharding.Mesh`.
"""
__version__ = "0.1.0"

import jax as _jax

# LongType/DoubleType columns require real int64/float64 semantics; without
# x64 JAX silently truncates to 32-bit and the CPU-vs-TPU oracle diverges.
_jax.config.update("jax_enable_x64", True)

from . import types  # noqa: F401
from .config import TpuConf  # noqa: F401
from .columnar import Column, ColumnarBatch  # noqa: F401
from .plan.logical import Window, WindowSpec  # noqa: F401
