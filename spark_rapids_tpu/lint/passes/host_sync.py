"""TPU001 — host-sync hazards.

The paper's perf model (and ROADMAP items 2/3) lives or dies on keeping
the device pipeline free of incidental device->host synchronization: one
stray `.item()` in a per-batch loop serializes the whole stage behind a
host round trip (a host-link RTT on a chip).  This pass flags the
expression forms that force a transfer:

  * `<x>.item()`                       — scalar pull
  * `np.asarray(x)` / `numpy.asarray`  — whole-array materialization
  * `jax.device_get(x)` / `device_get` — explicit pull
  * `int(...)/float(...)/bool(...)` over a jnp./jax. expression —
    implicit scalar sync (`int(jnp.sum(x))`)

Layers whose JOB is the host boundary are allowlisted wholesale (file I/O
encode/decode control planes, the CPU oracle, arrow conversion); the
hot-path layers (exec/, mem/, ops/ device kernels, shuffle/) carry their
historic sites in the baseline — every NEW site there must justify
itself with an inline suppression reason or get moved off the hot path.
"""
from __future__ import annotations

import ast
from typing import Iterable

from ..core import FileContext, Finding, LintPass
from . import _util as U

#: path fragments where device->host transfer is the layer's purpose:
#: file-format encode/decode host control planes, the CPU expression
#: oracle and CPU relational operators, and arrow interop in columnar/
ALLOWED_PATH_PARTS = (
    "spark_rapids_tpu/io/",
    "spark_rapids_tpu/ops/cpu_eval.py",
    "spark_rapids_tpu/exec/cpu_relational.py",
    "spark_rapids_tpu/columnar/",
)

_PULL_CALLS = {"np.asarray", "numpy.asarray", "jax.device_get",
               "device_get"}
_COERCIONS = {"int", "float", "bool"}


def _mentions_device_api(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        name = None
        if isinstance(sub, (ast.Attribute, ast.Name)):
            name = U.dotted_name(sub)
        if name and (name.startswith("jnp.") or name.startswith("jax.")
                     or name in ("jnp", "jax")):
            return True
    return False


class HostSyncPass(LintPass):
    rule_id = "TPU001"
    cacheable = True
    name = "host-sync-hazard"
    doc = ("device->host synchronization outside allowlisted host-boundary "
           "layers (.item(), np.asarray, device_get, int/float/bool over "
           "a jax expression)")
    scopes = ("package",)

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        rel = ctx.rel_path.replace("\\", "/")
        if any(part in rel for part in ALLOWED_PATH_PARTS):
            return
        for call in U.walk_calls(ctx.tree):
            name = U.call_name(call)
            # <x>.item() — any receiver: there is no non-sync .item()
            if isinstance(call.func, ast.Attribute) \
                    and call.func.attr == "item" and not call.args \
                    and not call.keywords:
                yield Finding(self.rule_id, ctx.rel_path, call.lineno,
                              "host-sync hazard: .item() pulls a device "
                              "scalar to the host; hoist it off the "
                              "per-batch path or suppress with a reason",
                              span_end=U.span_end(call))
            elif name in _PULL_CALLS:
                yield Finding(self.rule_id, ctx.rel_path, call.lineno,
                              f"host-sync hazard: {name}() materializes "
                              "device data on the host; keep the hot path "
                              "device-resident or suppress with a reason",
                              span_end=U.span_end(call))
            elif name in _COERCIONS and len(call.args) == 1 \
                    and _mentions_device_api(call.args[0]):
                yield Finding(self.rule_id, ctx.rel_path, call.lineno,
                              f"host-sync hazard: {name}() over a jax "
                              "expression blocks on the device; fold it "
                              "lazily (metrics add_lazy) or batch the "
                              "transfer",
                              span_end=U.span_end(call))
