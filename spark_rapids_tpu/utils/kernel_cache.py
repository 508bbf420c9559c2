"""Process-wide compiled-kernel cache.

jax.jit's own cache is keyed by function identity, but the execs build fresh
closures every plan/execute, so without this layer each collect() re-traces
and re-compiles every kernel (the reference has no analogue — cuDF kernels
are precompiled; for us compilation IS the kernel-build step, so caching it
is what makes repeated/streaming queries cheap).

Keys are structural: (kernel kind, expression-tree signature, schema
signature).  Shape/dtype differences of the incoming batches are handled by
jit itself underneath one cache entry.
"""
from __future__ import annotations

import contextlib
import functools
import re
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, Tuple

import jax


_CACHE: Dict[tuple, Callable] = {}
# guards the LRU bookkeeping below and _CACHE build races: the serving
# tier dispatches kernels from several query threads at once, and
# OrderedDict.move_to_end is not safe under concurrent mutation
_CACHE_LOCK = threading.Lock()

# whole-stage AOT executables, keyed (stage key, input signature): the
# fused-stage path compiles per exact shape bucket so compile COUNT and
# trace-vs-compile time are first-class observables (exec/whole_stage.py).
# Bounded LRU: compiled executables are NOT dropped by jax.clear_caches(),
# so an unbounded dict would defeat the conftest's periodic cache clears
# that keep XLA:CPU's live-executable count under its segfault threshold.
_STAGE_EXECUTABLES: "OrderedDict[tuple, Callable]" = OrderedDict()
_STAGE_EXECUTABLES_MAX = 512

# process-wide counters (stats(); chipbench/run.py reads builds and
# stage_compiles around its window, the tests read the rest):
# builds = distinct jitted programs constructed through cached_kernel,
# stage_compiles = AOT whole-stage programs compiled,
# dispatches = per-batch device program invocations through this layer,
# kernel_hits/stage_hits = cache hits (a parameterized plan-cache hit
# shows up here as stage/kernel hits instead of fresh builds)
_COUNTERS = {"builds": 0, "stage_compiles": 0, "dispatches": 0,
             "kernel_hits": 0, "stage_hits": 0, "donated_buffers": 0}


# --- program names ----------------------------------------------------------
# Every executable this module jits is named `<layer>.<role>`, so a
# profiler trace's `XLA Modules` line and jax's `PjitFunction(...)` host
# spans read `jit_agg.whole_stage_bucket`, `jit_scan.pq_sdict`,
# `jit_dist.join_probe` instead of the closure's name (`jit_k`).  What
# stays un-named in a trace is then an eager op outside any compiled
# program.  THE table: the module a program's builder lives in (relative
# to the package; a package name covers its modules) -> its layer.
_LAYER_OF_MODULE = {
    "io": "scan",
    "exec.aggregate": "agg",
    "streaming.state": "agg",
    "exec.join": "join",
    "exec.sort": "sort",
    "exec.window": "sort",
    "exec.whole_stage": "stage",
    "exec.exchange": "stage",
    "exec.distributed": "dist",
    "parallel.distributed": "dist",
    "shuffle.mesh_exchange": "dist",
    "columnar.contiguous": "mem",
    "exec.basic": "expr",
    "exec.generate": "expr",
}
_PACKAGE = __name__.split(".")[0] + "."
_OPERATOR_CLASS = re.compile(r"(?:Tpu)?(\w+?)Exec")
_ROLE_WORD = re.compile(r"[A-Za-z][\w-]{0,31}")


def program_layer(builder) -> str:
    """The layer of the program `builder` builds, from the module the
    builder was written in.  An unmapped module is an error: a program
    nobody can place must not reach a trace as a silent `misc`."""
    while isinstance(builder, functools.partial):
        builder = builder.func
    module = getattr(builder, "__module__", None) or ""
    rel = module.removeprefix(_PACKAGE)
    while rel:
        if rel in _LAYER_OF_MODULE:
            return _LAYER_OF_MODULE[rel]
        rel = rel.rpartition(".")[0]
    raise KeyError(
        f"kernel_cache: no layer for a program built in {module!r}; add "
        f"the module to kernel_cache._LAYER_OF_MODULE")


def program_role(key: tuple) -> str:
    """The role a cache key states: its string head (`pq_sdict`,
    `contig_pack`, `whole_stage`; an operator's class name is shortened,
    `TpuHashJoinExec` -> `hashjoin`) and, where the call site appended a
    word after the key's last structural (tuple) element, that word
    (`... + ("probe", guess)` -> `hashjoin_probe`)."""
    head = key[0] if key else None
    if not isinstance(head, str) or not _ROLE_WORD.fullmatch(head):
        raise ValueError(
            f"kernel_cache: a cache key starts with the program's role, "
            f"a short word; got {head!r}")
    op = _OPERATOR_CLASS.fullmatch(head)
    role = op[1].lower() if op else head
    tuples = [i for i, x in enumerate(key) if isinstance(x, tuple)]
    if tuples and tuples[-1] + 1 < len(key):
        word = key[tuples[-1] + 1]
        if isinstance(word, str) and _ROLE_WORD.fullmatch(word):
            role += "_" + word
    return role


def named_jit(builder: Callable[[], Callable], role: str, **jit_kw):
    """`jax.jit` of what `builder` builds, named `<layer>.<role>`: the one
    way a program of this package is jitted.  The name is what `jax.jit`
    reads for the executable (`jit_<name>`) and its `PjitFunction(<name>)`
    host span; it goes on a wrapper, because bound methods and partials
    take no `__name__`."""
    fn = builder()

    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = \
        f"{program_layer(builder)}.{role}"
    return jax.jit(program, **jit_kw)


def record_dispatch(n: int = 1) -> None:
    # dict[k] += n is a read-modify-write: under concurrent serving the
    # scheduler's worker threads dispatch simultaneously and an unlocked
    # fold silently loses counts (the tests assert them exactly)
    with _CACHE_LOCK:
        _COUNTERS["dispatches"] += n


def record_donated(n_buffers: int) -> None:
    """Count input buffers donated to a compiled program (the HBM copies
    a warm dispatch did not pay), read like dispatches around warm
    runs."""
    with _CACHE_LOCK:
        _COUNTERS["donated_buffers"] += n_buffers


def stats() -> Dict[str, int]:
    with _CACHE_LOCK:
        return dict(_COUNTERS, cached_kernels=len(_CACHE),
                    stage_executables=len(_STAGE_EXECUTABLES))


def input_signature(args) -> tuple:
    """Static (shape, dtype, placement) signature of a pytree of arguments
    — the shape-bucket key of a whole-stage executable.  Placement is part
    of it because an AOT executable is compiled for its inputs' shardings
    and REJECTS the same shapes on another device: after a mesh exchange
    partition i lives on device i, and every device needs its own
    executable (a jitted function would re-specialize by itself)."""
    leaves = jax.tree_util.tree_flatten(args)[0]
    return tuple((tuple(getattr(x, "shape", ())),
                  str(getattr(x, "dtype", type(x).__name__)),
                  getattr(x, "sharding", None))
                 for x in leaves)


def stage_executable(key: tuple, builder: Callable[[], Callable],
                     args: tuple, metrics=None, name: str = "stage",
                     donate_argnums: tuple = ()):
    """AOT-compiled whole-stage program for (key, signature-of-args).

    On a cache miss the program is traced, lowered and compiled EXPLICITLY
    (jax AOT API) so the build is observable: numStageCompiles /
    stageCompileTime on `metrics` and a `compile` journal event with the
    trace-vs-compile time split.  A compile error surfaces with the
    stage's name.  Returns a callable taking *args.

    `donate_argnums` lowers the program with input/output buffer aliasing
    on those argument positions (mem/donation.py owns the safety proof —
    a donated executable ALWAYS deletes those inputs, so donated and
    non-donated dispatches must resolve to distinct cache entries: the
    argnums are part of the key)."""
    if donate_argnums:
        key = key + ("donate", tuple(donate_argnums))
    k = (key, input_signature(args))
    with _CACHE_LOCK:
        fn = _STAGE_EXECUTABLES.get(k)
        if fn is not None:
            _STAGE_EXECUTABLES.move_to_end(k)
            _COUNTERS["stage_hits"] += 1
            return fn
    from ..metrics import names as MN
    from ..metrics.journal import journal_event
    timer = (metrics.timer(MN.STAGE_COMPILE_TIME) if metrics is not None
             else None)
    jfn = named_jit(builder, name, donate_argnums=donate_argnums)
    t0 = time.perf_counter()
    if timer is not None:
        timer.__enter__()
    try:
        traced = jfn.trace(*args)
        t_traced = time.perf_counter()
        lowered = traced.lower()
        t_lowered = time.perf_counter()
        fn = lowered.compile()
        t_compiled = time.perf_counter()
    except Exception as e:
        # a program the compiler refuses surfaces with the stage's name
        # on it (type kept: the OOM ladder keys on MemoryError) — never a
        # quiet retreat to a lazily-jitted function that would hit the
        # same refusal at first dispatch, unnamed
        e.add_note(f"while building whole-stage program {name!r} for the "
                   f"{jax.default_backend()} backend")
        raise
    finally:
        if timer is not None:
            timer.__exit__(None, None, None)
    with _CACHE_LOCK:
        _COUNTERS["stage_compiles"] += 1
    if metrics is not None:
        metrics.add(MN.NUM_STAGE_COMPILES, 1)
    journal_event("compile", name,
                  trace_s=round(t_lowered - t0, 6),
                  compile_s=round(t_compiled - t_lowered, 6),
                  trace_only_s=round(t_traced - t0, 6),
                  signature_leaves=len(k[1]))
    with _CACHE_LOCK:
        _STAGE_EXECUTABLES[k] = fn
        while len(_STAGE_EXECUTABLES) > _STAGE_EXECUTABLES_MAX:
            _STAGE_EXECUTABLES.popitem(last=False)
    return fn


def clear_stage_executables() -> None:
    with _CACHE_LOCK:
        _STAGE_EXECUTABLES.clear()


# --- plan-cache parameter keying --------------------------------------------
# Default: a Parameter keys like the Literal it replaced (value INCLUDED),
# so any dispatch site that does not thread parameter values as runtime
# arguments recompiles per value — always correct, merely slower.  The
# threaded sites (RowLocalExec.execute, TpuWholeStageExec, the aggregate
# whole-stage absorption, the exchange bucketing fusion) compute their keys
# under `param_free_keys()` so literal-variant queries share ONE compiled
# program and re-bind values per dispatch.

_KEY_MODE = threading.local()


@contextlib.contextmanager
def param_free_keys():
    """Within this scope, expr_key() omits Parameter VALUES (slot + dtype
    only).  Use ONLY around key computation for a dispatch site that
    passes the parameter values as traced runtime arguments."""
    prev = getattr(_KEY_MODE, "free", False)
    _KEY_MODE.free = True
    try:
        yield
    finally:
        _KEY_MODE.free = prev


def expr_key(e) -> tuple:
    """Structural signature of an expression tree: class + every non-child
    constructor attribute + children, recursively.  Safer than repr (an
    expression whose repr omits a parameter would under-key the cache)."""
    from ..ops.expressions import Expression, Parameter
    if isinstance(e, Parameter):
        key = ("Parameter", e.slot, e._dtype.name)
        if not getattr(_KEY_MODE, "free", False):
            key += (repr(e.value),)
        return key
    attrs = []
    d = getattr(e, "__dict__", None)
    items = sorted(d.items()) if d else \
        [(s, getattr(e, s)) for s in getattr(e, "__slots__", ())]
    for k, v in items:
        if k == "children" or isinstance(v, Expression):
            continue
        if isinstance(v, (list, tuple)) and any(
                isinstance(x, Expression) for x in v):
            continue
        attrs.append((k, _val_key(v)))
    kids = tuple(expr_key(c) for c in e.children)
    return (type(e).__name__, tuple(attrs), kids)


def _val_key(v):
    from ..types import DataType
    if isinstance(v, DataType):
        return v.name
    if isinstance(v, (list, tuple)):
        return tuple(_val_key(x) for x in v)
    if isinstance(v, (set, frozenset)):
        return tuple(sorted(map(repr, v)))
    if isinstance(v, dict):
        return tuple(sorted((k, _val_key(x)) for k, x in v.items()))
    return repr(v)


def schema_key(schema) -> tuple:
    return tuple((f.name, f.dtype.name) for f in schema)


def cached_kernel(key: tuple, builder: Callable[[], Callable],
                  **jit_kw) -> Callable:
    """Return the jitted kernel for `key`, building it on first use.
    Concurrent misses on the same key may both build; last registration
    wins — a benign duplicate trace, never a wrong program (the key fully
    determines the closure).  jit keywords (donate_argnums etc.) must be
    reflected in the key by the caller: a donated kernel always deletes
    its donated inputs, so it can never share an entry with the
    non-donated variant."""
    role_key = key
    if jit_kw.get("donate_argnums"):
        key = key + ("donate", tuple(jit_kw["donate_argnums"]))
    fn = _CACHE.get(key)
    if fn is None:
        fn = named_jit(builder, program_role(role_key), **jit_kw)
        with _CACHE_LOCK:
            if key in _CACHE:
                return _CACHE[key]
            _CACHE[key] = fn
            _COUNTERS["builds"] += 1
    else:
        with _CACHE_LOCK:
            _COUNTERS["kernel_hits"] += 1
    return fn


def cache_info() -> Tuple[int, list]:
    return len(_CACHE), [k[0] for k in _CACHE]


def clear():
    with _CACHE_LOCK:
        _CACHE.clear()
        _STAGE_EXECUTABLES.clear()
