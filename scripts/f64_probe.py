"""How far do doubles move on this device?  One process, prints one JSON
object per line.  Run it on the chip (`python scripts/f64_probe.py`); on
the CPU backend every line reads exact, which is the control.

XLA:TPU has no native f64 and refuses f64->int bitcasts, so the engine's
TPU branches (exec/sort.py, ops/hashing.py, columnar/contiguous.py) assume
a double is carried as a (hi, lo) float32 pair.  This script measures what
that costs: round trip through the host link, range, one rounding step of
+ and *, a 6M-addend sum, and whether the (hi, lo) split reconstructs.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def emit(**kw):
    print(json.dumps(kw), flush=True)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    with np.errstate(all="ignore"):
        r = np.abs(a - b) / np.maximum(np.abs(b), 1e-300)
    r = np.where(np.isnan(a) & np.isnan(b), 0.0, r)
    r = np.where(a == b, 0.0, r)
    return float(np.nanmax(np.where(np.isnan(r), np.inf, r)))


def main() -> int:
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    d = jax.devices()[0]
    emit(device={"platform": d.platform, "kind": d.device_kind,
                 "count": len(jax.devices())})
    rng = np.random.RandomState(7)

    # 1. host -> device -> host, nothing computed
    x = rng.uniform(900.0, 105000.0, 1 << 20)
    back = np.asarray(jax.device_put(x))
    emit(test="round_trip_uniform", max_rel=rel(back, x),
         exact_fraction=float(np.mean(back == x)))
    special = np.array([0.0, -0.0, 1.0, 1.0 + 2.0 ** -52, 1.0 + 2.0 ** -30,
                        1e38, 1e39, 1e300, 1e-38, 1e-45, 1e-300, 5e-324,
                        np.inf, -np.inf, np.nan, 2.0 ** 53 + 2.0,
                        0.1, 1.0 / 3.0])
    sback = np.asarray(jax.device_put(special))
    emit(test="round_trip_special",
         sent=[repr(float(v)) for v in special],
         got=[repr(float(v)) for v in sback],
         neg_zero_kept=bool(np.signbit(sback[1])))

    # 2. mantissa width seen by one add: (1 + 2^-k) - 1, k runtime data
    k = np.arange(1, 64, dtype=np.float64)
    eps = 2.0 ** -k
    got = np.asarray(jax.jit(lambda o, e: (o + e) - o)(np.ones_like(eps), eps))
    kept = [int(kk) for kk, g, e in zip(k, got, eps) if g == e]
    emit(test="add_mantissa_bits", largest_k_exact=max(kept) if kept else 0,
         cpu_control=52)

    # 3. one multiply and one add against IEEE
    a = rng.uniform(900.0, 105000.0, 1 << 20)
    b = rng.choice(np.arange(0.0, 0.11, 0.01), 1 << 20)
    prod = np.asarray(jax.jit(lambda u, v: u * (1.0 - v))(a, b))
    emit(test="mul_sub", max_rel=rel(prod, a * (1.0 - b)),
         exact_fraction=float(np.mean(prod == a * (1.0 - b))))

    # 4. the smoke's shape: sum of 6M products, and a segmented sum
    n = 6_000_000
    p = rng.uniform(900.0, 105000.0, n)
    q = rng.choice(np.arange(0.0, 0.11, 0.01), n)
    t0 = time.perf_counter()
    s = float(jax.jit(lambda u, v: jnp.sum(u * v))(p, q))
    import math
    ref = math.fsum((p * q).tolist())
    emit(test="sum_6m_products", device=repr(s), exact=repr(ref),
         rel=abs(s - ref) / abs(ref),
         numpy_pairwise_rel=abs(float(np.sum(p * q)) - ref) / abs(ref),
         seconds=round(time.perf_counter() - t0, 2))
    seg = rng.randint(0, 6, n).astype(np.int32)
    ss = np.asarray(jax.jit(
        lambda u, g: jax.ops.segment_sum(u, g, num_segments=6))(p, seg))
    sref = np.array([math.fsum(p[seg == i].tolist()) for i in range(6)])
    emit(test="segment_sum_6m", max_rel=rel(ss, sref))

    # 5. the (hi, lo) float32 split the engine's TPU branches rely on
    def split(v):
        hi = v.astype(jnp.float32)
        lo = (v - hi.astype(jnp.float64)).astype(jnp.float32)
        return hi, lo, hi.astype(jnp.float64) + lo.astype(jnp.float64)
    hi, lo, re = (np.asarray(t) for t in jax.jit(split)(x))
    emit(test="hi_lo_split", reconstructs_on_device_fraction=float(
        np.mean(re == back)), max_rel_vs_sent=rel(re, x),
        host_pair_rel=rel(hi.astype(np.float64) + lo.astype(np.float64), x))

    # 6. compare: does the device order doubles that differ in the last
    # IEEE bit, and at 2^-40?
    base = rng.uniform(1.0, 2.0, 1 << 16)
    for name, bump in (("1ulp", 2.0 ** -52), ("2^-45", 2.0 ** -45),
                       ("2^-40", 2.0 ** -40)):
        lt = np.asarray(jax.jit(lambda u, v: u < v)(base, base + bump))
        emit(test="compare_" + name, ordered_fraction=float(np.mean(lt)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
