"""The comparison that decides `correct`: a query's rows against the plain
reference's.  Copied from `chip_smoke.py` (`rows_match`)."""
import math

# Doubles are compared at this relative tolerance; integers, strings, row
# count and row order exactly.  XLA:TPU carries a double as a pair of f32:
# a value moves by up to 1.8e-15 crossing the host link, one multiply by up
# to 1.3e-14, and the device folds 6M addends in another order than the
# reference.  The worst result drift at SF1 was 1.7e-13 (Q1's sums; chip
# run, PR 22); one missing or doubled row moves a sum of a million rows by
# about 1e-6.  1e-10 sits three orders above the drift and four below the
# smallest wrong answer.
DOUBLE_RTOL = 1e-10


def rows_match(got, want, rtol=DOUBLE_RTOL):
    """(ok, worst relative double error)."""
    if len(got) != len(want):
        return False, float("nan")
    worst = 0.0
    for g_row, w_row in zip(got, want):
        if len(g_row) != len(w_row):
            return False, worst
        for g, w in zip(g_row, w_row):
            if isinstance(w, float) and isinstance(g, float):
                if math.isnan(w) or math.isnan(g):
                    if not (math.isnan(w) and math.isnan(g)):
                        return False, float("nan")
                    continue
                worst = max(worst, abs(g - w) / max(abs(w), 1e-300))
            elif g != w or type(g) is not type(w):
                return False, worst
    return worst <= rtol, worst
