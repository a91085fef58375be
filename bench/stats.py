"""Order statistics the benchmark reports (copied from the program so that a
later change to the program cannot change the yardstick)."""

import math


def percentile(values, q: float) -> float:
    """Linear-interpolation percentile (numpy's default convention): with
    sorted values v_0..v_{m-1}, p_q is v at rank (m-1)*q/100, interpolated
    between the two bracketing ranks. Infinite values sort last."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return float("nan")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q must be in [0, 100], got {q}")
    pos = (len(vals) - 1) * (q / 100.0)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(vals) - 1)
    frac = pos - lo
    if frac == 0.0:
        return vals[lo]
    return vals[lo] * (1.0 - frac) + vals[hi] * frac

