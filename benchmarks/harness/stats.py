"""Order statistics, the same on every run: linear interpolation between
the closest ranks (numpy's default), written out so the load generator's
records need no numpy."""

from __future__ import annotations


def quantile(values, q: float) -> float | None:
    """q in [0, 100]. None for an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
