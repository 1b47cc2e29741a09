"""Order statistics of a run's readings, unrounded."""

from __future__ import annotations

from typing import Dict, Sequence


def quantile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no readings")
    pos = (len(xs) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return quantile(values, 0.5)


def summary(values: Sequence[float]) -> Dict[str, float]:
    return {"n": len(values), "p25": quantile(values, 0.25),
            "p50": quantile(values, 0.5), "p75": quantile(values, 0.75),
            "min": min(values), "max": max(values)}
