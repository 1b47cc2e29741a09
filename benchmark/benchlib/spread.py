"""How widely a cell's runs spread, reckoned as the driver's check reckons it,
so that a bound can be set from the same arithmetic it will be held to.

A spread is the distance between the first and the third quartile as
`statistics.quantiles(values, n=4)` gives them (numpy's lie closer together),
as a share of the median. The check reads two sets of runs of one tree. A
bound is too tight where the mean of the two sets' spreads, each set without
its run farthest from the median, is over half of it; it is too loose where
it is over eight times the wider spread of the sets' runs, all kept."""

from __future__ import annotations

import statistics
from typing import Dict, List, Sequence

TIGHT_SHARE = 0.5   # a cell may spread by this share of the bound
LOOSE_TIMES = 8.0   # and the bound may be this many times the widest spread
MIN_BOUND = 0.01    # a bound of 1% is never too loose


def spread(values: Sequence[float]) -> float:
    """(third quartile - first quartile) / median."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values: Sequence[float]) -> List[float]:
    """The runs but the one farthest from their median."""
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    return [v for i, v in enumerate(values) if i != far]


def range_share(values: Sequence[float]) -> float:
    """(max - min) / median: the distance the ledger's notes give in seconds."""
    return (max(values) - min(values)) / statistics.median(values)


def of_sets(sets: Sequence[Sequence[float]]) -> Dict[str, float]:
    """The readings of the check over some sets of runs of one tree."""
    every = [v for s in sets for v in s]
    return {
        "median": statistics.median(every),
        "set_medians": [statistics.median(s) for s in sets],
        # what "too tight" reads: the farthest run of each set left out
        "tight": statistics.mean(spread(without_farthest(s)) for s in sets),
        # what "too loose" reads: every run kept, the wider set
        "loose": max(spread(s) for s in sets),
        # the issue's measure: each set's range without its farthest run
        "trimmed_range": statistics.mean(
            range_share(without_farthest(s)) for s in sets),
        "range": max(range_share(s) for s in sets),
    }


def bound_window(cells: Dict[str, Dict[str, float]]) -> Dict[str, float]:
    """The lowest and the highest bound the check would take, from every
    cell's ``of_sets``: no cell's ``tight`` over half of it, and not over
    eight times the widest ``loose``."""
    low = max(c["tight"] for c in cells.values()) / TIGHT_SHARE
    high = max(LOOSE_TIMES * max(c["loose"] for c in cells.values()), MIN_BOUND)
    return {"lowest": max(low, MIN_BOUND), "highest": high}
