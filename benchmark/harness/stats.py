"""The benchmark's own arithmetic: medians, quartiles, per-segment rates.

Kept here, not read from the program, so that no PR can move the
yardstick. All functions take plain Python numbers.
"""
from __future__ import annotations

import statistics
from typing import List, Sequence


def median(values: Sequence[float]) -> float:
    if not values:
        raise ValueError("median of no values")
    return float(statistics.median(values))


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with Python's ``statistics.quantiles(values, n=4)``: the
    spread the contract's bounds are worked out from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return float((q3 - q1) / statistics.median(values))


def segment_rates(stamps: Sequence[float], work: Sequence[float]) -> List[float]:
    """Per-segment rates from ``len(work) + 1`` completion instants:
    segment ``i`` did ``work[i]`` between ``stamps[i]`` and
    ``stamps[i + 1]``. Never work over a nominal length."""
    if len(stamps) != len(work) + 1:
        raise ValueError("need one more instant than segments")
    return [w / (b - a) for w, a, b in zip(work, stamps[:-1], stamps[1:])]
