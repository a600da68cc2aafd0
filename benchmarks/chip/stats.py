"""Order statistics the metrics use.

Every percentile here pools all samples of a run (no median of chunks)
and interpolates linearly between order statistics, as numpy's default
does.  A missing sample (a request that failed) is +inf: a percentile
whose rank touches it is +inf too.
"""
from __future__ import annotations

import math
import statistics
from typing import Iterable, List


def percentile(xs: Iterable[float], q: float) -> float:
    """The q-th percentile (0..100) of ``xs``; nan for no samples."""
    ys: List[float] = sorted(xs)
    if not ys:
        return math.nan
    pos = (len(ys) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(ys) - 1)
    frac = pos - lo
    if frac == 0.0 or ys[lo] == ys[hi]:
        return ys[lo]
    if math.isinf(ys[hi]):
        return math.inf
    return ys[lo] + (ys[hi] - ys[lo]) * frac


def spread(values: List[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
