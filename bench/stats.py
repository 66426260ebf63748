"""Order statistics the benchmark reports."""
from __future__ import annotations

import math
from typing import List


def percentile(xs: List[float], p: float) -> float:
    """``p``-th percentile over every sample (linear between order
    statistics), not a median of groups of samples."""
    ys = sorted(xs)
    if not ys:
        return math.nan
    pos = (len(ys) - 1) * p / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(ys) - 1)
    return ys[lo] + (ys[hi] - ys[lo]) * (pos - lo)
