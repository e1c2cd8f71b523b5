"""Exact order statistics over all samples (no buckets)."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by the nearest-rank rule: the
    smallest value with at least ``q`` percent of the values at or below
    it.  A missing value (``inf``) ranks above every finite one."""
    if not values:
        raise ValueError("percentile of no values")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100], got {q}")
    ordered = sorted(values)
    return ordered[max(1, math.ceil(q / 100 * len(ordered))) - 1]
