"""Order statistics the benchmark reports, kept here so that every PR
computes them the same way."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest value with at least ``p``% of
    the values at or below it.  ``None`` for no values."""
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return float(xs[rank - 1])

