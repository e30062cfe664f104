"""Percentiles the way the end-to-end metrics state them."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by the nearest-rank rule: the
    smallest value with at least ``q`` percent of the samples at or
    below it.  ``None`` for no samples.  A request that failed counts as
    missing every limit: pass ``math.inf`` for it."""
    if not values:
        return None
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]
