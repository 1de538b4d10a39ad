"""Order statistics of the chip benchmark.

``quantile`` is the nearest-rank percentile (copied from the program's
``obs.metrics.quantile`` so that the yardstick cannot move with it): it
returns an observed sample, rank ``ceil(q/100 * n)``.  ``spread`` is the
width between the first and third quartile over the median, as
``statistics.quantiles(values, n=4)`` gives them: the measure a bound
is set from.
"""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def quantile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of ``values``, ``q`` in [0, 100]."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q must be in [0, 100], got {q}")
    n = len(values)
    if n == 0:
        raise ValueError("quantile of no values")
    s = sorted(values)
    if q == 0.0:
        return float(s[0])
    rank = math.ceil(q / 100.0 * n)
    return float(s[min(n, max(1, rank)) - 1])


def spread(values: Sequence[float]) -> float:
    """(Q3 - Q1) / median, quartiles as ``statistics.quantiles`` gives."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
