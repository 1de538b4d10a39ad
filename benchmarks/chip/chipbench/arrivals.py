"""Arrival schedules of the chip benchmark's open-loop traffic.

The law is the one of the program's ``serving.queueing.OpenLoopGenerator``
without bursts (rebuilt here so that the yardstick stays fixed): a
Poisson stream at a base rate.

The sampling differs on purpose.  A Poisson draw gives each seed a
different number of frames and a different set of gaps, so runs of
different seeds would do different work.  Here every seed gets the same
frames: ``n = round(rate * seconds)`` gaps taken at the midpoints of
``n`` equal-probability strata of the exponential law (the sum rescaled
to ``seconds``), in an order drawn from the seed.
"""

from __future__ import annotations

import numpy as np


def schedule(rate: float, seconds: float, seed) -> np.ndarray:
    """Due times in ``[0, seconds)``, ascending, the same count and the
    same gaps for every seed; ``seed`` is anything
    ``numpy.random.default_rng`` takes."""
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s over {seconds} s offers no frame")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(seed).permutation(gaps)
    due = np.cumsum(gaps) - 0.5 * gaps[0]
    return np.minimum(due, np.nextafter(seconds, 0.0))
