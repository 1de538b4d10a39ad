"""The arrival schedule: the same frames for every seed, in an order
drawn from the seed, at the stated mean rate."""

from __future__ import annotations

import numpy as np
import pytest
from chipbench_tiny import BASE  # noqa: F401  (puts chipbench on the path)

from chipbench import arrivals


@pytest.mark.parametrize("rate,seconds", [(400.0, 10.0), (37.5, 20.0)])
def test_repeats_per_seed_and_keeps_mean_rate(rate, seconds):
    a = arrivals.schedule(rate, seconds, 123)
    b = arrivals.schedule(rate, seconds, 123)
    c = arrivals.schedule(rate, seconds, 2 ** 40 + 5)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert len(a) == len(c) == round(rate * seconds)
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < seconds
    assert a[-1] == pytest.approx(c[-1], abs=0.05 * seconds)


def test_gaps_are_exponential():
    a = arrivals.schedule(1000.0, 20.0, 7)
    gaps = np.diff(a)
    assert np.mean(gaps) == pytest.approx(1e-3, rel=0.01)
    assert np.std(gaps) == pytest.approx(1e-3, rel=0.05)   # cv of 1
