"""The percentile against numpy."""

import math

import numpy as np
import pytest

from bench import stats


@pytest.mark.parametrize("q", [0.0, 5.0, 50.0, 95.0, 99.0, 100.0])
@pytest.mark.parametrize("n", [1, 2, 7, 40, 401])
def test_percentile_matches_numpy(q, n):
    values = np.random.default_rng(n).exponential(size=n)
    assert stats.percentile(values, q) == pytest.approx(
        np.percentile(values, q), rel=1e-12)


def test_percentile_of_infinitely_late_queries():
    values = [1.0] * 95 + [math.inf] * 5
    assert stats.percentile(values, 50.0) == 1.0
    assert stats.percentile(values, 99.0) == math.inf
    assert math.isnan(stats.percentile([], 50.0))

