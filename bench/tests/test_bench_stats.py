"""Percentile and window arithmetic, with sample counts."""
import numpy as np
import pytest

from bench.stats import (beyond, in_window, percentile, time_weighted_mean,
                         tpot)


def test_percentile_matches_numpy_linear():
    v = list(range(1, 201))
    assert percentile(v, 50) == pytest.approx(100.5)
    assert percentile(v, 95) == pytest.approx(np.percentile(v, 95))
    # 200 samples: ten lie beyond the 95th percentile
    assert beyond(v, 95) == 10
    with pytest.raises(ValueError):
        percentile([], 50)


def test_window_and_tpot():
    assert in_window(1.0, 1.0, 2.0) and not in_window(2.0, 1.0, 2.0)
    assert tpot(1.0, 3.0, 5) == pytest.approx(0.5)
    with pytest.raises(ValueError):
        tpot(1.0, 1.0, 1)


def test_weighted_mean():
    assert time_weighted_mean([32, 16], [3.0, 1.0]) == pytest.approx(28.0)
    with pytest.raises(ValueError):
        time_weighted_mean([1.0], [0.0])
