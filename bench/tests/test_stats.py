import numpy as np
import pytest

from bench.harness.stats import percentile


def test_empty_sample_raises():
    with pytest.raises(ValueError):
        percentile([], 90)


@pytest.mark.parametrize("q", [1, 50, 90, 99, 100])
def test_matches_linear_interpolation(q):
    xs = list(np.random.default_rng(q).exponential(size=137))
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_single_value():
    assert percentile([3.5], 99) == 3.5
