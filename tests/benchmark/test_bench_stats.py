"""Percentiles from raw samples."""

import numpy as np
import pytest

import bench_tiny  # noqa: F401
from benchmark.harness import stats


@pytest.mark.parametrize("p", [0, 5, 50, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 10, 1001])
def test_percentile_matches_order_statistics(p, n):
    xs = list(np.random.default_rng(n).exponential(2.6, n) + 9.0)
    assert stats.percentile(xs, p) == pytest.approx(
        float(np.percentile(xs, p)), rel=1e-12)


def test_percentile_resolves_what_a_9pct_bucket_cannot():
    """Two tails 2 % apart: the raw samples tell them apart; a histogram
    of 8 buckets an octave (9.05 % wide) puts both in one bucket."""
    a = np.linspace(10.0, 17.0, 8000)
    b = a * 1.02
    assert stats.percentile(b, 95) / stats.percentile(a, 95) == \
        pytest.approx(1.02, rel=1e-9)
    bucket = lambda x: int(np.floor(np.log2(x) * 8))   # noqa: E731
    assert bucket(stats.percentile(a, 95)) == bucket(stats.percentile(b, 95))


def test_percentile_rejects_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)
