"""chipbench's percentile and sample-count arithmetic."""
import numpy as np
import pytest

from chipbench import stats


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy(q):
    xs = list(np.random.default_rng(0).lognormal(3, 1, 257))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


@pytest.mark.parametrize("n,q,beyond", [(200, 95, 10), (199, 95, 9),
                                        (1000, 99, 10), (20, 50, 10)])
def test_samples_beyond(n, q, beyond):
    assert stats.samples_beyond(n, q) == beyond


def test_tail_refuses_a_sample_too_small_to_carry_it():
    with pytest.raises(stats.TooFewSamples):
        stats.tail(list(range(199)), 95)
    assert stats.tail(list(range(200)), 95) == pytest.approx(189.05)
    with pytest.raises(stats.TooFewSamples):
        stats.percentile([], 95)


def test_spread_is_interquartile_over_median_with_exclusive_quartiles():
    xs = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    # statistics.quantiles (exclusive): q1 = 10.075, q3 = 10.425
    assert stats.spread(xs) == pytest.approx(0.35 / 10.25)
