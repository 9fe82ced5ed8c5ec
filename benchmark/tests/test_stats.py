"""Percentile and sample-count arithmetic, and the spread bounds are set from."""

import statistics

import pytest

from harness import stats
from harness.obs import Obs


@pytest.mark.parametrize(
    "values, q, want",
    [
        ([], 50, None),
        ([7.0], 95, 7.0),
        ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
        ([4.0, 1.0, 3.0, 2.0], 50, 2.5),  # order does not matter
        ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
        (list(map(float, range(1, 102))), 95, 96.0),
        ([0.0, 10.0], 25, 2.5),
        ([0.0, 10.0], 100, 10.0),
    ],
)
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == want


def test_median_agrees_with_statistics():
    values = [3.2, 9.1, 0.4, 7.7, 5.0, 5.1]
    assert stats.percentile(values, 50) == pytest.approx(statistics.median(values))


@pytest.mark.parametrize("n, q, want", [(400, 95, 20), (100, 95, 5), (9, 50, 4), (199, 95, 9)])
def test_samples_beyond(n, q, want):
    assert stats.samples_beyond(n, q) == want


def test_quartile_spread_is_the_contracts():
    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartile_spread(values) == pytest.approx((q3 - q1) / statistics.median(values))


def test_obs_records_only_inside_the_window():
    obs = Obs()
    obs.sample("x", 1.0)
    obs.count("c")
    with obs.span("s"):
        pass
    obs.open_window()
    obs.sample("x", 2.0)
    obs.count("c", 3)
    with obs.span("s"):
        pass
    t1 = obs.close_window()
    obs.sample("x", 3.0)
    obs.late_sample("x", 4.0)  # began inside, ended in the grace period
    assert obs.close_window() == t1  # a second close is void
    assert obs.series("x") == [2.0, 4.0]
    assert obs.counter("c") == 3
    assert len(obs.span_ms("s")) == 1
    assert obs.window_s > 0
