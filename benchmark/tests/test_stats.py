import pytest

from lib import stats
from lib.ledger import stage_ms


def test_percentile_interpolates_between_order_statistics():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 0) == 10
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 100) == 50
    assert stats.percentile([7], 95) == 7
    # 100 samples 1..100: p95 sits between the 95th and 96th
    assert stats.percentile(range(1, 101), 95) == pytest.approx(95.05)


def test_percentile_of_nothing_is_an_error_not_zero():
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_rate_and_spread():
    assert stats.rate(9400, 20.0) == 470.0
    with pytest.raises(ValueError):
        stats.rate(1, 0)
    # statistics.quantiles(n=4) of 1..6: q1 = 1.75, q3 = 5.25, median 3.5
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)


ROWS = [
    dict(ready_lag_s=1.4, fetch_lag_s=1.25, collect_lag_s=1.41,
         publish_lag_s=5.2),
    dict(ready_lag_s=0.05, fetch_lag_s=0.04, collect_lag_s=0.06,
         publish_lag_s=0.26),
    dict(ready_lag_s=0.03, fetch_lag_s=0.02, collect_lag_s=0.03),  # empty
    dict(ready_lag_s=0.07, fetch_lag_s=0.05, collect_lag_s=0.08,
         publish_lag_s=0.18),
]


def test_ledger_stage_of_the_first_cohort():
    assert stage_ms(ROWS, ["fetch_lag_s"], [], "first") == pytest.approx(1250)
    assert stage_ms(ROWS, ["ready_lag_s"], ["fetch_lag_s"], "first") == (
        pytest.approx(150))
    assert stage_ms(ROWS, ["publish_lag_s"], ["collect_lag_s"], "first") == (
        pytest.approx(3790))


def test_ledger_median_leaves_out_rows_without_the_stamp():
    # publish - collect: 3790, 200, (no publish stamp), 100 -> median 200
    assert stage_ms(ROWS, ["publish_lag_s"], ["collect_lag_s"], "median") == (
        pytest.approx(200))
    assert stage_ms([], ["fetch_lag_s"], [], "median") is None
    assert stage_ms(ROWS[2:3], ["publish_lag_s"], [], "first") is None
