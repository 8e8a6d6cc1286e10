"""Nearest-rank percentiles and quartile spreads against hand values."""

import chipbench_support  # noqa: F401
import pytest

from benchmarks.chip.stats import nearest_rank, spread


@pytest.mark.parametrize("q,want", [(50, 5), (95, 10), (90, 9), (10, 1), (100, 10), (1, 1)])
def test_nearest_rank_of_one_to_ten(q, want):
    assert nearest_rank([7, 3, 10, 1, 5, 9, 2, 8, 4, 6], q) == want


def test_nearest_rank_small_and_bad_input():
    assert nearest_rank([42.0], 95) == 42.0
    assert nearest_rank([1, 2, 3, 4], 50) == 2
    assert nearest_rank([1, 2, 3, 4], 51) == 3
    with pytest.raises(ValueError):
        nearest_rank([], 50)
    with pytest.raises(ValueError):
        nearest_rank([1.0], 0)


def test_spread_is_quartile_distance_over_median():
    # statistics.quantiles (exclusive) of 1..7: q1 = 2, median 4, q3 = 6
    assert spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(1.0)
    assert spread([10.0] * 6) == 0.0
