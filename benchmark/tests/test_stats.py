"""The harness's own arithmetic on hand-made readings."""

import pytest

from benchmark.lib import stats


def test_prompt_tokens_are_split_by_time_at_the_window_edges():
    prefills = [(100, 1.0, 2.0),     # wholly inside
                (100, -1.0, 1.0),    # half before the window
                (300, 9.0, 12.0),    # a third inside, at the end
                (50, -3.0, -1.0),    # wholly before
                (80, 10.0, 11.0)]    # wholly after
    assert stats.prompt_tokens_between(prefills, 0.0, 10.0) == \
        pytest.approx(100 + 50 + 100)
    assert stats.prompt_tokens_between([], 0.0, 10.0) == 0.0


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(2 / 3)
    assert stats.spread([7.0]) is None and stats.pct([], 50) is None
