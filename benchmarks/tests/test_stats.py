"""Percentiles and what counts as inside the window, on hand-made stamps."""
import statistics

import numpy as np
import pytest

from benchmarks import stats

WINDOW = (10.0, 20.0)
REQUESTS = [
    # first token before the window: its TTFT is not the window's, its
    # later gaps are
    {"submit": 8.0, "token_times": [9.0, 10.5, 11.0]},
    # wholly inside
    {"submit": 12.0, "token_times": [12.5, 13.5, 13.75]},
    # first token inside, last token after the close
    {"submit": 18.0, "token_times": [19.0, 19.5, 20.5]},
    # submitted inside, nothing emitted yet
    {"submit": 19.9, "token_times": []},
]


def test_ttft_counts_requests_whose_first_token_fell_inside():
    assert stats.ttfts(REQUESTS, WINDOW) == [0.5, 1.0]


def test_gaps_count_by_their_later_token():
    assert stats.token_gaps(REQUESTS, WINDOW) == [1.5, 0.5, 1.0, 0.25, 0.5]


def test_tokens_inside():
    assert stats.tokens_inside(REQUESTS, WINDOW) == 2 + 3 + 2


@pytest.mark.parametrize("p", [0, 5, 50, 95, 100])
def test_percentile_is_numpys_linear(p):
    values = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    assert stats.percentile(values, p) == pytest.approx(
        float(np.percentile(values, p)))


def test_percentile_of_one_and_of_none():
    assert stats.percentile([2.0], 95) == 2.0
    assert stats.percentile([], 95) is None
    assert stats.median([1.0, 2.0, 4.0]) == statistics.median([1.0, 2.0, 4.0])
