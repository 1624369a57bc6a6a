"""Percentiles and the window's latency rules."""
import pytest

from tiny_cell import ROOT  # noqa: F401  (puts the repo on sys.path)
from bench import stats


def test_nearest_rank_percentile():
    vals = [5, 1, 4, 2, 3, 10, 9, 8, 7, 6]
    assert stats.percentile(vals, 50) == 5
    assert stats.percentile(vals, 90) == 9
    assert stats.percentile(vals, 95) == 10
    assert stats.percentile(vals, 0) == 1
    assert stats.percentile([], 90) is None


def test_censored_ttft():
    t_open, t_close = 10.0, 20.0
    # first token inside the window: due to first token
    assert stats.censored_ttft(12.0, 12.5, False, t_open, t_close) == 0.5
    # no first token when the window closed: the wait so far
    assert stats.censored_ttft(12.0, None, False, t_open, t_close) == 8.0
    assert stats.censored_ttft(12.0, 21.0, False, t_open, t_close) == 8.0
    # failed or refused: the window's length
    assert stats.censored_ttft(19.0, None, True, t_open, t_close) == 10.0


def test_token_gaps_inside_window_only():
    stamps = [9.5, 10.2, 10.5, 11.0, 20.5]
    assert stats.token_gaps(stamps, 10.0, 20.0) == pytest.approx([0.3, 0.5])
