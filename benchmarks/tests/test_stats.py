"""Percentile and window arithmetic on made-up samples."""
import numpy as np
import pytest

from benchmarks.harness import serve, stats, traffic


def test_percentile_matches_numpy():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 25, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_whole_units_lie_inside_the_window():
    b = [(0.0, 8), (4.0, 16), (8.0, 24), (12.0, 32), (16.5, 40)]
    # opens at the boundary at 4.0, deadline 16.0: units ending 8 and 12
    assert stats.whole_units(b, 4.0, 16.0) == (8.0, 16, 2)
    assert stats.whole_units(b, 4.0, 7.0) is None


def test_quartile_spread_is_the_contracts():
    values = [14660, 15388, 15392, 15393, 15395, 15395]
    assert stats.quartile_spread(values) == pytest.approx(
        (15395 - 15206) / 15392.5, rel=1e-3)


def _served(first, last, n):
    req = traffic.ServeRequest(0, [1, 2], n)
    return serve._Served(req, submit_t=first - 0.1, prefill_done_t=first,
                         done_t=last, tokens=list(range(n)),
                         finish_reason="length")


def test_tokens_are_apportioned_to_the_window_by_time():
    # 11 tokens, the first at 10 s, the last at 20 s: one a second
    assert serve.tokens_inside(_served(10, 20, 11), 0, 30) == 11
    assert serve.tokens_inside(_served(10, 20, 11), 15, 30) == 5
    assert serve.tokens_inside(_served(10, 20, 11), 10, 15) == 6
    assert serve.tokens_inside(_served(10, 20, 11), 21, 30) == 0
    assert serve.tokens_inside(_served(10, 10, 1), 5, 30) == 1
