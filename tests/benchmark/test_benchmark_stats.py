"""The arithmetic of the reported statistics, on hand-made lists."""

import math

import pytest

from benchmarks.harness import stats


def test_percentile_interpolates_like_numpy():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 50) is None


@pytest.mark.parametrize("n,expect_k", [(1, 1), (5, 1), (19, 1), (20, 1),
                                        (21, 2), (100, 5), (101, 6)])
def test_tail_mean_takes_ceil_of_five_percent(n, expect_k):
    xs = [float(i) for i in range(1, n + 1)]
    top = xs[-expect_k:]
    assert stats.tail_mean(xs, 0.05) == pytest.approx(sum(top) / len(top))


def test_tail_mean_with_fewer_than_twenty_samples_is_the_maximum():
    assert stats.tail_mean([3.0, 9.0, 4.0], 0.05) == 9.0
    assert stats.tail_mean([], 0.05) is None


def _bimodal(n, stalled_share):
    """Token gaps as the chat cell shows them: a token follows the previous
    decode step directly (57 ms) or waits for one prefill (97 ms)."""
    k = round(n * stalled_share)
    return [57.0] * (n - k) + [97.0] * k


def test_tail_mean_moves_smoothly_where_the_p95_falls_off_a_cliff():
    """A later optimisation moves the stalled share of gaps through 5%. The
    95th percentile jumps between the modes; the mean of the slowest 5%
    must change by less."""
    before = _bimodal(2000, 0.055)
    after = _bimodal(2000, 0.045)
    p95_move = abs(stats.percentile(before, 95) - stats.percentile(after, 95))
    tail_move = abs(stats.tail_mean(before) - stats.tail_mean(after))
    assert p95_move == pytest.approx(40.0)       # the whole gap between modes
    assert tail_move < 0.15 * p95_move
    # and it stays a tail: all of the slowest 5% are stalled gaps before
    assert stats.tail_mean(before) == pytest.approx(97.0)
    assert 57.0 < stats.tail_mean(after) < 97.0


def test_quartile_spread_is_the_contracts_rule():
    xs = [100.0, 101.0, 99.0, 100.5, 99.5, 100.0]
    import statistics
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx((q3 - q1) / 100.0)
    assert stats.quartile_spread([1.0]) is None


def test_token_gaps_pool_requests_and_skip_first_tokens():
    times = [[1.0, 1.5, 2.5], [1.2, 3.0], [9.0]]
    gaps = stats.token_gaps(times, window_start=1.4, window_end=2.9)
    # 1.0->1.5 (later token inside), 1.5->2.5; 1.2->3.0 ends outside
    assert sorted(gaps) == pytest.approx([0.5, 1.0])
    assert len(stats.token_gaps(times, 0.0, 10.0)) == 3
    assert not math.isnan(sum(gaps))
