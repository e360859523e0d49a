"""The arithmetic behind every reported statistic, kept in one place so a
later PR cannot change what a metric means.

Nothing here touches JAX: the functions take plain lists of numbers.
"""

import math
import statistics


def percentile(values, q):
    """Linear-interpolated percentile (numpy's default method), ``q`` in
    [0, 100]. None for an empty list."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    rank = (len(xs) - 1) * (q / 100.0)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def tail_mean(values, share=0.05):
    """Mean of the ``ceil(share * n)`` largest values: how long a stutter is
    when there is one. Smooth in the share of slow samples, where a
    percentile of a distribution with a few narrow modes has cliffs. With
    fewer than ``1 / share`` samples it is the maximum. None when empty."""
    if not values:
        return None
    k = max(1, math.ceil(share * len(values)))
    top = sorted(values)[-k:]
    return float(sum(top) / len(top))


def quartile_spread(values):
    """Distance between the first and the third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` (the rule the bounds
    are set by). None with fewer than two values or a zero median."""
    if len(values) < 2:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return None if med == 0 else float((q3 - q1) / abs(med))


def token_gaps(token_times, window_start, window_end):
    """Pooled gaps between consecutive output tokens of one request.

    ``token_times`` is one list of monotonic timestamps per request, in
    emission order. A request's first token is not a gap; a gap counts when
    its LATER token falls inside [window_start, window_end)."""
    gaps = []
    for times in token_times:
        for prev, cur in zip(times, times[1:]):
            if window_start <= cur < window_end:
                gaps.append(cur - prev)
    return gaps
