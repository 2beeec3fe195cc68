"""The few statistics the benchmark reports (copied in spirit from
``flexflow_tpu.profiling.quantiles``: nearest rank, no interpolation, so a
reported tail is a value that was measured)."""

from __future__ import annotations

import math
import statistics


def quantile(values, q):
    """Nearest-rank quantile of ``values`` (0 < q <= 1); None when empty."""
    vs = sorted(values)
    if not vs:
        return None
    return vs[min(len(vs) - 1, max(0, math.ceil(q * len(vs)) - 1))]


def median(values):
    return quantile(values, 0.5)


def union_ns(intervals):
    """Total length of the union of ``(start, end)`` intervals, and the gaps
    between its pieces as ``(start, end)``."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s > cur_e:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def spread_less_farthest(values):
    """The spread the driver's check holds a bound against: the range of the
    runs with the one farthest from their median left out, over the median
    of all of them."""
    vs = sorted(values)
    if len(vs) < 3:
        raise ValueError("a spread wants three runs or more")
    mid = statistics.median(vs)
    rest = vs[1:] if mid - vs[0] >= vs[-1] - mid else vs[:-1]
    return (rest[-1] - rest[0]) / mid


def spread_quartiles(values):
    """The spread a bound is set from: the distance between the first and
    the third quartile (``statistics.quantiles(values, n=4)``) over the
    median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
