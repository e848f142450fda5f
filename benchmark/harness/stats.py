"""The arithmetic of the yardstick: percentiles that state their sample
count, the spread the bounds are set from, and exact-interval counting."""

from __future__ import annotations

import bisect
import math
import statistics
from typing import NamedTuple, Sequence


class Pct(NamedTuple):
    value: float
    n: int


def percentile(values: Sequence[float], q: float) -> Pct:
    """The q-th percentile (0..100, linear interpolation between order
    statistics) together with the sample count it stands on."""
    xs = sorted(values)
    n = len(xs)
    if n == 0:
        return Pct(math.nan, 0)
    pos = (n - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, n - 1)
    return Pct(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo), n)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median — the contract's definition (`statistics.quantiles(n=4)`)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


class Window(NamedTuple):
    """An exact measuring interval: it opens at the first boundary at or
    after `earliest` and closes at the first boundary at or after
    `opened + seconds`; both are indices into the boundary list."""
    i_open: int
    i_close: int
    t_open: float
    t_close: float

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def exact_window(boundaries: Sequence[float], earliest: float,
                 seconds: float) -> Window | None:
    """`boundaries`: ascending times at which a unit of work (an engine
    step, a training step) had wholly completed. Returns None while the
    log does not yet reach a closing boundary."""
    i = bisect.bisect_left(boundaries, earliest)
    if i >= len(boundaries):
        return None
    j = bisect.bisect_left(boundaries, boundaries[i] + seconds)
    if j >= len(boundaries):
        return None
    return Window(i, j, boundaries[i], boundaries[j])


def count_in_window(work_per_boundary: Sequence[float], w: Window) -> float:
    """Work completed strictly after the opening boundary up to and
    including the closing one: `work_per_boundary[k]` is what finished
    between boundary k-1 and boundary k."""
    return sum(work_per_boundary[w.i_open + 1:w.i_close + 1])


def rate(work_per_boundary: Sequence[float], w: Window) -> float:
    """Work per second over the measured interval, not the nominal one."""
    return count_in_window(work_per_boundary, w) / w.seconds
