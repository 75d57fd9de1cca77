"""The arithmetic of the end-to-end readings: kept here so that no later PR
can change what a metric means."""

from __future__ import annotations

import math
import statistics
from typing import List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks; the values need not be sorted."""
    if not values:
        raise ValueError("percentile of nothing")
    v = sorted(values)
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def slice_rates(
    marks: Sequence[Tuple[float, float]], t_open: float, t_close: float, slices: int
) -> List[float]:
    """Work a second in each of ``slices`` equal stretches of a window.

    ``marks`` are ``(time, cumulative work)`` readings taken at step ends,
    rising in time.  Each slice boundary is moved to the first mark at or
    after it, so a slice holds whole steps and no edge rounds; its rate is
    the work between its two marks over the time between them.
    """
    if slices < 1 or t_close <= t_open:
        raise ValueError("need a window and at least one slice")
    inside = [m for m in marks if t_open <= m[0] <= t_close]
    edges = []
    j = 0
    for k in range(slices + 1):
        want = t_open + (t_close - t_open) * k / slices
        while j < len(inside) - 1 and inside[j][0] < want:
            j += 1
        if not inside:
            break
        edges.append(inside[j])
    rates = []
    for a, b in zip(edges, edges[1:]):
        if b[0] > a[0]:
            rates.append((b[1] - a[1]) / (b[0] - a[0]))
    return rates


def spread(values: Sequence[float]) -> float:
    """The distance between the first and third quartile as a share of the
    median: the contract's measure of how widely runs differ."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
