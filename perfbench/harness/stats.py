"""Percentile, spread and rate arithmetic of the benchmark's metrics."""
from __future__ import annotations

import math
import statistics
from typing import List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks of the sorted values (numpy's default rule). Missing
    samples are ``math.inf``: they lie above every served one."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == xs[lo]:
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def completion_rate(times: Sequence[float], burst_s: float = 0.25
                    ) -> Optional[float]:
    """Completions per second between the first and the last completion of
    a window. The window opens at the first completion; the completions
    within ``burst_s`` of it belong to the burst that opened it and are not
    counted, every later one is. So a batch that completes many plans at
    once is counted whole or not at all, and the rate is not quantized by
    batch boundaries. ``None`` with fewer than two bursts."""
    ts = sorted(times)
    if not ts:
        return None
    t_open, t_close = ts[0], ts[-1]
    counted = sum(1 for t in ts if t > t_open + burst_s)
    if counted == 0 or t_close <= t_open:
        return None
    return counted / (t_close - t_open)


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals as sorted disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(intervals: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of the part of [lo, hi) that merged ``intervals`` cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def gaps(intervals: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The uncovered stretches of [lo, hi) between merged intervals."""
    out, t = [], lo
    for s, e in intervals:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median, by
    ``statistics.quantiles(values, n=4)``: how the bounds are set."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
