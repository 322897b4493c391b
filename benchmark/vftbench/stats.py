"""Arithmetic on completions and samples. No JAX, no program import."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics, as ``numpy.percentile`` does; None on no samples."""
    if not values:
        return None
    vs = sorted(float(v) for v in values)
    pos = (len(vs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median: the driver's measure
    of how far runs of one cell disagree."""
    med = median(values)
    if not med:
        return None
    return (percentile(values, 75.0) - percentile(values, 25.0)) / abs(med)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie above the ``q``-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def units_in_window(completions: Iterable[Tuple[float, int]],
                    t0: float, t1: float) -> int:
    """Units of the requests whose response became visible in ``[t0, t1)``.
    A request counts whole, at the instant it completed: one in flight at
    either edge adds nothing for the part outside."""
    return sum(units for t, units in completions if t0 <= t < t1)


def tapered_rate(completions: Iterable[Tuple[float, int]],
                 t0: float, t1: float) -> float:
    """Units per second over ``[t0, t1)`` as the least-squares slope of the
    cumulative count of completed units against time.

    In closed form that is a weighted count: a completion at ``t`` weighs
    ``1.5 / T * (1 - ((t - mid) / (T / 2))**2)``, a parabola that integrates
    to one over the window and falls to nothing at both edges. Where
    responses come in bursts (six workers that take turns on one device
    answer within a second of each other, then nothing for seven) a plain
    count swings by a whole burst with where the edge happens to fall; the
    slope does not."""
    if t1 <= t0:
        raise ValueError(f"empty window [{t0}, {t1})")
    half, mid = 0.5 * (t1 - t0), 0.5 * (t0 + t1)
    return sum(units * 0.75 / half * (1.0 - ((t - mid) / half) ** 2)
               for t, units in completions if t0 <= t < t1)


def clipped_seconds(intervals: Iterable[Tuple[float, float]],
                    t0: float, t1: float) -> float:
    """Seconds of ``(start, duration)`` intervals that fall inside
    ``[t0, t1)``, summed over intervals (threads add up)."""
    total = 0.0
    for start, dur in intervals:
        total += max(0.0, min(start + dur, t1) - max(start, t0))
    return total


def request_latencies(due: Sequence[float], done: Sequence[Optional[float]],
                      t0: float, t1: float, limit_s: float) -> List[float]:
    """Latency of every request that was DUE inside ``[t0, t1)``: response
    visible minus due. A request with no good response (``None``) counts as
    the drain limit, and so does one that took longer than it."""
    out = []
    for d, t in zip(due, done):
        if t0 <= d < t1:
            out.append(limit_s if t is None else min(t - d, limit_s))
    return out
