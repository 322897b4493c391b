"""Open-loop arrival schedules from a mix's parameters and the seed.

The arithmetic is ``video_features_tpu/loadgen.py``'s (named seeded streams,
a constant rate or a rate with periodic bursts), with one change: the number
of arrivals is fixed at the rate's integral over the horizon and the seed
draws only where they fall, which is a Poisson process conditioned on its
count. A run then offers the same amount of work under every seed.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from .corpus import stream


def rate_at(arrivals: Dict[str, Any], t: np.ndarray) -> np.ndarray:
    """Requests per second at times ``t`` (seconds from the first arrival
    instant): ``rate_rps``, plus ``burst.rate_rps`` during the first
    ``burst.length_s`` of every ``burst.period_s``."""
    rate = np.full_like(t, float(arrivals["rate_rps"]), dtype=np.float64)
    burst = arrivals.get("burst")
    if burst:
        inside = np.mod(t, float(burst["period_s"])) < float(burst["length_s"])
        rate = rate + inside * float(burst["rate_rps"])
    return rate


def schedule(arrivals: Dict[str, Any], horizon_s: float, seed: int,
             mix: str) -> List[float]:
    """Sorted due times in ``[0, horizon_s)``."""
    steps = max(1, int(round(horizon_s * 1000)))  # the rate on a 1 ms grid
    grid = np.linspace(0.0, float(horizon_s), steps + 1)
    mid = 0.5 * (grid[:-1] + grid[1:])
    cum = np.concatenate([[0.0], np.cumsum(
        rate_at(arrivals, mid) * np.diff(grid))])
    n = int(round(cum[-1]))
    u = np.sort(stream(seed, mix, "arrivals").uniform(0.0, cum[-1], n))
    return [float(x) for x in np.interp(u, cum, grid)]
