"""Open-loop arrival times from a traffic mix's parameters.

    arrivals   "poisson": exponential gaps at `rate_per_s`

The gaps are drawn from the mix's own fixed `schedule_seed` and only
their order comes from the run's seed, so every seed offers the same
set of arrivals and the same total work.
"""
from __future__ import annotations

import numpy as np

from harness import data


def arrivals(traffic: dict, seconds: float, seed: int,
             stream: int = data.STREAM_ORDER) -> np.ndarray:
    """Sorted send times (s from the window's start) in [0, seconds)."""
    rate = float(traffic["rate_per_s"])
    kind = traffic.get("arrivals", "poisson")
    fixed = np.random.default_rng(int(traffic.get("schedule_seed", 0)))
    order = data.rng(seed, stream)
    if kind == "poisson":
        n = int(rate * seconds * 1.2) + 64
        gaps = fixed.exponential(1.0 / rate, n)
        order.shuffle(gaps)
        t = np.cumsum(gaps) - gaps[0]
    else:
        raise ValueError(f"unknown arrivals {kind!r}")
    if t[-1] < seconds:
        raise ValueError("schedule too short for the window")
    return t[t < seconds]
