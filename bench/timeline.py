"""Open-loop Poisson arrivals and the latency tail taken from due times.

The arrival model is that of `benchmarks/loadgen.py` (independent clients:
requests arrive on a Poisson timeline at the offered rate whatever the
server does), with two changes that make it a yardstick:

- Every seed offers the same work at the same moments. The window's
  request count is rate x seconds, and its gaps are the evenly spaced
  quantiles of Exp(rate) in one fixed order, the same for every seed, so
  every seed brings the same bursts; the sizes are the evenly spaced
  quantiles of U[lo, hi] rows in an order drawn from the seed. Seeds
  differ in which request is large and in the query points, not in how
  much is asked or when.
- Latency runs from the request's DUE time on the schedule to its result,
  so a stalled generator or server charges its wait to every request
  behind it, and a request that is rejected, dropped or fails counts as a
  miss (infinite latency) in the tail.
"""
from __future__ import annotations

import math

import numpy as np


def arrivals(rate: float, seconds: float):
    """Due times (s from the window's start) of rate x seconds requests:
    quantiles of Exp(rate) gaps in a fixed shuffled order, rescaled so
    that the last one is due inside the window."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    np.random.default_rng(0).shuffle(gaps)
    due = np.cumsum(gaps)
    return due * min(1.0, seconds / (due[-1] + gaps.mean() * 0.5))


def sizes(rng: np.random.Generator, n: int, lo: int, hi: int):
    """n request sizes: evenly spaced over [lo, hi] rows, shuffled."""
    s = np.rint(lo + (hi - lo) * (np.arange(n) + 0.5) / n).astype(int)
    rng.shuffle(s)
    return s


def percentile(latencies, p: float) -> float:
    """The p-th percentile (nearest rank) of latencies, misses as +inf:
    the value below which p% of ALL requests finished."""
    lat = np.sort(np.asarray(latencies, np.float64))
    if lat.size == 0:
        return math.inf
    k = max(0, math.ceil(p / 100.0 * lat.size) - 1)
    return float(lat[k])


def tail_ms(due, done, p: float = 95.0) -> float:
    """p-th percentile latency in ms from due times to completion times
    (NaN or None completion = a miss)."""
    done = np.asarray([np.nan if d is None else d for d in done], np.float64)
    lat = np.where(np.isfinite(done), done - np.asarray(due), np.inf)
    return percentile(lat, p) * 1e3
