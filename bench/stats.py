"""Percentiles and the latency rules of the open loop.

`percentile` is the nearest-rank percentile of the program's
`serving/metrics.py`, copied here so that no later change to the program
moves the yardstick.
"""
from __future__ import annotations

from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float | None:
    """Nearest-rank percentile (q in [0, 100]); None for an empty input."""
    if not values:
        return None
    vals = sorted(values)
    if q <= 0:
        return vals[0]
    rank = max(1, -(-len(vals) * q // 100))  # ceil(len * q / 100)
    return vals[min(int(rank), len(vals)) - 1]


def censored_ttft(due: float, first: float | None, failed: bool,
                  t_open: float, t_close: float) -> float:
    """Time to first token of a request due at `due` in [t_open, t_close),
    timed from its due time.  A request that failed or was refused counts
    at the window's length; one with no first token when the window closed
    counts at its wait so far."""
    if failed:
        return t_close - t_open
    if first is None or first >= t_close:
        return t_close - due
    return first - due


def token_gaps(stamps: Sequence[float], t_open: float,
               t_close: float) -> list[float]:
    """Gaps between consecutive output tokens of one request, both inside
    the window."""
    inside = [t for t in stamps if t_open <= t < t_close]
    return [b - a for a, b in zip(inside, inside[1:])]
