"""Exact order statistics and rates over all samples of a window."""

from __future__ import annotations

import math
from typing import Iterable, Sequence


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of the samples at or below it.  Exact (no interpolation, no
    sketch), so a missing request entered as ``inf`` stays a miss."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100], got {q}")
    rank = math.ceil(q / 100.0 * len(xs))
    return xs[max(rank, 1) - 1]


def rate(count: float, seconds: float) -> float:
    """Events per second over a window of ``seconds``."""
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return count / seconds


def count_in(times: Sequence[float], t0: float, t1: float) -> int:
    """Timestamps in the half-open window [t0, t1)."""
    return sum(1 for t in times if t0 <= t < t1)
