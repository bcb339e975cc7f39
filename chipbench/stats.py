"""Percentile and rate arithmetic of the end-to-end metrics.

A tail is taken over every request due in the window, the slow and the
failed included: a failed request counts at the latency it had when the
run gave up on it, so it misses every limit a served one meets.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0 < q <= 100) by the nearest-rank rule:
    the smallest value with at least ``q`` percent of all values at or
    below it. ``None`` for no values."""
    if not values:
        return None
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def latencies(due: Sequence[float], answered: Sequence[Optional[float]],
              gave_up: float) -> list:
    """Seconds from when each request was due to when its answer was on
    the host; an unanswered one (``None``) counts until ``gave_up``."""
    return [(gave_up if a is None else a) - d for d, a in zip(due, answered)]


def solves_per_s(first_submit: float, completions: Sequence[float],
                 solves_each: int, window_end: float) -> Optional[float]:
    """Source solves completed per second over the span from the first
    submit to the last completion at or before ``window_end``."""
    done = [c for c in completions if c <= window_end]
    if not done:
        return None
    span = max(done) - first_submit
    if span <= 0:
        return None
    return solves_each * len(done) / span
