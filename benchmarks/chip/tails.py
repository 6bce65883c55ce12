"""Percentiles and the samples they are taken over.

Every tail is taken over all the samples of the window, never from
medians of parts of it.  A percentile is the nearest rank: the smallest
sample with at least ``p`` percent of the samples at or below it.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def nearest_rank(values: Sequence[float], p: float) -> Optional[float]:
    """The ``p``-th percentile (0 < p <= 100) by nearest rank; None for
    no samples."""
    if not values:
        return None
    xs = sorted(values)
    k = max(int(math.ceil(p / 100.0 * len(xs))) - 1, 0)
    return float(xs[k])


def median(values: Sequence[float]) -> Optional[float]:
    return nearest_rank(values, 50.0)


def in_window(record: Dict, t: float) -> bool:
    t0, t1 = record["window"]
    return t0 <= t <= t1


def due_in_window(record: Dict) -> List[Dict]:
    """Requests due between the window's start and its nominal end."""
    t0 = record["window"][0]
    return [r for r in record["requests"]
            if t0 <= r["due"] < record["nominal_end"]]


def ttft_samples(record: Dict) -> List[float]:
    """Seconds from due time to first token, for every request due in the
    window.  A request that failed or had no first token by the window's
    close ranks above every served one: it gets the time it had waited
    by the close plus the largest served value."""
    t1 = record["window"][1]
    served, missing = [], []
    for r in due_in_window(record):
        if r["stamps"] and r["stamps"][0] <= t1 and not r["failed"]:
            served.append(r["stamps"][0] - r["due"])
        else:
            missing.append(t1 - r["due"])
    top = max(served, default=0.0)
    return served + [top + m for m in missing]


def itl_samples(record: Dict) -> List[float]:
    """Every gap between consecutive tokens of one request, both tokens
    inside the window."""
    gaps = []
    for r in record["requests"]:
        st = [t for t in r["stamps"] if in_window(record, t)]
        gaps.extend(b - a for a, b in zip(st, st[1:]))
    return gaps
