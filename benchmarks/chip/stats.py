"""End-to-end arithmetic over host time stamps.

Every number is taken over the whole measured window: a rate is all the
work done in the window over the window's length, and a tail is a
percentile of every sample, never a statistic of per-chunk statistics.
"""
from __future__ import annotations

from typing import Iterable, List, Sequence

import numpy as np


def percentile(samples: Iterable[float], q: float) -> float:
    x = np.asarray(list(samples), dtype=float)
    if x.size == 0:
        raise ValueError("no samples")
    return float(np.percentile(x, q))


def ttft_s(due: Sequence[float], first: Sequence[float]) -> List[float]:
    """Time to first token of each request, from when it was due."""
    return [f - d for d, f in zip(due, first)]


def token_gaps(stamps: Iterable[Sequence[float]], t0: float,
               t1: float) -> List[float]:
    """Gaps between consecutive output tokens of each request, for every
    gap that ends inside [t0, t1]."""
    out = []
    for s in stamps:
        s = np.asarray(s, dtype=float)
        if s.size < 2:
            continue
        gaps = np.diff(s)
        ends = s[1:]
        out.extend(gaps[(ends >= t0) & (ends <= t1)].tolist())
    return out


def tokens_per_s(stamps: Iterable[Sequence[float]], t0: float,
                 t1: float) -> float:
    """Output tokens stamped inside [t0, t1] over the window's length."""
    n = sum(int(np.sum((np.asarray(s) >= t0) & (np.asarray(s) <= t1)))
            for s in stamps)
    return n / (t1 - t0)

