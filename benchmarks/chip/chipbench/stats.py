"""Percentile arithmetic (the linear interpolation numpy uses by
default), copied from the program's ``serving/metrics.py`` so that the
yardstick cannot move with the program."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile of ``xs`` by linear interpolation between
    closest ranks; None for an empty sample."""
    if not xs:
        return None
    s = sorted(xs)
    if len(s) == 1:
        return float(s[0])
    pos = min(max(q, 0.0), 100.0) / 100.0 * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] + (s[hi] - s[lo]) * (pos - lo))
