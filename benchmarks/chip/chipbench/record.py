"""Arithmetic shared by the metric files: the run record's requests as
the window saw them.  Times are seconds on the window's clock (0 when the
window opens); a token stamped after the window's end is outside it."""

from __future__ import annotations

from typing import List

from chipbench.stats import percentile  # noqa: F401  (the metric files use it)


def due(rec: dict) -> List[dict]:
    """Every request due in the window."""
    return [r for r in rec["requests"] if r["due"] < rec["window_s"]]


def ttft(r: dict, end: float) -> float:
    """Time to first token from when the request was due; a request with
    no first token by the window's end counts with its wait so far."""
    if r["first"] is not None and r["first"] <= end:
        return r["first"] - r["due"]
    return end - r["due"]


def token_times(r: dict, end: float) -> List[float]:
    ts = ([r["first"]] if r["first"] is not None else []) + r["token_times"]
    return [t for t in ts if t <= end]


def gaps(r: dict, end: float) -> List[float]:
    ts = token_times(r, end)
    return [b - a for a, b in zip(ts, ts[1:])]
