"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

``extract`` reads the trace with ``jax.profiler.ProfileData`` and keeps
three lists of (name, start_ns, end_ns): the operations on the first
device's op line, the executables on its module line, and the benchmark's
own host spans (``bench.*`` TraceAnnotations).  ``reduce`` turns them
into the traced window's length, the time in which any operation ran
(the union of the op intervals), the operations that took most time
(each named with the executable it ran in), and the idle time between
operations, each gap named by the host span that overlaps it most.  The
window is the ``bench.window`` span, so host and device times are read on
the trace's one clock."""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Tuple

Span = Tuple[str, float, float]

WINDOW = "bench.window"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def extract(path: str) -> Dict[str, List[Span]]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: Dict[str, List[Span]] = {"ops": [], "modules": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and not out["ops"]:
            for line in plane.lines:
                key = ("ops" if line.name in OP_LINES else "modules"
                       if line.name in MODULE_LINES else None)
                if key:
                    out[key] = [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.end_ns)
                                for e in line.events
                                if e.name.startswith("bench.")]
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(spans: List[Span], lo: float, hi: float) -> List[Span]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in spans
            if b > lo and a < hi]


def _short(name: str) -> str:
    """An op's HLO name without its text (``%while.65 = (...) while(...)``
    -> ``while.65``), an executable's without its id (``jit_f(12)``)."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0] if name.endswith(")") else name


def _top(totals: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, s] for n, s in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:k]]


def reduce(ev: Dict[str, List[Span]]) -> dict:
    """{window_s, busy_s, device_ops, idle_gaps}; times in seconds.  Raises when the trace holds no window or no device op."""
    windows = [(a, b) for n, a, b in ev["host"] if n == WINDOW]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    lo, hi = windows[0]
    ops = _clip(ev["ops"], lo, hi)
    if not ops:
        raise ValueError("trace has no device operation in the window")
    busy = union([(a, b) for _, a, b in ops])
    busy_ns = sum(b - a for a, b in busy)

    mods = sorted(_clip(ev["modules"], lo, hi), key=lambda s: s[1])
    mod_starts = [a for _, a, _ in mods]
    op_time: Dict[str, float] = defaultdict(float)
    for n, a, b in ops:
        j = bisect.bisect_right(mod_starts, a) - 1
        mod = _short(mods[j][0]) if j >= 0 and mods[j][2] >= b else "?"
        op_time[f"{mod}/{_short(n)}"] += (b - a) / 1e9

    spans = [s for s in _clip(ev["host"], lo, hi) if s[0] != WINDOW]
    gaps, t = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    idle: Dict[str, float] = defaultdict(float)
    spans.sort(key=lambda s: s[1])
    starts = [a for _, a, _ in spans]
    for ga, gb in gaps:
        best, best_ov = "no bench span", 0.0
        j = bisect.bisect_left(starts, gb)
        # the benchmark's spans follow one another, so the ones that
        # overlap a gap are among the last few that start before its end
        for n, a, b in spans[max(0, j - 16):j]:
            ov = min(b, gb) - max(a, ga)
            if ov > best_ov:
                best, best_ov = n, ov
        idle[best] += (gb - ga) / 1e9
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "device_ops": _top(op_time), "idle_gaps": _top(idle)}
