"""The program's own spans (``repro.serving.spans``) over the measured
window, for the metric files that read them.

``window(rec)`` returns the ring's records that lie wholly inside the
window, as ``Span`` tuples with times in seconds on the window's clock (0
when it opens).  It returns None where there is nothing sound to read: a
program without the tracer, no window run, or a ring that dropped spans of
the window (its oldest record must end before the window opens).  The
window opens at the start of the last ``runtime.run`` span on the executor
clock: the warm-up's cohort replay runs ``ServingRuntime`` on that clock
too, before the window.  ``steps`` pairs each ``engine.step`` with its
launch and fetch."""

from __future__ import annotations

import bisect
from typing import List, NamedTuple, Optional


class Span(NamedTuple):
    name: str
    start: float
    end: float
    parent: Optional[str]
    attrs: dict


class Step(NamedTuple):
    start: float
    end: float
    attrs: dict
    launch: Optional[Span]
    fetch: Optional[Span]


def window(rec: dict) -> Optional[List[Span]]:
    try:
        from repro.serving import spans
    except ImportError:
        return None
    recs = spans.records()
    runs = [r for r in recs if r[0] == "runtime.run"
            and r[4].get("clock") == "executor"]
    if not runs:
        return None
    t0 = runs[-1][1]
    if spans.dropped() and recs[0][2] >= t0:
        return None
    hi = rec["window_s"]
    out = []
    for name, a, b, parent, attrs in recs:
        a, b = (a - t0) / 1e9, (b - t0) / 1e9
        if a >= 0 and b <= hi:
            out.append(Span(name, a, b, parent, attrs))
    out.sort(key=lambda s: s.start)
    return out


def steps(spans: List[Span]) -> List[Step]:
    """The window's engine steps in order, each with the ``engine.launch``
    and ``engine.fetch`` spans that ran inside it (None where it had
    none)."""
    outer = [s for s in spans if s.name == "engine.step"]
    starts = [s.start for s in outer]
    inner = {}
    for s in spans:
        if s.name in ("engine.launch", "engine.fetch"):
            j = bisect.bisect_right(starts, s.start) - 1
            if j >= 0 and s.end <= outer[j].end:
                inner[j, s.name] = s
    return [Step(s.start, s.end, s.attrs, inner.get((j, "engine.launch")),
                 inner.get((j, "engine.fetch")))
            for j, s in enumerate(outer)]
