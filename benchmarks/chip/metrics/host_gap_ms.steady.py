"""Engine layer: mean host time between one engine step's ``engine.fetch``
end and the next step's ``engine.launch`` start, over the window's pairs
of consecutive steps with no ``runtime.idle`` span between them, in
milliseconds: host work (the step's bookkeeping, the loop, planning,
admission) in which the device has nothing of the serving loop's to run."""

import bisect

from chipbench.program_spans import steps, window


def compute(rec):
    sp = window(rec)
    if sp is None:
        return None
    idle = [(s.start, s.end) for s in sp if s.name == "runtime.idle"]
    idle_starts = [a for a, _ in idle]
    gaps = []
    st = steps(sp)
    for prev, nxt in zip(st, st[1:]):
        if prev.fetch is None or nxt.launch is None:
            continue
        lo, hi = prev.fetch.end, nxt.launch.start
        j = bisect.bisect_left(idle_starts, hi)
        if j and idle[j - 1][1] > lo:        # an idle span overlaps the gap
            continue
        gaps.append(hi - lo)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
