"""Engine layer: median host time of the window's engine steps that
carried a prefill layer group (the program's ``engine.step`` spans with
``n_groups`` >= 1), in milliseconds: the steps that set the TBT tail."""

from chipbench.program_spans import steps, window
from chipbench.record import percentile


def compute(rec):
    sp = window(rec)
    if sp is None:
        return None
    p = percentile([s.end - s.start for s in steps(sp)
                    if s.attrs["n_groups"] >= 1], 50)
    return None if p is None else 1e3 * p
