"""Engine layer: median host time of the window's engine steps that
carried no prefill layer group (the program's ``engine.step`` spans with
``n_groups`` 0), in milliseconds: the decode-only steps."""

from chipbench.program_spans import steps, window
from chipbench.record import percentile


def compute(rec):
    sp = window(rec)
    if sp is None:
        return None
    p = percentile([s.end - s.start for s in steps(sp)
                    if s.attrs["n_groups"] == 0], 50)
    return None if p is None else 1e3 * p
