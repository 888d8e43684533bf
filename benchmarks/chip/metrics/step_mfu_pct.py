"""Model step: model FLOPs of every prompt and output token the window's
completed steps processed, each at its context length (chipbench.flops,
from the configuration's shapes), over the window's seconds times the
device's peak bf16 FLOP/s, in percent."""


def compute(rec):
    f = sum(x for _, t1, x in rec["steps"] if t1 <= rec["window_s"])
    return 100.0 * f / (rec["window_s"] * rec["peak_flops"])
