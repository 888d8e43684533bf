"""Device: share of the traced window in which no operation ran on the
device (one minus the union of the op intervals of the profiler's device
plane over the window), in percent.  Only a traced run has it."""


def compute(rec):
    t = rec.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
