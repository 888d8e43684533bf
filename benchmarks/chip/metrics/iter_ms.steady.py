"""Engine layer: mean host time of one ``Engine`` step (the benchmark's
span around the executor's execute, which ends in the step's one
``device_get``) over the window's steps, in milliseconds."""


def compute(rec):
    d = [t1 - t0 for t0, t1, _ in rec["steps"] if t1 <= rec["window_s"]]
    return 1e3 * sum(d) / len(d) if d else None
