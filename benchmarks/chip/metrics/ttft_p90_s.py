"""90th percentile of time to first token over every request due in the
window, timed from when it was due; a request with no first token by the window's end counts with its wait so
far, so a stall shows."""

from chipbench.record import due, percentile, ttft


def compute(rec):
    return percentile([ttft(r, rec["window_s"]) for r in due(rec)], 90)
