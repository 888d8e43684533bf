"""Scheduler layer: median wait from due to admission
(``Request.queue_delay``), over the requests due in the window; one not
admitted by the window's end counts with its wait so far."""

from chipbench.record import due, percentile


def compute(rec):
    end = rec["window_s"]
    w = [(r["admit"] if r["admit"] is not None and r["admit"] <= end
          else end) - r["due"] for r in due(rec)]
    return percentile(w, 50)
