"""Scheduler and engine layers: 90th percentile of the program's
``request.prefill`` spans (from the plan that admitted a request to the
end of the engine step that emitted its first token: its walk through the
layer groups) over the requests due in the window whose first token came
inside it, in seconds.  With the queue wait it makes up the TTFT."""

from chipbench.program_spans import window
from chipbench.record import due, percentile


def compute(rec):
    sp = window(rec)
    if sp is None:
        return None
    walk = {s.attrs["req_id"]: s.end - s.start for s in sp
            if s.name == "request.prefill"}
    end = rec["window_s"]
    return percentile([walk[r["id"]] for r in due(rec)
                       if r["first"] is not None and r["first"] <= end
                       and r["id"] in walk], 90)
