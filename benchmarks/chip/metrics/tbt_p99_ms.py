"""99th percentile of every gap between consecutive output tokens in the
window, over all requests together, in milliseconds."""

from chipbench.record import due, gaps, percentile


def compute(rec):
    g = [x for r in due(rec) for x in gaps(r, rec["window_s"])]
    p = percentile(g, 99)
    return None if p is None else 1e3 * p
