"""Output tokens delivered in the window over the window's seconds."""

from chipbench.record import due, token_times


def compute(rec):
    end = rec["window_s"]
    return sum(len(token_times(r, end)) for r in due(rec)) / end
