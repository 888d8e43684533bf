"""Runtime layer: 99th percentile of how late the serving loop handed a
request to the engine after it was due, in milliseconds, from the benchmark's span around each submit."""

from chipbench.record import percentile


def compute(rec):
    lags = [r["injected"] - r["due"] for r in rec["requests"]
            if r["due"] < rec["window_s"]]
    p = percentile(lags, 99)
    return None if p is None else 1e3 * p
