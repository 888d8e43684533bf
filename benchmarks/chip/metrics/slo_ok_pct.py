"""Share of the requests due in the window that met both limits of the
mix: first token within ``slo.ttft_s`` of the due time and every gap
between its tokens within ``slo.tbt_s``, as far as the window saw them.
A failed or refused request is a miss, and so is one still without a
first token whose wait already exceeds the limit."""

from chipbench.record import due, gaps, ttft


def compute(rec):
    end, slo = rec["window_s"], rec["slo"]
    reqs = due(rec)
    ok = sum(r["shed"] is None and ttft(r, end) <= slo["ttft_s"]
             and all(g <= slo["tbt_s"] for g in gaps(r, end))
             for r in reqs)
    return 100.0 * ok / len(reqs) if reqs else None
