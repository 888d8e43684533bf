"""Engine / MoE layer: expert-weight bytes the engine counted as loaded
over the window (``Engine.expert_load_bytes``, reckoned from the union of
routed experts per layer and step: a count, not a device read), per
output token delivered in the window, in GB."""

from chipbench.record import due, token_times


def compute(rec):
    if not rec["moe"]:
        return None
    n = sum(len(token_times(r, rec["window_s"])) for r in due(rec))
    return rec["expert_load_bytes"] / n / 1e9 if n else None
