"""The metric files' arithmetic on hand-made run records: failed and
unserved requests count as misses, time to first token is censored at the
window's end, and tails are taken over all samples together."""

import pytest

import benchtiny  # noqa: F401  (puts the benchmark and the program on the path)
from chipbench import spec


def _req(due, first=None, toks=(), shed=None, admit=None, injected=None,
         done=True):
    return {"id": 0, "due": due, "injected": due if injected is None
            else injected, "admit": admit, "first": first,
            "token_times": list(toks), "done": done, "shed": shed,
            "prompt_len": 10, "max_new": 1 + len(toks), "n_out": 0}


def _rec(reqs, window=10.0, **kw):
    r = {"window_s": window, "slo": {"ttft_s": 1.0,
                                                   "tbt_s": 0.1},
         "requests": reqs, "steps": [], "expert_load_bytes": 0,
         "moe": True, "setup_s": 3.0, "peak_flops": 100.0}
    r.update(kw)
    return r


def m(name, rec):
    return spec.metric(name)(rec)


def test_ttft_is_censored_at_the_window_end():
    reqs = [_req(0.0, first=0.5)] * 9 + [_req(2.0)]        # never served
    assert m("ttft_p90_s", _rec(reqs)) == pytest.approx(0.5 + 0.1 * 7.5)
    # a request due after the window is not counted
    assert m("ttft_p90_s", _rec(reqs[:9] + [_req(11.0)])) == 0.5


def test_first_token_after_the_window_counts_as_unserved():
    rec = _rec([_req(9.0, first=12.0)])
    assert m("ttft_p90_s", rec) == pytest.approx(1.0)
    assert m("out_tok_per_s", rec) == 0.0


def test_failed_and_late_requests_are_slo_misses():
    reqs = [_req(0.0, first=0.2, toks=[0.25, 0.3]),        # met both
            _req(0.0, first=0.2, toks=[0.5]),              # gap 0.3 > 0.1
            _req(0.0, first=1.5),                          # ttft 1.5 > 1
            _req(0.0, shed="deadline"),                    # failed
            _req(5.0),                                     # waited 5 s, none
            _req(9.5)]                                     # waited 0.5 s
    assert m("slo_ok_pct", _rec(reqs)) == pytest.approx(100 * 2 / 6)


def test_tbt_tail_is_over_all_gaps_not_per_request():
    # one request with 99 gaps of 10 ms and one gap of 1 s, 99 others with
    # one 10 ms gap each: p99 over the 199 gaps is 10 ms; a p99 over
    # per-request means would be dominated by the one slow request
    reqs = [_req(0.0, first=0.0, toks=[0.01 * (i + 1) for i in range(99)]
                 + [1.99])]
    reqs += [_req(0.0, first=1.0, toks=[1.01])] * 99
    assert m("tbt_p99_ms", _rec(reqs)) == pytest.approx(10.0, rel=1e-6)


def test_throughput_counts_tokens_inside_the_window():
    reqs = [_req(0.0, first=1.0, toks=[2.0, 3.0, 11.0])]
    assert m("out_tok_per_s", _rec(reqs)) == pytest.approx(0.3)


def test_layer_metrics():
    reqs = [_req(0.0, first=1.0, toks=[2.0], admit=0.5, injected=0.02),
            _req(1.0, first=3.0, toks=[4.0], admit=2.0, injected=1.01)]
    steps = [(0.0, 0.05, 10.0), (0.05, 0.15, 30.0), (9.9, 10.2, 50.0)]
    rec = _rec(reqs, steps=steps, expert_load_bytes=4e9,
               trace={"busy_s": 7.5, "window_s": 10.0})
    assert m("queue_wait_p50_s", rec) == pytest.approx(0.75)
    assert m("inject_lag_p99_ms", rec) == pytest.approx(
        10 + 0.99 * 10, rel=1e-6)
    assert m("iter_ms.steady", rec) == pytest.approx(75.0)
    assert m("expert_load_gb_per_tok", rec) == pytest.approx(1.0)
    assert m("step_mfu_pct", rec) == pytest.approx(100 * 40 / 1000)
    assert m("device_idle_pct.steady", rec) == pytest.approx(25.0)
    assert m("setup_s", rec) == 3.0


def test_metrics_with_nothing_to_read_return_none():
    rec = _rec([_req(0.0, first=1.0)], moe=False)
    for name in ("expert_load_gb_per_tok", "device_idle_pct.steady",
                 "iter_ms.steady"):
        assert m(name, rec) is None
    assert m("inject_lag_p99_ms", _rec([])) is None
