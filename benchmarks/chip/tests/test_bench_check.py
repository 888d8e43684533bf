"""The comparison that decides ``correct`` and the knee rule of the rate
sweep, on hand-made readings."""

import pytest

import benchtiny  # noqa: F401  (puts the benchmark and the program on the path)
import sweep
from chipbench import check as K

SUMMARY = {"widest": 0.3, "p99": 0.05, "mean": 0.008, "not_first": 0.1,
           "tokens": 900}


@pytest.mark.parametrize("limits,ok", [
    ({"mean_logit_gap": 0.01}, True),
    ({"mean_logit_gap": 0.005}, False),
    ({"mean_logit_gap": 0.01, "widest_logit_gap": 0.4}, True),
    ({"mean_logit_gap": 0.01, "widest_logit_gap": 0.2}, False),
])
def test_each_named_statistic_is_held_to_its_limit(limits, ok):
    compared = K.checks({"check": limits}, SUMMARY)
    assert set(compared) == set(limits)
    assert compared["mean_logit_gap"]["value"] == 0.008
    assert K.passed(compared) is ok


def test_no_reading_is_not_correct():
    assert not K.passed(K.checks({"check": {"mean_logit_gap": 1.0}}, {}))


def _row(rate, ttft, mid, end):
    return {"rate_rps": rate, "ttft_p90_s": ttft, "backlog_mid": mid,
            "backlog_end": end}


def test_knee_is_the_highest_rate_with_no_growing_queue():
    rows = [_row(0.4, 0.6, 0, 0), _row(0.5, 0.9, 1, 0),
            _row(0.6, 2.4, 0, 0), _row(0.7, 5.8, 0, 6)]
    assert sweep.knee(rows, 10.0) == 0.6
    # a TTFT tail past a quarter of the limit is past the knee
    assert sweep.knee(rows, 8.0) == 0.5
    # so is a backlog larger at the end than at the middle
    assert sweep.knee([_row(0.3, 0.5, 2, 3)], 10.0) is None
