"""The reduction from a profiler trace to the device's busy time, its top
operations and its idle gaps named by host spans: on a hand-made trace
with known answers, and on a small trace recorded on a TPU v5e (test
data), checked against a plain per-microsecond timeline."""

import json
from pathlib import Path

import numpy as np
import pytest

import benchtiny  # noqa: F401  (puts the benchmark and the program on the path)
from chipbench import trace as TR

DATA = Path(__file__).resolve().parent / "data" / "v5e_trace_events.json"


def test_hand_made_trace():
    ev = {"host": [("bench.window", 0, 100e9),
                   ("bench.engine_step", 5e9, 35e9),
                   ("bench.wait_arrival", 40e9, 100e9)],
          "ops": [("a", 10e9, 20e9), ("b", 15e9, 30e9), ("a", 50e9, 60e9),
                  ("c", 120e9, 130e9)],                # after the window
          "modules": [("jit_step(7)", 10e9, 30e9)]}
    r = TR.reduce(ev)
    assert r["window_s"] == 100 and r["busy_s"] == 30
    assert r["device_ops"] == [["jit_step/b", 15.0], ["jit_step/a", 10.0],
                               ["?/a", 10.0]]
    assert r["idle_gaps"] == [["bench.wait_arrival", 60.0],
                              ["bench.engine_step", 10.0]]


def test_trace_without_window_or_ops_is_refused():
    with pytest.raises(ValueError):
        TR.reduce({"host": [], "ops": [("a", 0, 1)], "modules": []})
    with pytest.raises(ValueError):
        TR.reduce({"host": [("bench.window", 0, 10)], "ops": [],
                   "modules": []})


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_recorded_v5e_trace_against_a_timeline():
    ev = json.loads(DATA.read_text())
    r = TR.reduce(ev)
    lo, hi = next((a, b) for n, a, b in ev["host"] if n == TR.WINDOW)
    # plain timeline at 1 us: a slot is busy when any op covers it
    n = int((hi - lo) // 1000) + 1
    busy = np.zeros(n, bool)
    for _, a, b in ev["ops"]:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            busy[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    assert r["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert r["busy_s"] == pytest.approx(busy.sum() * 1e-6, rel=0.02)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    tops = [s for _, s in r["device_ops"]]
    assert tops == sorted(tops, reverse=True) and len(tops) <= 10
