"""The per-layer metrics that read the program's own spans, on a tiny
window on the CPU: each is computed in the MoE and the dense cell, they
agree with the benchmark's own step span and with the end-to-end TTFT,
and nothing is read from a ring that dropped spans of the window."""

import time

import pytest

import benchtiny as tiny
from chipbench import cell as C
from chipbench import program_spans as P
from chipbench import spec
from repro.serving import spans
from repro.serving.engine import Engine

NEW = ("iter_ms.prefill_p50", "iter_ms.decode_p50", "host_gap_ms.steady",
       "prefill_span_s.p90")


# step times the test sets: on a shared CPU the tiny model's own work is a
# few milliseconds with heavy tails, so every step sleeps a fixed time on
# top of it, longer when it carries a prefill layer group
STEP_S = {False: 0.004, True: 0.03}


@pytest.fixture(scope="module", params=["moe", "dense"])
def window(request):
    cs = tiny.cell(request.param)
    conf, mix = cs["config"], cs["traffic"]
    seed = 2 ** 31 + 29
    eng, sc = C.build(conf, seed)
    C.warm_up(eng, sc, conf, mix, 3.0)
    step = Engine._execute

    def paced(self, plan, span):
        first = step(self, plan, span)
        time.sleep(STEP_S[bool(plan.prefill)])
        return first
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Engine, "_execute", paced)
        rec = C.run_window(eng, sc, conf, mix, seed, 3.0)
    return rec, {n: spec.metric(n)(rec) for n in NEW + ("iter_ms.steady",)}


def test_each_metric_is_computed(window):
    rec, m = window
    assert all(m[n] is not None and m[n] > 0 for n in NEW), m
    assert set(NEW) <= set(tiny.cell()["per_layer"])


def test_steady_lies_between_the_step_medians(window):
    rec, m = window
    kinds = {s.attrs["n_groups"] > 0 for s in P.steps(P.window(rec))}
    assert kinds == {True, False}
    assert m["iter_ms.decode_p50"] <= m["iter_ms.steady"] \
        <= m["iter_ms.prefill_p50"], m


def test_queue_wait_plus_prefill_walk_is_the_ttft(window):
    rec, _ = window
    walk = {s.attrs["req_id"]: s for s in P.window(rec)
            if s.name == "request.prefill"}
    served = [r for r in rec["requests"] if r["first"] is not None
              and r["first"] <= rec["window_s"]]
    assert len(served) >= 5
    for r in served:
        s = walk[r["id"]]
        assert abs((r["admit"] - r["due"]) + (s.end - s.start)
                   - (r["first"] - r["due"])) < 2e-3, (r, s)
        assert s.attrs["n_steps"] >= 1


def test_a_ring_that_dropped_window_spans_reads_none(window):
    rec, _ = window
    assert P.window(rec) is not None
    recs = spans.records()
    t0 = [r for r in recs if r[0] == "runtime.run"
          and r[4]["clock"] == "executor"][-1][1]
    before = sum(r[2] < t0 for r in recs)
    assert before > 0

    def push(n):
        now = time.perf_counter_ns()
        for _ in range(n):
            spans.record("filler", now, now)
    # push out all but the last record that ended before the window
    push(spans.MAXLEN - len(recs) + before - 1)
    assert P.window(rec) is not None
    # one more: the oldest record left may be one of the window's
    push(1)
    assert P.window(rec) is None
    for name in NEW:
        assert spec.metric(name)(rec) is None
