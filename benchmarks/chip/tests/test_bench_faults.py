"""A whole run of the harness at a tiny size on the CPU (the look for a
chip skipped), with the timed path broken underneath: ``correct`` has to
come out false for each fault a serving cell can have, and true for the
sound program."""

import jax.numpy as jnp
import pytest

import benchtiny as tiny
import bench
from repro.serving.engine import Engine

LABEL = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def _run(seed=2 ** 31 + 17):
    return bench.serve_and_check(tiny.cell("moe"), seed, 3.0, False, LABEL,
                                 lambda msg: None)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_s", "tbt_p99_ms",
                                   "out_tok_per_s", "setup_s"}


def _token_altered(monkeypatch):
    orig = Engine._record_token

    def record(self, rid, tok, *, first):
        orig(self, rid, (tok + 1) % self.cfg.vocab_size, first=first)
    monkeypatch.setattr(Engine, "_record_token", record)


def _state_unchanged(monkeypatch):
    orig = Engine._decode_step_impl

    def step(self, params, cache, tokens, offsets, valid_rows):
        tok, _, loads = orig(self, params, cache, tokens, offsets,
                             valid_rows)
        return tok, cache, loads
    monkeypatch.setattr(Engine, "_decode_step_impl", step)


def _half_batch_left_out(monkeypatch):
    orig = Engine._decode_step_impl

    def step(self, params, cache, tokens, offsets, valid_rows):
        half = jnp.arange(valid_rows.shape[0]) % 2 == 0
        return orig(self, params, cache, tokens, offsets, valid_rows & half)
    monkeypatch.setattr(Engine, "_decode_step_impl", step)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch_left_out],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = _run()
    assert not out["correct"], out["checks"]


def test_answers_that_come_late_are_checked(monkeypatch):
    """A window in which the host stalls so long that no request finishes
    in it: the run serves on past the close, and judges the late answers
    by what they say."""
    import time

    from chipbench import cell as C
    stalled = {"on": False}
    execute = Engine.execute_plan

    def slow(self, plan):
        if stalled["on"]:
            time.sleep(1.0)
        return execute(self, plan)

    run_window = C.run_window

    def run(*a, **k):
        stalled["on"] = True
        try:
            rec = run_window(*a, **k)
        finally:
            stalled["on"] = False
        assert not any(r["done"] for r in rec["requests"])
        return rec

    monkeypatch.setattr(Engine, "execute_plan", slow)
    monkeypatch.setattr(C, "run_window", run)
    out = _run()
    assert out["correct"], out["checks"]
    assert out["checks"]["checked_requests"]["value"] >= 1
