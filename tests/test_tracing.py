"""Program spans (serving/spans.py): the ring's records nest as the work
does, spans of one request share its id, the ring is bounded, executable
builds are spanned once per cache miss, /metrics exports the build count,
every annotated span sits where the profiler's host plane has it, and the
device ops carry the named scopes a trace reduction reads."""

from __future__ import annotations

import asyncio
import tempfile
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_moe
from repro.core.base import make_scheduler
from repro.core.plan import SubmitSpec
from repro.models.model import DecoderModel
from repro.serving import spans
from repro.serving.engine import ITER_LOG_SIZE, Engine
from repro.serving.runtime import EngineExecutor, ServingRuntime

ENGINE_SPANS = ("engine.admit", "engine.launch", "engine.fetch",
                "engine.commit")


def _engine(n_slots=4, max_len=64, **kw):
    model = DecoderModel(tiny_moe())
    params = model.init(jax.random.PRNGKey(0))
    sched = make_scheduler("layered", model.n_blocks, n_slots=n_slots,
                           quantum=8)
    return Engine(model, params, sched, n_slots=n_slots, max_len=max_len,
                  **kw)


def _trace(n=6, gap=1.0, seed=0):
    rng = np.random.default_rng(seed)
    return [SubmitSpec(max_new_tokens=int(rng.integers(3, 6)),
                       prompt_tokens=tuple(int(t) for t in rng.integers(
                           1, 200, int(rng.integers(12, 30)))),
                       arrival_time=gap * i) for i in range(n)]


def _serve(eng, trace, wall=False):
    """Serve ``trace`` from an empty ring: on the iteration clock, or in
    wall seconds (the runtime then sleeps through idle gaps)."""
    spans.clear()
    rt = ServingRuntime(EngineExecutor(eng, wall=wall),
                        clock="executor" if wall else "iteration")
    res = rt.run(trace, max_iterations=10_000)
    return res, spans.records()


def _named(recs, name):
    return [r for r in recs if r[0] == name]


@pytest.fixture(scope="module")
def served():
    eng = _engine()
    res, recs = _serve(eng, _trace())
    return eng, res, recs


def test_spans_nest(served):
    eng, res, recs = served
    steps = _named(recs, "engine.step")
    assert len(steps) == res.n_iterations == len(eng.iter_log)
    assert all(r[3] == "runtime.run" for r in steps)
    assert all(r[3] == "runtime.run" for r in _named(recs, "scheduler.plan"))
    (run,) = _named(recs, "runtime.run")
    assert run[3] is None and run[4] == {"clock": "iteration"}
    # every sub-span of a step lies inside exactly one engine.step
    for name in ENGINE_SPANS:
        subs = _named(recs, name)
        assert subs and all(r[3] == "engine.step" for r in subs), name
        for _, a, b, _, _ in subs:
            assert sum(s[1] <= a and b <= s[2] for s in steps) == 1, name
    assert len(_named(recs, "engine.fetch")) == len(steps)
    # the step's attrs are its iter_log row and its layer groups
    for s, row in zip(steps, eng.iter_log):
        a = s[4]
        assert (a["iteration"], a["n_decode"], a["prefill_tokens"]) == \
            (row["iteration"], row["n_decode"], row["prefill_tokens"])
        assert (a["n_groups"] > 0) == (a["prefill_tokens"] > 0)


def test_req_id_links_inject_plan_and_prefill(served):
    eng, res, recs = served
    injects = {r[4]["req_id"]: r for r in _named(recs, "runtime.inject")}
    prefills = {r[4]["req_id"]: r for r in _named(recs, "request.prefill")}
    plans = _named(recs, "scheduler.plan")
    steps = _named(recs, "engine.step")
    assert set(injects) == set(prefills) == {r.req_id for r in res.requests}
    for req in res.requests:
        rid = req.req_id
        assert injects[rid][4]["due"] == req.arrival_time
        (plan,) = [p for p in plans if rid in p[4]["admitted"]]
        _, a, b, _, attrs = prefills[rid]
        assert a == plan[1] and injects[rid][2] <= a
        (last,) = [s for s in steps if rid in s[4]["first"]]
        assert b == last[2]
        walked = [s for s in steps if a <= s[1] and s[2] <= b]
        assert attrs["n_steps"] == len(walked) >= 2     # layer groups
        assert [s[4]["iteration"] for s in walked] == list(range(
            walked[0][4]["iteration"], last[4]["iteration"] + 1))


def test_ring_is_bounded_and_counts_drops():
    spans.clear()
    extra = 10
    for i in range(spans.MAXLEN + extra):
        spans.record("x", i, i + 1, i=i)
    recs = spans.records()
    assert len(recs) == spans.MAXLEN
    assert spans.dropped() == extra
    assert recs[0][4]["i"] == extra and recs[-1][4]["i"] == \
        spans.MAXLEN + extra - 1
    spans.clear()
    assert spans.records() == [] and spans.dropped() == 0


def test_iter_log_is_bounded(served):
    eng, _, _ = served
    assert eng.iter_log.maxlen == ITER_LOG_SIZE
    assert set(eng.iter_log[0]) == {
        "iteration", "n_decode", "prefill_tokens", "expert_load_bytes",
        "pages_in_use", "host_pages_in_use", "n_preempted",
        "n_swapped_out", "n_swapped_in", "n_dispatches"}


def test_engine_build_once_per_cache_miss():
    eng = _engine()
    trace = _trace(n=3)
    _, recs = _serve(eng, trace)
    builds = _named(recs, "engine.build")
    keys = [r[4]["key"] for r in builds]
    assert len(keys) == len(set(keys)) == eng.n_prefill_compiles > 0
    assert set(keys) == set(eng._jit_prefill)
    assert all(r[3] == "engine.launch" for r in builds)
    # prompts of the same lengths again (other ids: no prefix-cache hit)
    # hit every executable: no build
    again = [SubmitSpec(max_new_tokens=s.max_new_tokens,
                        prompt_tokens=tuple(t % 199 + 1
                                            for t in s.prompt_tokens),
                        arrival_time=s.arrival_time + 100) for s in trace]
    _, recs = _serve(eng, again)
    assert _named(recs, "engine.build") == []
    assert len(keys) == eng.n_prefill_compiles


def test_metrics_export_executables_built():
    from test_server import _fetch, _post_generate, _with_server

    async def body(srv):
        status, _, _ = await _post_generate(
            srv.host, srv.port, {"prompt_tokens": list(range(1, 20)),
                                 "max_new_tokens": 3, "stream": False})
        assert status == 200
        status, text = await _fetch(srv.host, srv.port, "/metrics")
        line = [ln for ln in text.decode().splitlines()
                if ln.startswith("repro_engine_executables_built_total ")]
        eng = srv.engine
        built = eng.n_prefill_compiles + eng.n_verify_compiles
        assert built > 0 and line == [
            f"repro_engine_executables_built_total {built}"]

    asyncio.run(_with_server(body))


def _host_plane(path: str, names) -> dict:
    from jax.profiler import ProfileData
    out = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        out[e.name].append((e.start_ns, e.end_ns))
    return out


def test_spans_sit_on_the_profilers_clock():
    """Aligned on ``runtime.run``, every annotated span's start and end on
    the profiler's host plane lie within 200 us of the ring's."""
    eng = _engine(prefix_cache=False)
    trace = _trace(n=3, gap=0.3)
    _serve(eng, trace, wall=True)            # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            _, recs = _serve(eng, trace, wall=True)
        finally:
            jax.profiler.stop_trace()
        pb = next(Path(tmp).rglob("*.xplane.pb"))
        ring = defaultdict(list)
        for name, a, b, _, _ in recs:
            if name != "request.prefill":       # recorded, not annotated
                ring[name].append((a, b))
        host = _host_plane(str(pb), set(ring))
    assert {"runtime.run", "runtime.inject", "runtime.idle",
            "scheduler.plan", "engine.step", *ENGINE_SPANS} <= set(ring)
    (r0,), (h0,) = ring["runtime.run"], host["runtime.run"]
    shift = h0[0] - r0[0]
    assert abs(h0[1] - r0[1] - shift) < 200e3
    for name, got in ring.items():
        seen = sorted(host[name])
        assert len(seen) == len(got), name
        for (a, b), (ha, hb) in zip(sorted(got), seen):
            assert abs(ha - shift - a) < 200e3, name
            assert abs(hb - shift - b) < 200e3, name


def test_named_scopes_in_the_executables():
    """The decode step's and a prefill group's ops carry the scopes a
    reduction reads by op metadata, and the prefill module is named."""
    eng = _engine(n_slots=2, max_len=32)
    cfg, n_blocks = eng.cfg, eng.model.n_blocks
    i32 = jnp.int32
    decode = jax.jit(eng._decode_step_impl).lower(
        eng.params, eng.cache, jnp.zeros((2, 1), i32), jnp.zeros(2, i32),
        jnp.ones(2, bool)).compile().as_text()
    fn = eng._get_prefill_fn(0, n_blocks, True, 1, 16)
    args = (eng.params, eng.cache, jnp.zeros((1, 16, cfg.d_model)),
            jnp.ones((1, 16), bool), jnp.zeros(1, i32), jnp.zeros(1, i32),
            jnp.full(1, 16, i32))
    fn(*args)                                # the miss: traced and built
    prefill = eng._jit_prefill[(0, n_blocks, True, 1, 16)].lower(
        eng.params, eng.cache, *args[2:])
    assert prefill.as_text().startswith("module @jit_prefill_group")
    hlo = prefill.compile().as_text()
    for scope in ("attention", "moe.route", "moe.experts", "kv_write"):
        for text in (decode, hlo):
            assert f"/{scope}/" in text, scope
