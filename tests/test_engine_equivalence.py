"""The paper's implicit correctness requirement: scheduling must not change
model outputs. Running the SAME tiny model + prompts through the engine
under every scheduler must generate identical token sequences — layered
prefill (group-wise vertical execution with stashed boundary activations)
is numerically the same function as one-shot prefill.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from conftest import tiny_dense, tiny_hybrid, tiny_mla, tiny_moe, tiny_xlstm
from repro.core.base import make_scheduler
from repro.core.plan import RequestState
from repro.models import moe
from repro.models.model import DecoderModel
from repro.serving.engine import Engine

SCHEDS = ["continuous", "chunked", "layered", "hybrid", "static"]


def generate(cfg, sched_name, prompts, max_new=6, moe_dispatch="ragged",
             gmm_fn=None, **sched_kw):
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = make_scheduler(sched_name, model.n_blocks, n_slots=4,
                           quantum=8, token_budget=16, **sched_kw)
    eng = Engine(model, params, sched, n_slots=4, max_len=128,
                 moe_dispatch=moe_dispatch, gmm_fn=gmm_fn)
    for p in prompts:
        eng.submit(p, max_new)
    eng.run()
    return {rid: list(toks) for rid, toks in eng.outputs.items()}


def reference_generate(cfg, prompts, max_new=6):
    """Naive greedy loop: full forward over the growing sequence."""
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    outs = {}
    for rid, p in enumerate(prompts):
        toks = list(p)
        out = []
        for _ in range(max_new):
            logits, _, _ = model.forward(
                params, jax.numpy.asarray([toks], dtype=jax.numpy.int32))
            nxt = int(jax.numpy.argmax(logits[0, -1]))
            out.append(nxt)
            toks.append(nxt)
        outs[rid] = out
    return outs


PROMPTS = [list(range(1, 12)), [5, 3, 7] * 9, list(range(40, 10, -1))]


@pytest.mark.parametrize("make_cfg", [tiny_dense, tiny_moe, tiny_mla,
                                      tiny_hybrid, tiny_xlstm],
                         ids=["dense", "moe", "mla", "hybrid", "xlstm"])
def test_all_schedulers_agree(make_cfg):
    cfg = make_cfg()
    base = generate(cfg, "continuous", PROMPTS)
    for name in SCHEDS[1:]:
        got = generate(cfg, name, PROMPTS)
        assert got == base, f"{name} diverged from continuous on {cfg.name}"


@pytest.mark.parametrize("sched", ["layered", "chunked"])
def test_moe_engine_dense_vs_ragged_dispatch(sched):
    """Acceptance: the dropless engine must produce IDENTICAL tokens with
    the dense capacity buffer and the ragged tile-aligned pipeline, under
    both the layered and chunked schedulers."""
    cfg = tiny_moe()
    dense = generate(cfg, sched, PROMPTS, moe_dispatch="dense")
    ragged = generate(cfg, sched, PROMPTS, moe_dispatch="ragged")
    assert ragged == dense, f"{sched}: ragged dispatch changed outputs"


@pytest.mark.parametrize("sched", ["layered", "chunked"])
@pytest.mark.parametrize("pattern", [(), ("gqa", "local_gqa")],
                         ids=["uniform", "two-block-pattern"])
def test_moe_engine_experts_in_place_matches_sliced(sched, pattern):
    """Ragged MoE blocks read their routed experts in place from the layer
    stack, by (layer, expert), in the decode scan and in packed prefill
    groups.  Handing the same jnp tile loop as ``gmm_fn`` keeps it on
    per-layer slices of the stack: the token streams are identical."""
    cfg = tiny_moe(n_layers=4, mixer_pattern=pattern, local_window=16)
    prompts = PROMPTS + [list(range(3, 60))]
    in_place = generate(cfg, sched, prompts, max_new=8)
    sliced = generate(cfg, sched, prompts, max_new=8,
                      gmm_fn=moe.ragged_ffn_ref)
    assert in_place == sliced


def test_engine_matches_naive_reference():
    cfg = tiny_dense()
    eng_out = generate(cfg, "layered", PROMPTS)
    ref_out = reference_generate(cfg, PROMPTS)
    assert eng_out == ref_out


def test_layered_stash_carries_activations():
    """A layered run on a deep stack forces >1 group: the boundary stash
    must be written and consumed (empty at drain)."""
    cfg = tiny_dense(n_layers=4)
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = make_scheduler("layered", model.n_blocks, n_slots=2, quantum=8)
    eng = Engine(model, params, sched, n_slots=2, max_len=128)
    eng.submit(list(range(1, 30)), 3)   # 29 tokens, quantum 8 -> G=4
    saw_stash = False
    while eng.scheduler.has_work():
        eng.step()
        saw_stash = saw_stash or bool(eng.stash)
    assert saw_stash
    assert not eng.stash


def test_moe_expert_loads_layered_leq_chunked():
    """Table 7's mechanism on a real router: layered prefill must load
    fewer (or equal) expert-bytes than chunked for long prompts."""
    cfg = tiny_moe(n_layers=4, moe=tiny_moe().moe)
    long_prompts = [list(np.random.default_rng(i).integers(1, 200, 64))
                    for i in range(2)]
    outs = {}
    loads = {}
    for name in ("chunked", "layered"):
        model = DecoderModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sched = make_scheduler(name, model.n_blocks, n_slots=4, quantum=8,
                               token_budget=16)
        eng = Engine(model, params, sched, n_slots=4, max_len=128)
        for p in long_prompts:
            eng.submit(p, 4)
        eng.run()
        outs[name] = eng.outputs
        loads[name] = eng.expert_load_bytes
    assert outs["layered"] == outs["chunked"]
    assert loads["layered"] <= loads["chunked"]
    # 64-token prompts at quantum 8 => 8 chunks; amplification must be real
    assert loads["layered"] < 0.75 * loads["chunked"]


def test_engine_eos_early_exit():
    cfg = tiny_dense()
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # find the first greedily generated token, then use it as EOS
    ref = reference_generate(cfg, [PROMPTS[0]], max_new=1)[0][0]
    eng = Engine(model, params, "layered", n_slots=2, max_len=128,
                 eos_token=ref)
    rid = eng.submit(PROMPTS[0], 50)
    eng.run()
    assert eng.outputs[rid] == [ref]       # stopped at EOS, not 50 tokens
    assert eng.requests[rid].finish_time is not None


def test_bucket_capped_at_max_len():
    from repro.serving.engine import _bucket
    assert _bucket(5) == 16
    assert _bucket(17) == 32
    assert _bucket(100, cap=112) == 112     # clamped below the pow2 bucket
    assert _bucket(100, cap=64) == 100      # never below n itself
    assert _bucket(60, cap=96) == 64        # cap above the bucket: no-op
    assert _bucket(100) == 128


def test_prefill_jit_cache_is_lru_bounded():
    from repro.serving.engine import PREFILL_CACHE_SIZE
    cfg = tiny_dense()
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, "layered", n_slots=2, max_len=64)
    for start in range(PREFILL_CACHE_SIZE + 8):
        eng._get_prefill_fn(start % (PREFILL_CACHE_SIZE + 4), 1, False, 1, 16)
    assert len(eng._jit_prefill) <= PREFILL_CACHE_SIZE
    # hits refresh recency: oldest surviving key evicts first, hit key stays
    keys = list(eng._jit_prefill)
    eng._get_prefill_fn(*keys[0])                 # touch the LRU entry
    eng._get_prefill_fn(999, 1, False, 1, 16)     # force one eviction
    assert keys[0] in eng._jit_prefill
    assert keys[1] not in eng._jit_prefill


def test_prefill_jit_cache_keys_include_shape_buckets():
    """The LRU key folds the batch and padded-token buckets in: shape
    retraces land in their own entries (one entry == one executable), so
    the PREFILL_CACHE_SIZE bound is real on mixed-shape traces."""
    cfg = tiny_dense()
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, "layered", n_slots=4, max_len=64)
    eng._get_prefill_fn(0, 1, False, 1, 16)
    eng._get_prefill_fn(0, 1, False, 1, 32)      # P retrace: new entry
    eng._get_prefill_fn(0, 1, False, 4, 16)      # B retrace: new entry
    eng._get_prefill_fn(0, 1, False, 1, 16)      # hit, not a compile
    assert len(eng._jit_prefill) == 3
    assert eng.n_prefill_compiles == 3


def _run_engine(cfg, sched_name, jobs, **eng_kw):
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = make_scheduler(sched_name, model.n_blocks, n_slots=4, quantum=8,
                           token_budget=16)
    eng = Engine(model, params, sched, n_slots=4, max_len=64, **eng_kw)
    for prompt, max_new in jobs:
        eng.submit(prompt, max_new)
    eng.run(max_iterations=100_000)
    return eng


@pytest.mark.parametrize("sched", ["layered", "chunked"])
def test_oversubscribed_pool_preempts_and_matches_unconstrained(sched):
    """Acceptance: requests >> pool capacity must COMPLETE via queueing +
    preemption (never 'pool exhausted'), and every request — including the
    recompute-restored victims — must emit exactly the tokens of an
    unconstrained run."""
    cfg = tiny_dense()
    rng = np.random.default_rng(0)
    jobs = [(list(rng.integers(1, 200, int(rng.integers(4, 10)))), 12)
            for _ in range(32)]
    # pool sized for ~3 resident requests (16 pages) against 32 submitted;
    # decode_reserve=1 forces growth pressure once decodes lengthen
    tight = _run_engine(cfg, sched, jobs, pages=16, page_size=4,
                        decode_reserve=1)
    assert tight.n_preempted > 0, "scenario must actually preempt"
    assert tight.alloc.pages_high_water <= tight.alloc.n_pages
    assert tight.alloc.pages_in_use() == 0

    free = _run_engine(cfg, sched, jobs)        # unconstrained pool
    assert free.n_preempted == 0
    assert tight.outputs == free.outputs, \
        "preemption/recompute changed generated tokens"
    # recompute-restored requests specifically were exercised and agree
    restored = [rid for rid, r in tight.requests.items()
                if r.n_preemptions > 0]
    assert restored
    for rid in restored:
        assert tight.outputs[rid] == free.outputs[rid]
        assert len(tight.outputs[rid]) == 12


def test_double_preemption_tokens_identical():
    """Force the SAME request through two evictions (fold-on-fold): the
    recompute prompt must extend by only the unfolded tail each time and
    the generated tokens must match an undisturbed run."""
    cfg = tiny_dense()
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sched = make_scheduler("layered", model.n_blocks, n_slots=2, quantum=8)
    eng = Engine(model, params, sched, n_slots=2, max_len=64)
    rid = eng.submit(list(range(1, 9)), 12)
    forced = []
    while eng.scheduler.has_work():
        r = eng.requests[rid]
        if r.state == RequestState.DECODE and r.n_generated in (3, 7) \
                and r.n_generated not in forced:
            sched.preempt(rid)            # what the pressure pass would do
            eng._preempt(rid)             # what step() would execute
            forced.append(r.n_generated)
        eng.step()
    assert forced == [3, 7]
    assert eng.requests[rid].n_preemptions == 2
    assert len(eng.prompts[rid]) == 8 + 7   # orig + folded, not 8+3+7+...
    clean = _run_engine(cfg, "layered", [(list(range(1, 9)), 12)])
    assert eng.outputs[rid] == clean.outputs[0]
    assert len(eng.outputs[rid]) == 12


def test_preemption_off_queues_but_can_exhaust():
    """--preemption off: admission still queues on pressure (no crash on
    submit), but unreservable decode growth surfaces PagedPoolExhausted."""
    from repro.serving.kvcache import PagedPoolExhausted
    cfg = tiny_dense()
    # each request alone fits the pool (passes the admission guard), but
    # two residents' CONCURRENT decode growth overcommits it
    jobs = [([1, 2, 3, 4], 14) for _ in range(2)]
    with pytest.raises(PagedPoolExhausted):
        _run_engine(cfg, "chunked", jobs, pages=8, page_size=4,
                    decode_reserve=0, preemption=False)


def test_engine_run_iteration_cap_checked_before_step():
    cfg = tiny_dense()
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, "layered", n_slots=2, max_len=64)
    eng.submit(list(range(1, 9)), 20)
    with pytest.raises(RuntimeError, match="did not drain"):
        eng.run(max_iterations=3)
    assert eng.iteration == 3              # cap enforced AT the cap


def test_submit_rejects_prompt_plus_max_new_over_max_len():
    cfg = tiny_dense()
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, "layered", n_slots=2, max_len=32)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(list(range(1, 30)), 8)


def test_engine_slot_reuse_many_requests():
    """More requests than slots: allocator must recycle; all finish."""
    cfg = tiny_dense()
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, "layered", n_slots=2, max_len=64)
    rids = [eng.submit([1 + i, 2, 3, 4], 3) for i in range(7)]
    eng.run()
    for rid in rids:
        assert len(eng.outputs[rid]) == 3
        assert eng.requests[rid].finish_time is not None
