#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip.

Serves Qwen3-30B-A3B at its published widths, with the depth cut to one
TPU v5e (``configs/qwen3_30b_a3b_1chip.py``), through the entry points a
user calls:

  phase 0  JAX's default device must be a TPU (there is no CPU path);
  phase 1  serve.py's engine helpers serve the same closed-loop requests
           under the ``layered`` and then the ``chunked`` scheduler, both
           engines over one copy of the weights;
  phase 2  an in-process HTTP/SSE server answers concurrent
           ``/v1/generate`` streams, then drains.

Every request must finish with exactly its ``max_new_tokens`` in-vocabulary
tokens, nothing may be shed, the KV pool must hold no live page after the
drain, and most served tokens must be the argmax of the plain full
forward's logits for their position (``reference_ranks``).  Any failure
exits non-zero.  On success the last line of the
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
printed timings are a smoke run's, not a benchmark.

Run from the checkout root on a machine with one TPU:

    python chip_smoke.py

JAX's persistent compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR``
or else ``<checkout>/.jax_cache``, so a second run compiles less.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen3-30b-a3b-1chip"
SLOTS = 8
MAX_LEN = 2048
N_REQUESTS = 8
PROMPT_LEN = (768, 1000)       # one power-of-two prefill bucket (1024)
NEW_TOKENS = (16, 32)
N_HTTP = 4
# at least this share of served tokens must be the full-forward reference's
# argmax.  Not every token: in bf16 the two implementations round apart, a
# flipped expert choice moves the logits by whole standard deviations, and a
# served token can then rank in the hundreds (a 4-layer bf16 model on the
# CPU: 83% argmax, worst rank 542 of 4096; all argmax in float32).  Wrong
# weights, lost context or a position off by one scored 0-24% there.
REF_SHARE = 0.5

# compile time, compiles and persistent-cache hits seen by this process
# (filled by JAX's monitoring events once ``watch_compiles`` ran)
COMPILES = {"seconds": 0.0, "n": 0, "cache_hits": 0}
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def watch_compiles() -> None:
    import jax

    def on_duration(event, secs, **_):
        if event in _COMPILE_EVENTS:
            COMPILES["seconds"] += secs
            COMPILES["n"] += event == _COMPILE_EVENTS[-1]

    def on_event(event, **_):
        COMPILES["cache_hits"] += event == "/jax/compilation_cache/cache_hits"

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def check_device() -> dict:
    """Phase 0: the device label, or exit non-zero when JAX's default
    device is not a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"[chip-smoke] FAIL: JAX's default device is "
                         f"{dev.platform} ({dev.device_kind}), not a TPU")
    label = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(jax.devices())}
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    print(f"[chip-smoke] phase 0: {label['kind']} x{label['count']}, "
          f"bytes_limit {limit}", flush=True)
    return label


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"[chip-smoke] FAIL: {what}")


def make_requests(vocab: int, n: int, prompt_len=PROMPT_LEN,
                  new_tokens=NEW_TOKENS, seed: int = 0):
    """``n`` seeded (prompt token ids, max_new_tokens) pairs."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        m = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        out.append((rng.integers(1, vocab, m).tolist(),
                    int(rng.integers(new_tokens[0], new_tokens[1] + 1))))
    return out


def _check_served(eng, rids, requests) -> None:
    """Every request DONE with exactly its max_new_tokens in-vocabulary
    tokens, none shed, and no live KV page left (refcount-0 prefix-cache
    pages count as free)."""
    vocab = eng.cfg.vocab_size
    for rid, (_, n_new) in zip(rids, requests):
        req, toks = eng.requests[rid], eng.outputs[rid]
        _require(req.state.name == "DONE" and req.shed_reason is None,
                 f"request {rid} ended {req.state.name}, shed "
                 f"{req.shed_reason}")
        _require(len(toks) == n_new,
                 f"request {rid}: {len(toks)} tokens, wanted {n_new}")
        _require(all(0 <= t < vocab for t in toks),
                 f"request {rid}: token id outside [0, {vocab})")
    _require(eng.n_swapped_out == eng.n_swapped_in,
             f"{eng.n_swapped_out} swapped out, {eng.n_swapped_in} back in")
    _require(eng.alloc.pages_in_use() == 0,
             f"{eng.alloc.pages_in_use()} KV pages still live")
    eng.alloc.check_invariants()


def serve_closed_loop(sc, requests) -> dict:
    """Phase 1, one scheduler: an engine from serve.py's helper drains
    ``requests`` through the closed-loop ServingRuntime."""
    from repro.launch.serve import build_engine
    gc.collect()                  # the previous engine's KV rows go first
    c0, t0 = COMPILES["seconds"], time.perf_counter()
    eng = build_engine(sc)
    # the engine's attention is always the jnp masked_attention
    gmm = getattr(eng.gmm_fn, "__name__", "ragged_ffn_ref (jnp)")
    print(f"[chip-smoke] {sc.scheduler}: MoE {eng.moe_dispatch} dispatch "
          f"over {gmm}; attention masked_attention (jnp)", flush=True)
    rids = [eng.submit(p, max_new_tokens=n) for p, n in requests]
    eng.run(max_iterations=100_000)   # each iteration ends in a device_get
    wall = time.perf_counter() - t0
    compile_s = COMPILES["seconds"] - c0
    _check_served(eng, rids, requests)
    res = {"outputs": [list(eng.outputs[r]) for r in rids],
           "iterations": eng.iteration,
           "expert_load_mb": eng.expert_load_bytes / 1e6,
           "n_prefill_compiles": eng.n_prefill_compiles,
           "preempted": eng.n_preempted,
           "compile_s": compile_s, "serve_s": wall - compile_s}
    print(f"[chip-smoke] phase 1 {sc.scheduler}: {len(rids)} requests in "
          f"{res['iterations']} iterations; expert-load "
          f"{res['expert_load_mb']:.1f} MB; {res['n_prefill_compiles']} "
          f"prefill executables; {res['preempted']} preemptions; compile "
          f"{compile_s:.1f} s, serving {res['serve_s']:.1f} s", flush=True)
    return res


def reference_ranks(model, params, requests, outputs) -> list:
    """Rank of every served token under the plain full forward
    (``DecoderModel.run_all`` with the dense dropless MoE: none of the
    engine's KV cache, batching, ragged dispatch or scheduling),
    teacher-forced on the served tokens; 0 where the served token is the
    reference's argmax.  One executable: every sequence is padded to the
    longest, which causal attention and per-token routing leave unseen."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    seq_len = max(len(p) + len(o) - 1 for (p, _), o in zip(requests, outputs))
    n_max = max(len(o) for o in outputs)

    @jax.jit
    def ranks(params, seq, at, toks):
        pos = jnp.arange(seq_len, dtype=jnp.int32)[None]
        x = model.embed(params, seq, positions=pos)
        x, _, _ = model.run_all(params, x, positions=pos, dropless=True)
        logits = model.logits(params, x[:, at])[0].astype(jnp.float32)
        picked = jnp.take_along_axis(logits, toks[:, None], axis=1)
        return jnp.sum(logits > picked, axis=1), jnp.isfinite(logits).all()

    out = []
    for (prompt, _), toks in zip(requests, outputs):
        seq = np.zeros((1, seq_len), np.int32)
        seq[0, :len(prompt) + len(toks) - 1] = prompt + toks[:-1]
        at = np.full(n_max, len(prompt) - 1, np.int32)
        at[:len(toks)] += np.arange(len(toks), dtype=np.int32)
        padded = np.zeros(n_max, np.int32)
        padded[:len(toks)] = toks
        r, finite = ranks(params, seq, at, padded)
        _require(bool(finite), "reference logits are not finite")
        out.append(np.asarray(r)[:len(toks)].tolist())
    return out


def agreement(a, b) -> tuple:
    """(requests whose streams are identical, share of tokens before the
    first divergence) between two runs of the same requests."""
    same = sum(x == y for x, y in zip(a, b))
    prefix = 0
    for x, y in zip(a, b):
        k = 0
        while k < min(len(x), len(y)) and x[k] == y[k]:
            k += 1
        prefix += k
    return same, prefix / max(sum(len(x) for x in a), 1)


async def _http(sc, requests):
    from repro.launch.load_gen import _post_generate
    from repro.launch.serve import build_engine
    from repro.serving.server import ServingServer
    server = ServingServer(build_engine(sc), **sc.server_kwargs())
    await server.start()
    try:
        answers = await asyncio.gather(*[
            _post_generate(server.host, server.port,
                           {"prompt_tokens": p, "max_new_tokens": n})
            for p, n in requests])
    finally:
        await server.drain()
    return server, answers


def serve_http(sc, requests) -> list:
    """Phase 2: concurrent SSE streams against an in-process server on an
    OS-assigned port; returns each stream's tokens."""
    sc = dataclasses.replace(sc, http="127.0.0.1:0").validate()
    gc.collect()
    t0 = time.perf_counter()
    server, answers = asyncio.run(_http(sc, requests))
    _require(server._engine_error is None,
             f"engine thread failed: {server._engine_error!r}")
    streams = []
    for i, ((status, _, events), (_, n_new)) in enumerate(
            zip(answers, requests)):
        _require(status == 200, f"stream {i}: HTTP {status}")
        _require(bool(events) and events[-1][0] == "done",
                 f"stream {i} did not end in a done event")
        toks = [d["token"] for k, d in events if k == "token"]
        _require(toks == events[-1][1]["tokens"] and len(toks) == n_new,
                 f"stream {i}: {len(toks)} streamed tokens, wanted {n_new}")
        streams.append(toks)
    _check_served(server.engine, [events[-1][1]["req_id"]
                                  for _, _, events in answers], requests)
    print(f"[chip-smoke] phase 2 http: {len(streams)} SSE streams done in "
          f"{time.perf_counter() - t0:.1f} s; drained", flush=True)
    return streams


def run(sc, requests, n_http: int = N_HTTP) -> dict:
    """Phases 1 and 2 for the configuration ``sc``."""
    import jax
    from repro.launch.serve import model_and_params
    c0, t0 = COMPILES["seconds"], time.perf_counter()
    model, params = model_and_params(sc)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    print(f"[chip-smoke] {model.cfg.name}: {model.cfg.param_count() / 1e9:.2f}"
          f"B params, {model.n_blocks} layers, d_model {model.cfg.d_model}, "
          f"{model.cfg.moe.n_experts} experts top-{model.cfg.moe.top_k}, "
          f"vocab {model.cfg.vocab_size}, {model.cfg.dtype}; init "
          f"{init_s:.1f} s", flush=True)
    runs = {s: serve_closed_loop(dataclasses.replace(sc, scheduler=s),
                                 requests)
            for s in ("layered", "chunked")}
    same, share = agreement(runs["layered"]["outputs"],
                            runs["chunked"]["outputs"])
    print(f"[chip-smoke] layered vs chunked: {same}/{len(requests)} streams "
          f"identical, {share:.3f} of tokens before the first divergence",
          flush=True)
    gc.collect()
    t0 = time.perf_counter()
    ranks = {s: reference_ranks(model, params, requests, r["outputs"])
             for s, r in runs.items()}
    for s, rk in ranks.items():
        flat = sorted(x for row in rk for x in row)
        share = flat.count(0) / len(flat)
        print(f"[chip-smoke] {s} vs full-forward reference: "
              f"{flat.count(0)}/{len(flat)} tokens are its argmax, median "
              f"rank {flat[len(flat) // 2]}, worst {flat[-1]}", flush=True)
        _require(share >= REF_SHARE,
                 f"{s}: only {share:.3f} of served tokens are the "
                 f"reference's argmax")
    print(f"[chip-smoke] reference check {time.perf_counter() - t0:.1f} s",
          flush=True)
    http = serve_http(dataclasses.replace(sc, scheduler="layered"),
                      requests[:n_http])
    same_http, _ = agreement(http, runs["layered"]["outputs"][:n_http])
    print(f"[chip-smoke] http vs offline layered: {same_http}/{n_http} "
          f"streams identical", flush=True)
    setup_s = init_s + sum(r["compile_s"] for r in runs.values())
    serve_s = sum(r["serve_s"] for r in runs.values())
    print(f"[chip-smoke] setup {setup_s:.1f} s (init {init_s:.1f} + "
          f"compile), serving {serve_s:.1f} s; {COMPILES['n']} backend "
          f"compiles, {COMPILES['cache_hits']} persistent-cache hits, "
          f"{COMPILES['seconds'] - c0:.1f} s compiling in all", flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[chip-smoke] device memory peak {stats.get('peak_bytes_in_use')} "
          f"of {stats.get('bytes_limit')} bytes", flush=True)
    return {"runs": runs, "ranks": ranks, "http": http, "setup_s": setup_s,
            "serve_s": serve_s}


def main() -> int:
    label = check_device()
    from repro.launch.config import ServeConfig
    from repro.launch.serve import setup_compile_cache
    print(f"[chip-smoke] compile cache: {setup_compile_cache()}", flush=True)
    watch_compiles()
    sc = ServeConfig(arch=ARCH, slots=SLOTS, max_len=MAX_LEN,
                     requests=N_REQUESTS).validate()
    from repro.configs import get_config
    run(sc, make_requests(get_config(ARCH).vocab_size, N_REQUESTS))
    print(json.dumps({"ok": True, "device": label}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
