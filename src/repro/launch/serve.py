"""Production serving launcher: the real-execution engine on the model
``--arch`` names (``--smoke`` swaps in its tiny same-family variant for CPU
runs) or the discrete-event simulator at full model scale.  Both run the SAME
ServingRuntime loop (serving/runtime.py): closed-loop drain by default,
open-loop timed-trace replay with ``--open-loop`` (engine) or
``--simulate`` (always open-loop), optional per-token streaming via
``--stream``, multi-tenant class mixes via ``--batch-fraction`` — and,
with ``--http``, a live asyncio HTTP/SSE front-end (serving/server.py)
ingesting POST /v1/generate concurrently with the engine loop.

Every flag lives on ``ServeConfig`` (launch/config.py) — the same typed
configuration the benchmarks and the load generator consume, with
``to_json``/``from_json`` round-trips for recording exactly what ran.

Usage:
  # real engine, Qwen3-30B-A3B at published widths cut to one v5e chip:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-30b-a3b-1chip \
      --scheduler layered --requests 8 --slots 8 --max-len 2048

  # real engine, reduced model on the CPU, layered prefill, closed loop:
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
      --arch qwen3-moe-235b-a22b --smoke --scheduler layered --requests 8

  # real engine, open-loop Poisson replay with streamed tokens:
  PYTHONPATH=src python -m repro.launch.serve --smoke --open-loop \
      --rate 0.5 --requests 8 --stream

  # live HTTP/SSE service on port 8000 (per-tenant rate limit 4 req/s,
  # 429 backpressure past the queue/pool watermarks, /metrics scrape):
  PYTHONPATH=src python -m repro.launch.serve --smoke --http :8000 \
      --ratelimit-rate 4
  curl -N localhost:8000/v1/generate \
      -d '{"prompt_tokens": [1,2,3], "max_new_tokens": 8}'

  # full-scale simulation of the paper's serving scenario, 30% batch-class
  # bursty background traffic, 64 pages held back for interactive:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-30b-a3b \
      --simulate --dataset arxiv --rate 1.3 --requests 100 \
      --batch-fraction 0.3 --arrival bursty --class-headroom 64
"""

from __future__ import annotations

import argparse
import dataclasses
import math
import os
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.base import make_scheduler
from repro.launch.config import ServeConfig
from repro.models.model import DecoderModel
from repro.serving.cost_model import H100X2, TPU_V5E
from repro.serving.engine import Engine, EngineHandoff
from repro.serving.faults import FaultInjector, FaultPlan
from repro.serving.metrics import per_class_metrics, request_metrics
from repro.serving.runtime import (DisaggRuntime, EngineExecutor,
                                   ServingRuntime)
from repro.serving.server import ServingServer
from repro.serving.simulator import DisaggSimulator, Simulator
from repro.serving.traffic import (ARRIVAL_PROCESSES, DATASETS, ClassSpec,
                                   multi_class_trace)


def _f(v, spec: str = ".2f") -> str:
    """NaN/None-safe number formatting for the per-class report lines:
    a class with zero completed requests has NaN percentiles, and "-" is
    the honest column value (format() would happily print "nan")."""
    if v is None or (isinstance(v, float) and math.isnan(v)):
        return "-"
    return format(v, spec)


def _print_per_class(tag, requests, slo=None) -> None:
    per = per_class_metrics(requests, slo)
    if len(per) < 2:
        return
    for cls, m in per.items():
        att = f" slo={_f(m['slo_attainment'])}" if "slo_attainment" in m \
            else ""
        print(f"[{tag}]   class {cls:<12} n={m['n_requests']:.0f} "
              f"ttft mean={_f(m['ttft_mean'])} p99={_f(m['ttft_p99'])}; "
              f"preempt rate {_f(m['preemption_rate'])}/req; "
              f"swap rate {_f(m['swap_rate'])}/req{att}")


def build_faults(sc: ServeConfig) -> FaultInjector | None:
    """Chaos mode: a deterministic ``FaultInjector`` from ``--fault-plan``
    ('@file.json' | 'seed:N' | inline JSON), or None when off."""
    if sc.fault_plan is None:
        return None
    return FaultInjector(FaultPlan.load(sc.fault_plan))


def _print_faults(tag: str, fi: FaultInjector | None, requests) -> None:
    """Chaos-run report line: what was injected and what got shed (a
    DONE request with ``shed_reason`` set never completed)."""
    if fi is None:
        return
    shed: dict = {}
    for r in requests:
        if r.shed_reason is not None:
            shed[r.shed_reason] = shed.get(r.shed_reason, 0) + 1
    inj = ", ".join(f"{k[2:]}={v}" for k, v in sorted(fi.counters.items())
                    if v)
    sheds = ", ".join(f"{k}={v}" for k, v in sorted(shed.items()))
    print(f"[{tag}] chaos: {sum(fi.counters.values())} faults injected"
          + (f" ({inj})" if inj else "")
          + (f"; shed {sheds}" if sheds else "; no requests shed"))


# the checkout root (src/repro/launch/serve.py -> three levels up)
_CHECKOUT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is what JAX already uses and
    nothing else is set here; otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache`` (the path is part of every entry's key, so a
    directory that moved between runs would never hit).  Entry points call
    this; importing the module never does."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


_WEIGHTS: dict = {}


def model_and_params(sc: ServeConfig):
    """The served model and its weights, initialised once per process by
    one jitted ``model.init`` (seeded, so every engine of a process — and
    of any other process — holds the same weights).  Every real-execution
    path builds on this, so two engines of one model share one copy of the
    weights, and only the most recent model's weights are held.  Raises
    ``MemoryError`` with the byte counts when the weights and the KV slot
    rows alone exceed the device's memory."""
    cfg = get_smoke_config(sc.arch) if sc.smoke else get_config(sc.arch)
    if cfg not in _WEIGHTS:
        _WEIGHTS.clear()
        model = DecoderModel(cfg)
        init = jax.jit(model.init)
        key = jax.random.PRNGKey(0)
        need = (sum(a.size * a.dtype.itemsize for a in
                    jax.tree_util.tree_leaves(jax.eval_shape(init, key)))
                + cfg.kv_bytes_per_token() * sc.slots * sc.max_len)
        dev = jax.devices()[0]
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        if limit is not None and need > limit:
            raise MemoryError(
                f"{cfg.name}: weights plus {sc.slots} x {sc.max_len} KV "
                f"rows need {need} bytes; the {dev.device_kind} holds "
                f"{limit}")
        _WEIGHTS[cfg] = (model, init(key))
    return _WEIGHTS[cfg]


def build_engine(sc: ServeConfig) -> Engine:
    """The one engine constructor every real-execution mode shares
    (closed loop, open-loop replay, HTTP service, load_gen verification)."""
    model, params = model_and_params(sc)
    sched = make_scheduler(sc.scheduler, model.n_blocks,
                           **sc.scheduler_kwargs())
    return Engine(model, params, sched, **sc.engine_kwargs())


def serve_http(sc: ServeConfig) -> None:
    """Live HTTP/SSE service: the engine iteration loop runs on a
    background thread in wall-clock mode while asyncio ingests requests
    concurrently (serving/server.py)."""
    eng = build_engine(sc)
    server = ServingServer(eng, faults=build_faults(sc),
                           **sc.server_kwargs())
    server.serve_forever()


def build_disagg_engines(sc: ServeConfig):
    """(prefill, decode) Engine pair sharing one model + params: the
    prefill pool runs the selected scheduler, the decode pool the
    internal decode-only scheduler (residents arrive via ``adopt``)."""
    model, params = model_and_params(sc)
    sp = make_scheduler(sc.scheduler, model.n_blocks,
                        **sc.scheduler_kwargs())
    sd = make_scheduler("decode", model.n_blocks, **sc.scheduler_kwargs())
    ekw = sc.engine_kwargs()
    dkw = dict(ekw, pages=sc.decode_pages if sc.decode_pages is not None
               else ekw["pages"])
    return (Engine(model, params, sp, **ekw),
            Engine(model, params, sd, **dkw))


def serve_disagg_real(sc: ServeConfig) -> None:
    """Two-pool real execution: prefill and decode engines under one
    DisaggRuntime clock, KV handed off through ``EngineHandoff``."""
    ep, ed = build_disagg_engines(sc)
    cfg = ep.cfg

    def _stream(rid, tok, t):
        print(f"[stream] t={t:8.2f} req={rid:<4} tok={tok}")
    bridge = EngineHandoff(ep, ed, streaming=sc.handoff == "stream")
    faults = build_faults(sc)
    runtime = DisaggRuntime(
        EngineExecutor(ep), EngineExecutor(ed), bridge,
        on_token=_stream if sc.stream else None, clock="iteration",
        decode_watermark_pages=sc.decode_watermark,
        faults=faults, retry_budget=sc.retry_budget)
    if sc.open_loop:
        trace = sc.engine_trace(cfg.vocab_size)
    else:
        trace = ()
        rng = np.random.default_rng(sc.seed)
        for _ in range(sc.requests):
            n = int(rng.integers(16, sc.max_len // 2))
            enc = None
            if cfg.encoder.enabled:
                enc = np.zeros((cfg.encoder.n_frames, cfg.d_model),
                               np.float32)
            cls = "batch" if rng.random() < sc.batch_fraction \
                else "interactive"
            ep.submit(rng.integers(1, cfg.vocab_size, n).tolist(),
                      max_new_tokens=int(rng.integers(4, 16)),
                      enc_frames=enc, slo_class=cls)
    rr = runtime.run(trace, max_iterations=100_000)
    reqs = list(ep.requests.values()) + list(ed.requests.values())
    m = request_metrics(reqs)
    loop = "open-loop" if sc.open_loop else "closed-loop"
    print(f"[serve-disagg] {cfg.name} x {sc.scheduler}+decode ({loop}, "
          f"{sc.handoff} handoff): {sc.requests} requests in "
          f"{rr.n_prefill_iterations} prefill + "
          f"{rr.n_decode_iterations} decode iterations")
    print(f"[serve-disagg] ttft(iters) mean={_f(m['ttft_mean'], '.1f')} "
          f"p99={_f(m['ttft_p99'], '.1f')}; "
          f"{rr.n_migrations} migrations ({rr.n_returns} returns), "
          f"{rr.handoff_bytes / 1e6:.1f} MB handed off, "
          f"queue peak {rr.migration_queue_peak}")
    print(f"[serve-disagg] handoff chunks/req "
          f"{_f(m['handoff_chunks_mean'], '.1f')}; link ratio "
          f"{_f(m['handoff_link_ratio'])}; decode-pool prefill slices "
          f"{rr.decode_prefill_slices} (must stay 0)")
    print(f"[serve-disagg] kv high-water prefill "
          f"{ep.alloc.pages_high_water}/{ep.alloc.n_pages}, decode "
          f"{ed.alloc.pages_high_water}/{ed.alloc.n_pages}; "
          f"preemptions {ep.n_preempted}+{ed.n_preempted}")
    _print_faults("serve-disagg", faults, reqs)
    _print_per_class("serve-disagg", reqs)


def serve_real(sc: ServeConfig) -> None:
    eng = build_engine(sc)
    cfg = eng.cfg

    def _stream(rid, tok, t):
        print(f"[stream] t={t:8.2f} req={rid:<4} tok={tok}")
    on_token = _stream if sc.stream else None
    faults = build_faults(sc)
    if sc.open_loop:
        # open-loop timed replay through the shared runtime: requests are
        # injected at their arrival times, the engine idles through gaps
        trace = sc.engine_trace(cfg.vocab_size)
        wall = sc.clock == "wall"
        runtime = ServingRuntime(
            EngineExecutor(eng, wall=wall), on_token=on_token,
            clock="executor" if wall else "iteration",
            faults=faults, retry_budget=sc.retry_budget)
        runtime.run(trace, max_iterations=100_000)
        unit = "s" if wall else "iters"
    else:
        rng = np.random.default_rng(sc.seed)
        for _ in range(sc.requests):
            n = int(rng.integers(16, sc.max_len // 2))
            enc = None
            if cfg.encoder.enabled:
                enc = np.zeros((cfg.encoder.n_frames, cfg.d_model),
                               np.float32)
            cls = "batch" if rng.random() < sc.batch_fraction \
                else "interactive"
            eng.submit(rng.integers(1, cfg.vocab_size, n).tolist(),
                       max_new_tokens=int(rng.integers(4, 16)),
                       enc_frames=enc, slo_class=cls)
        runtime = ServingRuntime(EngineExecutor(eng), on_token=on_token,
                                 clock="iteration",
                                 faults=faults,
                                 retry_budget=sc.retry_budget)
        runtime.run((), max_iterations=100_000)
        unit = "iters"
    m = request_metrics(eng.requests.values())
    loop = "open-loop" if sc.open_loop else "closed-loop"
    print(f"[serve] {cfg.name} x {sc.scheduler} ({loop}): "
          f"{sc.requests} requests in {eng.iteration} iterations")
    print(f"[serve] ttft({unit}) mean={_f(m['ttft_mean'], '.1f')} "
          f"p99={_f(m['ttft_p99'], '.1f')}; expert-load "
          f"{eng.expert_load_bytes / 1e6:.1f} MB")
    print(f"[serve] kv pages high-water {eng.alloc.pages_high_water}"
          f"/{eng.alloc.n_pages}; queue delay mean "
          f"{_f(m['queue_delay_mean'], '.1f')} {unit}; "
          f"preemptions {eng.n_preempted} "
          f"(rate {_f(m['preemption_rate'])}/req)")
    print(f"[serve] hot path: {'packed' if sc.packed else 'per-slice'}; "
          f"{eng.n_dispatches} device launches "
          f"({eng.n_dispatches / max(eng.iteration, 1):.1f}/iter), "
          f"{eng.n_prefill_dispatches} prefill batches, "
          f"{eng.n_prefill_compiles} prefill executables")
    if sc.spec != "off":
        acc = m["spec_acceptance_rate"]
        tpd = (sum(r.n_generated for r in eng.requests.values())
               / max(eng.n_dispatches, 1))
        print(f"[serve] spec({sc.spec}, k={sc.spec_k}): "
              f"{eng.n_spec_proposed} drafted, {eng.n_spec_accepted} "
              f"accepted (rate {_f(acc)}); accepted len "
              f"p50={_f(m['accepted_len_p50'], '.1f')} "
              f"p90={_f(m['accepted_len_p90'], '.1f')}; "
              f"{eng.n_verify_dispatches} verify + "
              f"{eng.n_draft_dispatches} draft dispatches, "
              f"{eng.n_verify_compiles} verify executables; "
              f"{tpd:.2f} generated tokens/dispatch")
    if sc.prefix_cache:
        print(f"[serve] prefix cache: hit rate "
              f"{m['prefix_hit_rate']:.2f} "
              f"({eng.alloc.n_prefix_hits} hits, "
              f"{eng.alloc.n_prefix_tokens} cached tokens, "
              f"{eng.n_prefix_restores} row restores); "
              f"{eng.alloc.n_shared_pages} shared pages live, "
              f"{eng.alloc.n_prefix_evictions} LRU reclaims")
    if eng.alloc.n_host_pages:
        print(f"[serve] swap: {eng.n_swapped_out} out / "
              f"{eng.n_swapped_in} in; host pages high-water "
              f"{eng.alloc.host_pages_high_water}/{eng.alloc.n_host_pages};"
              f" restore latency mean "
              f"{_f(m['restore_latency_mean'], '.1f')} {unit}")
    _print_faults("serve", faults, eng.requests.values())
    _print_per_class("serve", eng.requests.values())


def serve_sim(sc: ServeConfig) -> None:
    cfg = get_config(sc.arch)
    hw = H100X2 if sc.hw == "h100x2" else TPU_V5E
    if sc.host_bw is not None:
        hw = dataclasses.replace(hw, host_bw=sc.host_bw * 1e9)
    if sc.batch_fraction > 0:
        # multi-tenant mix: interactive foreground on the chosen dataset,
        # batch-class arXiv background on the selected arrival process
        n_batch = int(round(sc.requests * sc.batch_fraction))
        trace = multi_class_trace([
            ClassSpec("interactive", DATASETS[sc.dataset],
                      sc.rate * (1 - sc.batch_fraction),
                      sc.requests - n_batch),
            ClassSpec("batch", DATASETS["arxiv"],
                      sc.rate * sc.batch_fraction, n_batch,
                      process=sc.arrival),
        ], seed=sc.seed)
    else:
        trace = ARRIVAL_PROCESSES[sc.arrival](
            DATASETS[sc.dataset], sc.rate, sc.requests, seed=sc.seed)
    if sc.disagg:
        _serve_sim_disagg(sc, cfg, hw, trace)
        return
    sim = Simulator(cfg, sc.scheduler, hw, **sc.sim_kwargs())
    faults = build_faults(sc)
    res = sim.run(trace, faults=faults, retry_budget=sc.retry_budget)
    slo = sc.slo()
    m = request_metrics(res.requests, slo)
    print(f"[serve-sim] {cfg.name} x {sc.scheduler} on {sc.dataset} "
          f"@{sc.rate} req/s ({hw.name}; "
          f"{sim.kv.n_pages} x {sim.kv.page_size}-token pages)")
    for k in ("ttft_mean", "ttft_p99", "tbt_mean", "tbt_p99",
              "slo_attainment", "e2e_mean", "queue_delay_mean",
              "queue_delay_p99", "preemption_rate"):
        print(f"[serve-sim]   {k:<16} {_f(m[k], '.3f')}")
    print(f"[serve-sim]   energy/token     "
          f"{res.energy_per_token * 1e3:.1f} mJ")
    print(f"[serve-sim]   expert traffic   "
          f"{res.total_expert_bytes / 1e12:.2f} TB")
    print(f"[serve-sim]   kv pages         "
          f"high-water {res.pages_high_water}/{res.n_pool_pages}; "
          f"{res.n_preemptions} preemptions, "
          f"{res.recompute_tokens} recomputed tokens")
    if sc.prefix_cache:
        print(f"[serve-sim]   prefix cache     "
              f"hit rate {res.prefix_hit_rate:.2f} "
              f"({res.n_prefix_hits} hits, "
              f"{res.prefix_cached_tokens} cached tokens)")
    if sc.spec != "off":
        print(f"[serve-sim]   spec({sc.spec})      "
              f"{res.total_drafted} drafted / {res.total_accepted} "
              f"accepted (rate {_f(res.acceptance_rate)}); accepted len "
              f"p50={_f(m['accepted_len_p50'], '.1f')} "
              f"p90={_f(m['accepted_len_p90'], '.1f')}")
    if res.n_host_pages:
        print(f"[serve-sim]   swap             "
              f"{res.n_swap_outs} out / {res.n_swap_ins} in; "
              f"{res.swap_bytes / 1e9:.2f} GB over host link, "
              f"{res.swap_dma_time:.3f} s DMA ({res.swap_stall_time:.3f} s"
              f" unhidden stall); host pages "
              f"high-water {res.host_pages_high_water}/{res.n_host_pages};"
              f" restore latency mean "
              f"{_f(m['restore_latency_mean'], '.3f')} s")
    _print_faults("serve-sim", faults, res.requests)
    _print_per_class("serve-sim", res.requests, slo)


def _serve_sim_disagg(sc: ServeConfig, cfg, hw, trace) -> None:
    """Two-pool analytic serving report: per-pool rollups plus the link
    accounting the monolithic report has no column for."""
    sim = DisaggSimulator(cfg, sc.scheduler, hw, handoff=sc.handoff,
                          decode_pages=sc.decode_pages,
                          decode_watermark=sc.decode_watermark,
                          **sc.sim_kwargs())
    faults = build_faults(sc)
    res = sim.run(trace, faults=faults, retry_budget=sc.retry_budget)
    slo = sc.slo()
    m = request_metrics(res.requests, slo)
    print(f"[serve-sim] {cfg.name} x {sc.scheduler}+decode on "
          f"{sc.dataset} @{sc.rate} req/s ({hw.name}; {sc.handoff} "
          f"handoff; decode pool "
          f"{sim.decode.kv.n_pages} x {sim.decode.kv.page_size}-token "
          f"pages)")
    for k in ("ttft_mean", "ttft_p99", "tbt_mean", "tbt_p99",
              "slo_attainment", "e2e_mean", "queue_delay_mean",
              "preemption_rate"):
        print(f"[serve-sim]   {k:<16} {_f(m[k], '.3f')}")
    n_tok = sum(r.n_generated for r in res.requests) or 1
    print(f"[serve-sim]   energy/token     "
          f"{res.total_energy / n_tok * 1e3:.1f} mJ "
          f"(link {res.link_energy * 1e3:.1f} mJ total)")
    print(f"[serve-sim]   handoff          "
          f"{res.n_migrations} migrations ({res.n_returns} returns); "
          f"{res.link_bytes / 1e9:.2f} GB over link, "
          f"{res.link_stall_time:.4f} s unhidden stall, "
          f"{res.handoff_wait_time:.4f} s watermark wait; "
          f"queue peak {res.migration_queue_peak}")
    print(f"[serve-sim]   decode pool      "
          f"tbt mean {_f(res.decode_pool_tbt_mean, '.4f')} s over "
          f"{res.decode.n_iterations} iterations; prefill slices "
          f"{res.decode_prefill_slices} (must stay 0)")
    print(f"[serve-sim]   kv pages         "
          f"prefill high-water "
          f"{res.prefill.pages_high_water}/{res.prefill.n_pool_pages}, "
          f"decode {res.decode.pages_high_water}"
          f"/{res.decode.n_pool_pages}; "
          f"{res.prefill.n_preemptions + res.decode.n_preemptions} "
          f"preemptions")
    _print_faults("serve-sim", faults, res.requests)
    _print_per_class("serve-sim", res.requests, slo)


def main() -> None:
    ap = argparse.ArgumentParser()
    ServeConfig.add_arguments(ap)
    sc = ServeConfig.from_args(ap.parse_args())
    if sc.simulate:
        serve_sim(sc)
        return
    setup_compile_cache()
    if sc.http is not None:
        serve_http(sc)
    elif sc.disagg:
        serve_disagg_real(sc)
    else:
        serve_real(sc)


if __name__ == "__main__":
    main()
