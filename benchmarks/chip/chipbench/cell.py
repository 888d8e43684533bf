"""Drives the serving program for one cell: builds the configuration's
model and engine with the program's own constructors, warms up every
shape the cell's traffic can use, runs the measured window through
``ServingRuntime.run`` over ``EngineExecutor(engine, wall=True)``, and
collects the run record that the metric files read."""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench import flops as F
from chipbench import spec as S
from chipbench import traffic as T
from chipbench import weights as W

class CompileCounter:
    """Counts this process's compile events from JAX's monitoring hooks:
    jaxpr traces, backend compiles and persistent-cache hits."""

    def __init__(self):
        import jax
        self.n = {"traces": 0, "backend": 0, "cache_hits": 0}

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/jaxpr_trace_duration":
                self.n["traces"] += 1
            elif event == "/jax/core/compile/backend_compile_duration":
                self.n["backend"] += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.n["cache_hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def since(self, before: Dict[str, int]) -> Dict[str, int]:
        return {k: v - before[k] for k, v in self.n.items()}


class NoChip(RuntimeError):
    pass


def check_device(chips: int) -> dict:
    """The device label; raises ``NoChip`` when JAX's devices are not
    TPUs, are fewer than the cell asks for, or are of a kind that
    ``peaks.json`` does not list."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX's default device is {d.platform} "
                     f"({d.device_kind}), not a TPU")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} devices, the cell needs {chips}")
    try:
        S.peaks(d.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# published key -> how to read the same value off the program's ModelConfig
_SHAPES = {
    "hidden_size": lambda m: m.d_model,
    "num_hidden_layers": lambda m: m.n_layers,
    "num_attention_heads": lambda m: m.n_heads,
    "num_key_value_heads": lambda m: m.n_kv_heads,
    "head_dim": lambda m: m.head_dim_,
    "vocab_size": lambda m: m.vocab_size,
    "tie_word_embeddings": lambda m: m.tie_embeddings,
    "rope_theta": lambda m: m.rope_theta,
    "rms_norm_eps": lambda m: m.norm_eps,
    "torch_dtype": lambda m: m.param_dtype,
    "partial_rotary_factor": lambda m: (
        m.rotary_pct if m.pos_emb == "rope_partial" else 1.0),
    "num_experts": lambda m: m.moe.n_experts or None,
    "num_experts_per_tok": lambda m: m.moe.top_k or None,
    "moe_intermediate_size": lambda m: m.moe.expert_d_ff or None,
}


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry with the file's overrides, checked against every published
    shape the file states."""
    from repro.configs import get_config
    prog = conf["program"]
    m = dataclasses.replace(get_config(prog["arch"]),
                            **prog.get("overrides", {})).validate()
    bad = []
    for key, read in _SHAPES.items():
        want = conf.get(key, 1.0 if key == "partial_rotary_factor" else None)
        if read(m) != want:
            bad.append(f"{key}: file {want!r}, program {read(m)!r}")
    if not m.moe.enabled and m.d_ff != conf["intermediate_size"]:
        bad.append(f"intermediate_size: file {conf['intermediate_size']}, "
                   f"program {m.d_ff}")
    if m.dtype != conf["torch_dtype"] or m.norm != "rmsnorm" \
            or m.activation != "swiglu":
        bad.append(f"dtype/norm/activation: {m.dtype}/{m.norm}/"
                   f"{m.activation}")
    if bad:
        raise ValueError("program config departs from the file: "
                         + "; ".join(bad))
    return m


def build(conf: dict, seed: int):
    """(engine, ServeConfig): the program's model over weights made from
    ``seed``, its scheduler and its ``Engine`` built as
    ``serve.build_engine`` builds them from the ``ServeConfig``."""
    import jax
    import jax.numpy as jnp
    from repro.core.base import make_scheduler
    from repro.launch.config import ServeConfig
    from repro.models.model import DecoderModel
    from repro.serving.engine import Engine
    model = DecoderModel(program_config(conf))
    lay = W.layout(conf)
    got = W.tree_layout(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    want = {k: shape for k, (shape, _) in lay.items()}
    if got != want:
        raise ValueError(f"program parameter layout {got} is not {want}")
    params = W.make(lay, seed, jnp.dtype(conf["torch_dtype"]))
    sc = ServeConfig(arch=conf["program"]["arch"], **conf["serve"]).validate()
    sched = make_scheduler(sc.scheduler, model.n_blocks,
                           **sc.scheduler_kwargs())
    return Engine(model, params, sched, **sc.engine_kwargs()), sc


class _Replay:
    """A host-only executor for ``ServingRuntime``: no model runs, an
    iteration takes ``decode_s``, or ``prefill_s`` when it carries a
    prefill, and the prompt lengths of every set of two or more requests
    admitted in one iteration are recorded."""

    def __init__(self, sched, decode_s: float, prefill_s: float):
        self.scheduler = sched
        self.decode_s, self.prefill_s = decode_s, prefill_s
        self.together: List[Tuple[int, ...]] = []
        self._next_id = 0

    def submit(self, spec, now):
        from repro.core.plan import Request
        req = Request.from_spec(spec, self._next_id,
                                arrival_time=spec.arrival_time,
                                prompt_tokens=np.asarray(
                                    spec.prompt_tokens, np.int32))
        self._next_id += 1
        self.scheduler.submit(req)
        return req

    def execute(self, plan, now):
        from repro.serving.runtime import StepOutcome, TokenEvent
        if len(plan.admitted_ids) > 1:
            self.together.append(tuple(sorted(
                self.scheduler.requests[i].prompt_len
                for i in plan.admitted_ids)))
        events = [TokenEvent(sl.req_id, None, first=True)
                  for sl in plan.prefill if sl.emits_first_token]
        events += [TokenEvent(rid, None) for rid in plan.decode_ids]
        return StepOutcome(duration=self.prefill_s if plan.prefill
                           else self.decode_s, events=events)

    def idle(self, t, until):
        return until

    def poll_clock(self, t):
        return t

    def initial_clock(self):
        return 0.0

    def evict(self, req_id):
        pass

    def release(self, req_id):
        pass


# how much faster or slower than the configuration's step times the
# window's iterations may run, for the cohorts the warm-up serves
REPLAY_SCALES = (0.25, 0.5, 0.75, 1.0, 1.25, 1.5, 2.0)


def cohorts(eng, sc, conf: dict, mix: dict,
            seconds: float) -> List[Tuple[int, ...]]:
    """The prompt lengths of each set of two or more requests that the
    cell's scheduler admits together on the window's schedule (a merged
    cohort of the layered scheduler), in the order they first occur.  The
    schedule is replayed on the host through a scheduler built as
    ``build`` builds the engine's, with iterations that take the
    configuration's ``step_s`` (decode, and one carrying a prefill, as
    measured on the chip) times each of ``REPLAY_SCALES``: which requests
    merge turns on tens of milliseconds, so a window that runs a little
    faster or slower than the last measurement, or a program that has
    become faster, still finds its cohorts warm."""
    from repro.core.base import make_scheduler
    from repro.serving.runtime import ServingRuntime
    trace = [_spec(r, r.arrival_s) for r in T.open_loop(
        mix, seconds, 0, sc.max_len, eng.cfg.vocab_size)]
    step = conf["step_s"]
    seen: Dict[Tuple[int, ...], None] = {}
    for scale in REPLAY_SCALES:
        sched = make_scheduler(sc.scheduler, eng.model.n_blocks,
                               **sc.scheduler_kwargs())
        x = _Replay(sched, step["decode"] * scale,
                    step["prefill"] * scale)
        ServingRuntime(x, clock="executor").run(trace,
                                                max_iterations=10 ** 7)
        seen.update(dict.fromkeys(x.together))
    return list(seen)


def warm_up(eng, sc, conf: dict, mix: dict, seconds: float) -> dict:
    """Serve one prompt at each edge of the padding buckets and layer
    group counts that the mix's prompt lengths span, one at a time, so
    that each is a cohort of its own (each with two output tokens, so the
    decode step compiles too).  That compiles every prefill shape a lone
    request can use.  Then serve, all at once, the prompts of each set of
    requests that the window's schedule admits together (``cohorts``),
    so that the wider shapes of a merged cohort are compiled too.  The
    prompts' token ids come from a fixed seed: every run does the same
    warm-up.  Returns what was served and how long each part took."""
    ins, _ = T.length_models(mix, sc.max_len)
    rng = np.random.default_rng(20251008)

    def serve(lengths):
        for n in lengths:
            eng.submit(rng.integers(1, eng.cfg.vocab_size, n).tolist(),
                       max_new_tokens=2)
        eng.run(max_iterations=1_000_000)

    t0 = time.monotonic()
    lens = T.edge_lengths(ins.lo, ins.hi, sc.quantum, eng.model.n_blocks)
    for n in lens:
        serve([n])
    t1 = time.monotonic()
    together = cohorts(eng, sc, conf, mix, seconds)
    t2 = time.monotonic()
    for c in together:
        serve(c)
    return {"lone": len(lens), "lone_s": t1 - t0, "cohorts": together,
            "replay_s": t2 - t1, "cohorts_s": time.monotonic() - t2}


class WindowClosed(Exception):
    pass


class TimedExecutor:
    """The executor the window's runtime drives: ``EngineExecutor`` with
    the benchmark's host spans around each call into the engine, a record
    of every step and injection, and the window's end (the first call
    past it raises ``WindowClosed``)."""

    def __init__(self, inner, end_s: float, conf: dict, tracing: bool):
        self.inner = inner
        self.engine = inner.engine
        self.scheduler = inner.scheduler
        self.end = end_s
        self.conf = conf
        self.steps: List[tuple] = []     # (t0, t1, model flops)
        self.injects: List[tuple] = []   # (req id, due, injected at)
        self.span = _annotation if tracing else _no_span
        self._t0 = time.monotonic()

    def now(self) -> float:
        return time.monotonic() - self._t0

    def initial_clock(self) -> float:
        t = self.inner.initial_clock()       # the window opens here
        self._t0 = time.monotonic()
        return t

    def submit(self, spec, now):
        with self.span("bench.inject"):
            req = self.inner.submit(spec, now)
        self.injects.append((req.req_id, req.arrival_time, self.now()))
        return req

    def _flops(self, plan) -> float:
        c, eng = self.conf, self.engine
        f = sum(F.prefill_slice(c, sl.token_start, sl.token_end,
                                sl.block_end - sl.block_start,
                                sl.emits_first_token)
                for sl in plan.prefill)
        for rid in plan.decode_ids:
            r = eng.requests[rid]
            f += F.decode_token(c, r.prompt_len + len(eng.outputs[rid]) - 1)
        return f

    def plan(self, next_plan, *a, **k):
        """The scheduler's ``next_plan``; past the window's end it raises
        ``WindowClosed`` before planning, so that no plan the scheduler
        has booked goes unexecuted."""
        if self.now() >= self.end:
            raise WindowClosed
        with self.span("bench.plan"):
            return next_plan(*a, **k)

    def execute(self, plan, now):
        t0 = self.now()
        f = self._flops(plan)
        with self.span("bench.engine_step"):
            out = self.inner.execute(plan, now)
        self.steps.append((t0, self.now(), f))
        return out

    def idle(self, t, until):
        with self.span("bench.wait_arrival"):
            if until >= self.end:
                time.sleep(max(0.0, self.end - self.now()))
                raise WindowClosed
            return self.inner.idle(t, until)

    def poll_clock(self, t):
        v = self.inner.poll_clock(t)
        if v >= self.end:
            raise WindowClosed
        return v

    def evict(self, req_id):
        self.inner.evict(req_id)

    def release(self, req_id):
        self.inner.release(req_id)


def _annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _no_span(name: str):
    return contextlib.nullcontext()


def _spec(r: T.Request, arrival: Optional[float]):
    from repro.core.plan import SubmitSpec
    return SubmitSpec(max_new_tokens=r.max_new, prompt_tokens=r.prompt,
                      arrival_time=arrival)


def run_window(eng, sc, conf: dict, mix: dict, seed: int, seconds: float,
               tracing: bool = False,
               on_open: Optional[Callable[[], None]] = None,
               compiles: Optional[CompileCounter] = None) -> dict:
    """One measured window.  Returns the run record: every request that
    was due in the window with its times and tokens, every engine step,
    the engine's expert-load bytes over the window and the compiles that
    happened inside it."""
    from repro.serving.runtime import EngineExecutor, ServingRuntime
    x = TimedExecutor(EngineExecutor(eng, wall=True), seconds, conf,
                      tracing)
    trace = [_spec(r, r.arrival_s) for r in T.open_loop(
        mix, seconds, seed, sc.max_len, eng.cfg.vocab_size)]
    eng.scheduler.next_plan = functools.partial(x.plan,
                                                eng.scheduler.next_plan)
    runtime = ServingRuntime(x, clock="executor")
    expert0 = eng.expert_load_bytes
    compiles0 = dict(compiles.n) if compiles else None
    if on_open is not None:
        on_open()
    try:
        with x.span("bench.window"):
            try:
                runtime.run(trace, max_iterations=10 ** 9)
                time.sleep(max(0.0, seconds - x.now()))
            except WindowClosed:
                pass
    finally:
        del eng.scheduler.next_plan
    reqs = []
    for rid, due, at in x.injects:
        r = eng.requests[rid]
        reqs.append({
            "id": rid, "due": due, "injected": at,
            "admit": r.admit_time, "first": r.first_token_time,
            "token_times": list(r.token_times),
            # finished: its last iteration ran (a plan books DONE before
            # the engine executes it, and stamps the finish after)
            "done": r.state.name == "DONE" and r.finish_time is not None,
            "shed": r.shed_reason, "prompt_len": len(eng.prompts[rid]),
            "max_new": r.max_new_tokens, "n_out": len(eng.outputs[rid])})
    return {"window_s": float(seconds),
            "slo": mix["slo"], "requests": reqs, "steps": x.steps,
            "expert_load_bytes": eng.expert_load_bytes - expert0,
            "moe": bool(conf.get("num_experts")),
            "compiles": compiles.since(compiles0) if compiles else None}


def drain(eng) -> None:
    """Finish whatever the window left in flight (no clock is read)."""
    eng.run(max_iterations=1_000_000)


def finish_late(eng, rec: dict, want: int, limit_s: float) -> float:
    """Serve on past the window's close, with no new arrivals, until
    ``want`` of the window's requests have finished or ``limit_s`` has
    passed, and mark those that finished in the run record (a late answer
    is late, not missing: the check judges it by what it says; the
    metrics, taken before, count its wait).  Returns the seconds spent."""
    def finished():
        return [r for r in rec["requests"] if _finished(eng, r["id"])]

    t0 = time.monotonic()
    while len(finished()) < want and eng.scheduler.has_work() \
            and time.monotonic() - t0 < limit_s:
        eng.step()
    for r in finished():
        r.update(done=True, n_out=len(eng.outputs[r["id"]]))
    return time.monotonic() - t0


def _finished(eng, rid: int) -> bool:
    r = eng.requests[rid]
    # its last iteration ran (a plan books DONE before the engine
    # executes it, and stamps the finish after)
    return r.state.name == "DONE" and r.finish_time is not None \
        and r.shed_reason is None


def sample_finished(eng, rec: dict, seed: int, k: int) -> List[int]:
    """Up to ``k`` requests that finished in the window: the longest
    (prompt plus output), and the rest drawn from the seed."""
    done = [r for r in rec["requests"] if r["done"] and r["shed"] is None]
    if not done:
        return []
    done.sort(key=lambda r: (-(r["prompt_len"] + r["n_out"]), r["id"]))
    rest = [r["id"] for r in done[1:]]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [done[0]["id"]] + [rest[int(i)] for i in sorted(pick)]


def served_tokens_ok(eng, rec: dict) -> int:
    """Requests that finished with other than exactly their max_new_tokens
    in-vocabulary tokens."""
    vocab = eng.cfg.vocab_size
    bad = 0
    for r in rec["requests"]:
        if r["done"] and r["shed"] is None:
            out = eng.outputs[r["id"]]
            bad += len(out) != r["max_new"] or \
                any(not 0 <= t < vocab for t in out)
    return bad


def memory_peak() -> Optional[int]:
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def memory_in_use() -> Optional[int]:
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")


def metrics(names: List[str], rec: dict) -> Dict[str, float]:
    out = {}
    for name in names:
        v = S.metric(name)(rec)
        if v is not None:
            out[name] = float(v)
    return out
