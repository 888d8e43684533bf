"""The serving engine: executes IterationPlans from any scheduler against a
REAL JAX model. This is the functional-correctness half of the evaluation
(the temporal half is serving/simulator.py, which drives the same scheduler
classes through an analytic hardware model).

Execution model per iteration:

  1. admissions — allocate a KV slot; for enc-dec models run the encoder
     and install per-block cross-attention K/V into the slot.
  2. prefill slices — each slice is a (token-range × block-range) rectangle.
     Block ranges are static per jit-cache entry (the TPU analogue of the
     paper's CUDA-graph buckets); token ranges are padded to power-of-two
     buckets with a validity mask. Boundary activations between layer
     groups are stashed on the engine (this is layered prefill's carry
     state). The final slice computes the request's FIRST token.
  3. decode — ONE fixed-shape step over the whole slot pool: every slot
     decodes one token; non-decoding slots are masked (their KV writes and
     recurrent-state updates are suppressed — see models/attention._write_cache
     and the valid-masking in the recurrent mixers).

Expert-load accounting (paper §5.4): each forward returns per-block expert
activation counts from the REAL router; the engine takes, per (iteration,
block), the union of experts activated by decode and by every prefill slice
touching that block — exactly the set of expert weight loads a fused hybrid
batch would issue — and accumulates ``bytes = nnz(union) * bytes_per_expert``.

Hot-path contract (DESIGN.md §Engine hot path):

  * PACKED layer-group batches — all prefill slices of a plan sharing
    (block_start, n_blocks, emits_first_token) execute as ONE jitted call
    over a slot vector: hidden (B, P, d), per-row offsets/valid/lengths,
    cache rows gathered/scattered with a single take / ``.at[slots].set``
    per leaf (``kernels.ops.gather_slot_rows``).  ``packed=False`` keeps
    the per-slice reference path (each slice is a batch of one).
  * DONATED cache buffers — every prefill/decode executable takes the KV
    pool with ``donate_argnums``, so XLA updates it in place instead of
    allocating a fresh ``n_slots × max_len`` copy per call.
  * ONE device sync per iteration — jitted calls return device arrays
    (first tokens, per-block expert-activation masks, decode tokens, swap
    victim rows) that are fetched by a single ``jax.device_get`` at the
    end of ``execute_plan``; no per-slice ``int(token)`` stalls.
"""

from __future__ import annotations

from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.base import Scheduler, make_scheduler
from repro.core.plan import (IterationPlan, PrefillSlice, Request,
                             RequestState, SubmitSpec)
from repro.kernels.ops import gather_slot_rows, scatter_slot_rows
from repro.models.config import dtype_bytes
from repro.models.model import DecoderModel
from repro.serving import spans
from repro.serving.kvcache import PagedKVAllocator
from repro.serving.runtime import (EngineExecutor, RunResult, ServingRuntime,
                                   TokenEvent, timestamp_events)
from repro.serving.spec import NgramDrafter, accepted_prefix, build_draft_model

Array = jax.Array

# Upper bound on live prefill executables.  Keys are (block_start, n_blocks,
# emit, B_bucket, P_bucket) — the batch/token buckets are part of the key,
# so one LRU entry == one compiled executable and the bound is real (keyed
# on the triple alone, mixed-shape traces used to retrace INSIDE an entry
# and grow live executables past the bound unobserved).
PREFILL_CACHE_SIZE = 32

# rows of ``Engine.iter_log`` kept (the newest): a long-running server must
# not grow it without bound
ITER_LOG_SIZE = 2 ** 16


def _bucket(n: int, minimum: int = 16, cap: Optional[int] = None) -> int:
    """Next power-of-two padding bucket >= n, clamped to ``cap`` (padding
    past the engine's max_len would trace shapes no request can fill)."""
    b = minimum
    while b < n:
        b *= 2
    if cap is not None:
        b = min(b, max(cap, n))
    return b


def _slice_cache(cache, slot):
    """Select one slot row (axis 1 — axis 0 is the segment-repeat stack)."""
    return jax.tree_util.tree_map(
        lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1), cache)


def _scatter_cache(full, row, slot):
    return jax.tree_util.tree_map(
        lambda f, r: jax.lax.dynamic_update_slice_in_dim(
            f, r.astype(f.dtype), slot, axis=1), full, row)


def _chunks_cover(chunks, n_blocks: int) -> bool:
    """True iff the staged handoff chunks tile every block [0, n_blocks)
    — the export can then be assembled without touching the device."""
    nxt = 0
    for b0, b1 in sorted((b0, b1) for b0, b1, _ in chunks):
        if b0 > nxt:
            return False
        nxt = max(nxt, b1)
    return nxt >= n_blocks


class Engine:
    def __init__(self, model: DecoderModel, params, scheduler, *,
                 n_slots: int = 8, max_len: int = 512,
                 pages: Optional[int] = None, page_size: int = 16,
                 preemption: bool = True,
                 preemption_mode: str = "recompute",
                 host_pages: Optional[int] = None,
                 swap_in_budget: Optional[int] = None,
                 swap_cost_fn=None,
                 decode_reserve: Optional[int] = None,
                 class_headroom: Optional[Dict[str, int]] = None,
                 eos_token: Optional[int] = None, gmm_fn=None,
                 moe_dispatch: str = "ragged", packed: bool = True,
                 prefix_cache: bool = True,
                 prefix_lru_pages: Optional[int] = None,
                 spec_mode: str = "off", spec_k: int = 4,
                 spec_adaptive: bool = True, spec_ngram_n: int = 3,
                 draft_model: Optional[DecoderModel] = None,
                 draft_params=None, draft_config: Optional[str] = None):
        """``moe_dispatch`` selects the dropless MoE data path: "ragged"
        (default — expert-sorted tile-aligned buffer, compute/traffic scale
        with the routed work) or "dense" (worst-case (E, T, d) capacity
        buffer). Outputs are identical either way; see models/moe.py.

        ``pages``/``page_size`` size the paged KV pool shared with the
        scheduler (default: enough pages to fill every slot row — no
        pressure beyond the slot bound).  ``preemption`` enables memory-
        pressure eviction; with it off, admission still queues on pressure
        but decode growth past ``decode_reserve`` can raise
        PagedPoolExhausted.  ``preemption_mode`` picks the eviction flavour
        ("recompute" | "swap" | "auto"): under swap, victims' cache rows
        are copied to host memory and restored verbatim on swap-in (gated
        by ``swap_in_budget`` KV tokens per iteration), sized by
        ``host_pages`` (default 4x the device pool).  ``swap_cost_fn``
        prices swap vs recompute per victim for "auto"; without one, auto
        swaps whenever the victim is swappable.  ``class_headroom``
        reserves admission pages per SLO class (see
        core.base.Scheduler.attach_kv).  ``packed`` enables packed
        layer-group batches (one jitted call per (block-range, emit) group
        of the plan's prefill slices); ``packed=False`` executes every
        slice as its own batch of one — the reference path the
        equivalence tests and ``benchmarks/engine_iter_bench.py`` compare
        against.

        ``prefix_cache`` (default on) enables automatic prefix caching
        (DESIGN.md §Prefix caching): completed prompts' full KV pages are
        content-hashed into a refcounted shared index, admissions whose
        prompt matches a cached chain skip the matched tokens entirely —
        the engine restores the cached slot row and prefill starts past
        the cached boundary, with tokens bit-identical to a cold run.
        ``prefix_lru_pages`` caps the reclaimable (refcount-0) cached
        pages kept resident (None = bounded only by pool pressure).

        ``spec_mode`` enables speculative verify-k decoding ("ngram" =
        draft-free prompt/self-lookup; "draft" = a tiny stateless draft
        model — pass ``draft_model``/``draft_params`` directly or name a
        registered config via ``draft_config``).  ``spec_k`` caps the
        per-request draft budget; ``spec_adaptive`` lets a per-request
        acceptance EMA shrink the draft-model budget.  Greedy token
        streams are bit-identical to ``spec_mode="off"`` — speculation
        only changes how many tokens each dispatch commits (DESIGN.md
        §Speculative decode)."""
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.packed = packed
        if moe_dispatch not in ("dense", "ragged"):
            raise ValueError(f"unknown moe_dispatch {moe_dispatch!r}")
        self.moe_dispatch = moe_dispatch
        if isinstance(scheduler, str):
            scheduler = make_scheduler(scheduler, model.n_blocks,
                                       n_slots=n_slots)
        assert scheduler.n_slots <= n_slots, "scheduler must fit slot pool"
        self.scheduler: Scheduler = scheduler
        stash_factor = self.cfg.stash_token_factor()
        if pages is None:
            # default pool: every slot can hold a max_len request plus its
            # decode-reservation rounding and worst-case stash — admission
            # then never blocks while a slot is free (pre-paging behaviour)
            reserve = page_size if decode_reserve is None else decode_reserve
            per_slot = (-(-(max_len + reserve) // page_size)
                        + -(-int(max_len * stash_factor + 1) // page_size))
            pages = n_slots * per_slot
        if host_pages is None:
            host_pages = 4 * pages if preemption_mode != "recompute" else 0
        self.alloc = PagedKVAllocator(pages, page_size,
                                      stash_factor=stash_factor,
                                      n_host_pages=host_pages,
                                      prefix_caching=prefix_cache,
                                      prefix_lru_pages=prefix_lru_pages)
        self.prefix_cache = prefix_cache
        # digest -> (device KV row snapshot, usable tokens): the physical
        # realization of the allocator's shared-prefix index.  Rows are
        # sliced ONCE when a prompt's chains register and restored into a
        # hitting request's slot at admission; the allocator's reclaim hook
        # drops a row the moment its index entry dies.
        self._prefix_rows: Dict[bytes, Tuple[object, int]] = {}
        self.alloc.on_prefix_evict = \
            lambda digest: self._prefix_rows.pop(digest, None)
        self.scheduler.attach_kv(self.alloc, decode_reserve=decode_reserve,
                                 preemption=preemption,
                                 mode=preemption_mode,
                                 swap_in_budget=swap_in_budget,
                                 swap_cost_fn=swap_cost_fn,
                                 class_headroom=class_headroom)
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_token = eos_token
        self.gmm_fn = gmm_fn

        # speculative verify-k decoding (DESIGN.md §Speculative decode)
        if spec_mode not in ("off", "ngram", "draft"):
            raise ValueError(f"unknown spec_mode {spec_mode!r}")
        self.spec_mode = spec_mode
        self.spec_k = spec_k
        self.drafter = NgramDrafter(spec_ngram_n) \
            if spec_mode == "ngram" else None
        self.draft_model: Optional[DecoderModel] = None
        self.draft_params = None
        if spec_mode == "draft":
            if draft_model is not None:
                self.draft_model, self.draft_params = draft_model, \
                    draft_params
            elif draft_config is not None:
                self.draft_model, self.draft_params = build_draft_model(
                    draft_config, self.cfg.vocab_size)
            else:
                raise ValueError(
                    "spec_mode='draft' needs draft_model/draft_params or a "
                    "draft_config name")
            if self.draft_model.cfg.vocab_size != self.cfg.vocab_size:
                raise ValueError("draft model must share the target vocab")
        if spec_mode != "off":
            self.scheduler.configure_speculation(spec_mode, spec_k,
                                                 adaptive=spec_adaptive)
        # physical slot rows (the contiguous per-request realization of the
        # logical block tables; see DESIGN.md §Hardware adaptation)
        self._free_slots = list(range(n_slots))[::-1]
        self._slot_of: Dict[int, int] = {}

        self.cache = model.init_cache(n_slots, max_len)
        self.offsets = np.zeros(n_slots, np.int32)       # true filled length
        self.last_tok = np.zeros(n_slots, np.int32)
        self.decoding = np.zeros(n_slots, bool)

        self._next_id = 0
        self.requests: Dict[int, Request] = {}
        self.prompts: Dict[int, np.ndarray] = {}
        self.outputs: Dict[int, List[int]] = {}
        # req -> (packed boundary batch, row index, token count): cohort
        # members share ONE (B, P, d) batch array (§Engine hot path)
        self.stash: Dict[int, Tuple[Array, int, int]] = {}
        self.enc_frames: Dict[int, np.ndarray] = {}
        # swapped-out requests: req -> (host cache rows, offset, last_tok)
        self.host_kv: Dict[int, Tuple[object, int, int]] = {}
        # disaggregated handoff (DESIGN.md §Disaggregated serving): with
        # staging on (this engine is a prefill pool), every layer group
        # whose KV completes is sliced per-block and host-staged through
        # the same single end-of-iteration fetch as swap victims; at
        # export the chunks ARE the transfer — no extra device sync.
        # req -> [(block_start, block_end, host rows per block)]
        self.handoff_staging = False
        self._handoff_chunks: Dict[int, List[Tuple[int, int, list]]] = {}
        self.n_handoffs_out = 0
        self.n_handoffs_in = 0
        self.handoff_bytes = 0

        # metrics
        self.iteration = 0
        self._step_events: List[TokenEvent] = []
        self.n_preempted = 0
        self.n_swapped_out = 0
        self.n_swapped_in = 0
        self.expert_load_bytes = 0
        # one row per executed iteration, the newest ITER_LOG_SIZE kept
        self.iter_log: deque = deque(maxlen=ITER_LOG_SIZE)
        # req -> (ns its admitting plan began, iteration): the start of its
        # ``request.prefill`` span, until its first token
        self._prefill_from: Dict[int, Tuple[int, int]] = {}
        bytes_per_el = dtype_bytes(self.cfg.param_dtype)
        self._expert_bytes = self.cfg.expert_bytes(bytes_per_el)
        # dispatch accounting (benchmarks/engine_iter_bench.py and the
        # packed-vs-per-slice regression tests): n_dispatches counts
        # engine-level device launches (embed / prefill / decode / encode /
        # stash regather), n_prefill_* the packed-batch executions and
        # compiled executables specifically
        self.n_dispatches = 0
        self.n_prefill_dispatches = 0
        self.n_prefill_compiles = 0
        # prefix-cache accounting: restores = admissions that seeded their
        # slot row from a cached prefix (the allocator counts hits/tokens)
        self.n_prefix_restores = 0
        # speculative-decode accounting: verify/draft executables live in
        # the SAME bounded LRU as prefill executables (a growing family of
        # k buckets must not grow live executables past the bound)
        self.n_verify_dispatches = 0
        self.n_verify_compiles = 0
        self.n_draft_dispatches = 0
        self.n_spec_proposed = 0
        self.n_spec_accepted = 0
        # per-iteration record of what was ACTUALLY verified (rid -> k_eff;
        # may be smaller than plan.verify_len when a drafter found nothing)
        self.last_verify_executed: Dict[int, int] = {}

        self._jit_embed = {}
        self._jit_prefill: OrderedDict = OrderedDict()   # LRU, bounded
        # the KV pool is donated on every decode/prefill call: XLA aliases
        # the input buffers to the outputs and updates the cache in place
        self._jit_decode = jax.jit(self._decode_step_impl,
                                   donate_argnums=(1,))
        self._jit_encode = jax.jit(self._encode_impl)

    # ------------------------------------------------------------------ API

    def submit_spec(self, spec: SubmitSpec) -> Request:
        """THE ingestion door (core/plan.py): every submission path — HTTP
        front-end, trace replay, closed-loop drains — lands here with one
        frozen ``SubmitSpec``.  A spec without ``arrival_time`` is stamped
        at the engine's current iteration (live traffic on the iteration
        clock; wall-mode executors stamp before calling)."""
        if spec.prompt_tokens is None:
            raise ValueError(
                "engine submission needs real token ids — build the "
                "SubmitSpec with prompt_tokens (see "
                "traffic.attach_prompt_tokens for simulator-shaped traces)")
        rid = self._next_id
        self._next_id += 1
        prompt = np.asarray(spec.prompt_tokens, np.int32)
        if len(prompt) + spec.max_new_tokens > self.max_len:
            # the bound also caps the recompute prompt after a preemption
            # (prompt + generated-so-far never exceeds prompt + max_new)
            raise ValueError(
                f"request {rid}: prompt {len(prompt)} + max_new "
                f"{spec.max_new_tokens} exceeds max_len {self.max_len}")
        req = Request.from_spec(
            spec, rid,
            arrival_time=float(self.iteration)
            if spec.arrival_time is None else spec.arrival_time,
            prompt_tokens=prompt)
        self.requests[rid] = req
        self.prompts[rid] = prompt
        self.outputs[rid] = []
        if spec.enc_frames is not None:
            self.enc_frames[rid] = np.asarray(spec.enc_frames)
        self.scheduler.submit(req)
        return req

    def submit(self, prompt_tokens, max_new_tokens: int,
               enc_frames=None, *, slo_class: str = "interactive",
               arrival_time: Optional[float] = None) -> int:
        """Positional convenience wrapper over ``submit_spec`` (kept for
        closed-loop callers and tests); returns the request id."""
        return self.submit_spec(SubmitSpec(
            max_new_tokens=max_new_tokens, prompt_tokens=prompt_tokens,
            slo_class=slo_class, arrival_time=arrival_time,
            enc_frames=enc_frames)).req_id

    def run(self, max_iterations: int = 10_000) -> "RunResult":
        """Closed-loop drain of everything already submitted, through the
        shared ServingRuntime loop (timestamps are iteration-indexed, as
        they always were).  For open-loop timed-trace replay build a
        ``ServingRuntime(EngineExecutor(engine))`` and pass the trace."""
        runtime = ServingRuntime(EngineExecutor(self), clock="iteration")
        return runtime.run((), max_iterations=max_iterations)

    # -------------------------------------------------------------- jit fns

    def _encode_impl(self, params, frames):
        enc = self.model.encode(params, frames)
        return enc, self.model.precompute_cross_kv(params, enc)

    def _embed_impl(self, params, tokens, positions):
        return self.model.embed(params, tokens, positions=positions)

    def _decode_step_impl(self, params, cache, tokens, offsets, valid_rows):
        """tokens: (n_slots, 1). One decode token for every slot; masked
        rows are no-ops (state & KV preserved).  Returns the per-(block,
        expert) activation MASK rather than raw counts — the union
        reduction the host needs stays on device."""
        positions = offsets[:, None]
        valid = valid_rows[:, None]
        logits, cache, aux = self.model.forward(
            params, tokens, positions=positions, offset=offsets, cache=cache,
            valid=valid, gmm_fn=self.gmm_fn, dropless=True,
            moe_dispatch=self.moe_dispatch)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, cache, aux["expert_counts"] > 0

    def _prefill_impl(self, start: int, n: int, emit: bool,
                      params, cache, hidden, valid, slots, offset, length):
        """One packed layer-group batch: hidden (B, P, d) holds one row per
        prefill slice, slots/offset/length are (B,).  Static key: (start,
        n, emit, B, P).  The multi-slot cache is DONATED by the caller;
        rows are gathered/scattered with one take / one slot-vector
        scatter per leaf instead of B full-tree dynamic slices.  Padding
        rows (valid all-False, slot id == n_slots) are no-ops end to end:
        their KV/state writes are suppressed by ``valid`` and their
        writeback is dropped by the out-of-range scatter."""
        rows = gather_slot_rows(cache, slots)
        positions = offset[:, None] + jnp.arange(hidden.shape[1],
                                                 dtype=jnp.int32)[None]
        x, rows, auxes = self.model.run_blocks(
            params, hidden, start, n,
            positions=positions, offset=offset, cache=rows, valid=valid,
            gmm_fn=self.gmm_fn, dropless=True,
            moe_dispatch=self.moe_dispatch)
        cache = scatter_slot_rows(cache, rows, slots)
        # per-(block, expert) activation mask over the WHOLE batch (n, E):
        # router counts are already summed over rows, so the host-side
        # union fetch is batch-size-free
        loads = jnp.stack([a["expert_counts"] > 0 for a in auxes])
        tokens = jnp.full((hidden.shape[0],), -1, jnp.int32)
        if emit:
            h_last = jnp.take_along_axis(
                x, (length - 1)[:, None, None], axis=1)[:, 0]
            logits = self.model.logits(params, h_last)
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return x, cache, loads, tokens

    def _executable(self, key: tuple, build, counter: str):
        """The bounded LRU's entry for ``key``.  On a miss ``build()`` makes
        the jitted function, the engine counter ``counter`` counts it, and
        its first call, which traces and compiles it, runs inside an
        ``engine.build`` span."""
        cache = self._jit_prefill
        if key in cache:
            cache.move_to_end(key)
            return cache[key]
        fn = build()

        def first_call(*args):
            with spans.span("engine.build", key=key):
                out = fn(*args)
            if key in cache:
                cache[key] = fn
            return out
        cache[key] = first_call
        setattr(self, counter, getattr(self, counter) + 1)
        while len(cache) > PREFILL_CACHE_SIZE:
            cache.popitem(last=False)
        return first_call

    def _get_prefill_fn(self, start: int, n: int, emit: bool,
                        b: int, p: int):
        """One executable per (block_start, n_blocks, emit, B_bucket,
        P_bucket).  The shape buckets are part of the LRU key so the
        PREFILL_CACHE_SIZE bound counts executables, not trace families."""
        def build():
            def prefill_group(*args):
                return self._prefill_impl(start, n, emit, *args)
            return jax.jit(prefill_group, donate_argnums=(1,))
        return self._executable((start, n, emit, b, p), build,
                                "n_prefill_compiles")

    def _get_embed_fn(self):
        if "f" not in self._jit_embed:
            self._jit_embed["f"] = jax.jit(self._embed_impl)
        return self._jit_embed["f"]

    def _verify_impl(self, params, cache, tokens, valid, slots, offset):
        """Verify-k window for a cohort of drafting slots in ONE call:
        ``tokens`` (B, P) holds per row [last_tok, d_1..d_k, pad...] fed at
        positions offset..offset+P-1 through the FULL stack.  Row j of the
        returned argmax grid is the target's greedy token AFTER window
        position j — the host accepts the matching draft prefix.  KV for
        the whole window is written through the donated-buffer path;
        *rollback* past the first rejection is free: attention masks KV by
        the committed offset (``kv_valid = pos < offset + s``), so the
        stale tail beyond what the host commits is never read and is
        overwritten by a later window.  Padding rows (slot id == n_slots,
        valid all-False) are no-ops end to end."""
        rows = gather_slot_rows(cache, slots)
        positions = offset[:, None] + jnp.arange(tokens.shape[1],
                                                 dtype=jnp.int32)[None]
        hidden = self.model.embed(params, tokens, positions=positions)
        x, rows, auxes = self.model.run_blocks(
            params, hidden, 0, self.model.n_blocks,
            positions=positions, offset=offset, cache=rows, valid=valid,
            gmm_fn=self.gmm_fn, dropless=True,
            moe_dispatch=self.moe_dispatch)
        cache = scatter_slot_rows(cache, rows, slots)
        loads = jnp.stack([a["expert_counts"] > 0 for a in auxes])
        logits = self.model.logits(params, x)
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (B, P)
        return cache, loads, toks

    def _get_verify_fn(self, b: int, p: int):
        """Verify executables join the prefill LRU under ("verify", B, P)
        keys — bucketed k means adaptive speculation lengths reuse a small
        executable family, and the shared PREFILL_CACHE_SIZE bound counts
        them like any other live executable."""
        return self._executable(
            ("verify", b, p),
            lambda: jax.jit(self._verify_impl, donate_argnums=(1,)),
            "n_verify_compiles")

    def _draft_impl(self, k, params, tokens, lengths):
        """Greedy k-step extension by the STATELESS draft model, one jitted
        ``lax.scan``: each step re-runs the draft over the (padded) full
        history — no draft KV cache exists, so preemption/fold/swap need
        zero draft-side bookkeeping.  Proposals stay on device (the verify
        window consumes them directly); their values ride the single
        end-of-iteration fetch."""
        n_pos = tokens.shape[1]

        def step(state, _):
            toks, lens = state
            logits, _, _ = self.draft_model.forward(params, toks)
            nxt = jnp.take_along_axis(logits, (lens - 1)[:, None, None],
                                      axis=1)[:, 0]
            tok = jnp.argmax(nxt, axis=-1).astype(jnp.int32)
            toks = jnp.where(jnp.arange(n_pos, dtype=jnp.int32)[None]
                             == lens[:, None], tok[:, None], toks)
            return (toks, lens + 1), tok

        (_, _), props = jax.lax.scan(step, (tokens, lengths), None, length=k)
        return jnp.transpose(props)                              # (B, k)

    def _get_draft_fn(self, k: int, b: int, p: int):
        """Draft executables share the bounded prefill LRU too."""
        def build():
            def draft(*args):
                return self._draft_impl(k, *args)
            return jax.jit(draft)
        return self._executable(("draft", k, b, p), build,
                                "n_verify_compiles")

    # -------------------------------------------------------------- stepping

    def step(self) -> IterationPlan:
        """Legacy self-driving step (plan + execute + iteration-clock
        timestamps via the runtime's shared rule). The serving loop —
        arrivals, clocks, streaming — lives in serving/runtime.py; this
        remains for tests and tools that drive iterations by hand."""
        plan = self.scheduler.next_plan(now=float(self.iteration))
        events = self.execute_plan(plan)
        # execute_plan advanced self.iteration: tokens visible at the
        # new count, exactly the runtime's iteration-clock t_end
        timestamp_events(self.scheduler, events, float(self.iteration))
        return plan

    def execute_plan(self, plan: IterationPlan) -> List[TokenEvent]:
        """Execute one scheduler-produced plan against the real model and
        return the tokens it emitted (consumed by the ServingRuntime for
        timestamping and streaming callbacks).

        The hot path is sync-free: prefill groups and the decode step are
        LAUNCHED first (device arrays only), then ONE ``jax.device_get``
        fetches everything the host needs — emitted tokens, per-block
        expert-activation masks, and this iteration's swap-out victim rows
        — and all bookkeeping (offsets, EOS, token events, expert union)
        runs on the fetched numpy values."""
        self._step_events: List[TokenEvent] = []
        prefill_tokens = sum(sl.n_tokens for sl in plan.prefill)
        with spans.span("engine.step", iteration=self.iteration,
                        n_decode=len(plan.decode_ids),
                        prefill_tokens=prefill_tokens) as step:
            first = self._execute(plan, step)
        for rid in first:
            t0_ns, it0 = self._prefill_from.pop(rid)
            spans.record("request.prefill", t0_ns, step.end_ns, req_id=rid,
                         n_steps=step.attrs["iteration"] - it0 + 1)
        return self._step_events

    def _execute(self, plan: IterationPlan,
                 step: "spans.span") -> Tuple[int, ...]:
        """``execute_plan``'s body, inside its ``engine.step`` span; returns
        the requests whose first token this step emitted."""
        dispatches0 = self.n_dispatches
        block_expert_union = np.zeros(
            (self.model.n_blocks, max(self.cfg.moe.n_experts, 1)), bool)

        # memory-pressure victims first: their slot rows and stash must be
        # released before this iteration's swap-ins/admissions reuse them.
        # Swap-out rows are snapshotted as device arrays (immutable — later
        # writes build new buffers) and join the end-of-iteration fetch.
        with spans.span("engine.admit"):
            for rid in plan.preempted_ids:
                self._preempt(rid)
            swap_pending = [self._swap_out(rid)
                            for rid in plan.swapped_out_ids]
            for rid in plan.swapped_in_ids:
                self._swap_in(rid)
            for rid in plan.admitted_ids:
                self._admit(rid)
                if not self.outputs[rid]:      # not a recompute epoch
                    self._prefill_from.setdefault(
                        rid, (plan.planned_ns or step.start_ns,
                              self.iteration))

        with spans.span("engine.launch") as launch:
            groups = self._pack_slices(plan.prefill)
            launch.attrs["n_groups"] = step.attrs["n_groups"] = len(groups)
            launched, staged = [], []
            for g in groups:
                launched.append(self._launch_prefill_group(*g))
                if self.handoff_staging:
                    # group-granular streaming: a slice whose token range
                    # ends at the prompt completes its blocks' KV this
                    # iteration — slice those rows NOW (before a later
                    # donated call retires this cache buffer); values join
                    # the single fetch below
                    for sl in g[3]:
                        if sl.token_end \
                                == self.requests[sl.req_id].prompt_len:
                            staged.append(
                                (sl.req_id, sl.block_start, sl.block_end,
                                 self._slice_block_rows(sl.req_id,
                                                        sl.block_start,
                                                        sl.block_end)))

            # speculative verify-k: draft + verify are LAUNCHED here (device
            # arrays only); rows the drafter skipped fall through to the
            # plain decode step below
            spec_rows, spec_skipped, spec_fetch = [], [], None
            if plan.verify_len and self.spec_mode != "off":
                spec_rows, spec_skipped, spec_fetch = \
                    self._launch_verify(plan)
            spec_rids = {rid for rid, _, _, _, _ in spec_rows}

            decode_slot_req = decode_out = None
            plain_ids = [rid for rid in plan.decode_ids
                         if rid not in spec_rids]
            if plain_ids:
                decode_slot_req, decode_out = self._launch_decode(plain_ids)

        # ---- the ONE host sync per iteration ----
        if launched or decode_out is not None or swap_pending \
                or spec_fetch is not None or staged:
            with spans.span("engine.fetch"):
                launched, decode_out, spec_fetch, swap_rows, staged_rows = \
                    jax.device_get(
                        (launched, decode_out, spec_fetch,
                         [row for _, row in swap_pending],
                         [rows for *_, rows in staged]))

        with spans.span("engine.commit"):
            if swap_pending or staged:
                for (rid, _), row in zip(swap_pending, swap_rows):
                    self.host_kv[rid] = (row,) + self.host_kv[rid][1:]
                for (rid, b0, b1, _), rows in zip(staged, staged_rows):
                    self._handoff_chunks.setdefault(rid, []).append(
                        (b0, b1, rows))
                    self.handoff_bytes += sum(
                        a.nbytes for a in jax.tree_util.tree_leaves(rows))

            for (start, end, emit, slices), (loads, toks) in zip(groups,
                                                                 launched):
                block_expert_union[start:end] |= loads
                for i, sl in enumerate(slices):
                    self._finish_prefill_slice(sl, int(toks[i]))
            self.last_verify_executed = {}
            if spec_fetch is not None:
                loads, toks, props = spec_fetch
                block_expert_union |= loads
                self._finish_verify(spec_rows, toks, props)
            if decode_out is not None:
                next_tok, loads = decode_out
                block_expert_union |= loads
                for slot, rid in decode_slot_req.items():
                    tok = int(next_tok[slot])
                    self.offsets[slot] += 1
                    self.last_tok[slot] = tok
                    self._record_token(rid, tok, first=False)
                    self._maybe_finish(rid, tok)
            for rid in spec_skipped:
                # a 0-proposal commit releases the scheduler's page
                # pre-charge
                self.last_verify_executed[rid] = 0
                self.scheduler.commit_speculation(rid, proposed=0,
                                                  accepted=0, extra=0)

            loaded = int(block_expert_union.sum()) * self._expert_bytes
            if self.cfg.moe.enabled:
                self.expert_load_bytes += loaded
            self.iter_log.append({
                "iteration": self.iteration,
                "n_decode": len(plan.decode_ids),
                "prefill_tokens": step.attrs["prefill_tokens"],
                "expert_load_bytes": loaded,
                "pages_in_use": self.alloc.pages_in_use(),
                "host_pages_in_use": self.alloc.host_pages_in_use(),
                "n_preempted": len(plan.preempted_ids),
                "n_swapped_out": len(plan.swapped_out_ids),
                "n_swapped_in": len(plan.swapped_in_ids),
                "n_dispatches": self.n_dispatches - dispatches0,
            })
            first = tuple(ev.req_id for ev in self._step_events
                          if ev.first and ev.req_id in self._prefill_from)
            step.attrs["first"] = first
            self.iteration += 1
        return first

    # ------------------------------------------------ disaggregated handoff

    def _slice_block_rows(self, rid: int, b0: int, b1: int) -> list:
        """Device-slice one slot's cache rows for blocks [b0, b1) — the
        group-granular handoff chunk.  Eager ops allocate fresh buffers,
        so the snapshot survives later donated calls; the VALUES ride the
        single end-of-iteration ``jax.device_get``."""
        slot = self._slot_of[rid]
        rows = []
        for b in range(b0, b1):
            s, r, p_idx = self.model.index_map[b]
            rows.append(jax.tree_util.tree_map(
                lambda c: jax.lax.dynamic_slice_in_dim(c[r], slot, 1,
                                                       axis=0),
                self.cache[s][p_idx]))
        return rows

    def _scatter_block_rows(self, slot: int, b0: int, b1: int,
                            rows: list) -> None:
        """Install imported per-block chunk rows into ``slot`` (the decode-
        side half of the streaming handoff; device ops, no host sync)."""
        for b, row in zip(range(b0, b1), rows):
            s, r, p_idx = self.model.index_map[b]
            self.cache[s][p_idx] = jax.tree_util.tree_map(
                lambda f, ch: f.at[r, slot].set(
                    jnp.asarray(ch[0]).astype(f.dtype)),
                self.cache[s][p_idx], row)

    def export_request(self, rid: int) -> dict:
        """Pull a migrating request's state off this engine (the prefill
        pool): host-staged KV chunks (or, when they do not tile the stack
        — staging off, or a preemption dropped them — a one-off full-row
        snapshot), the token buffers, and the allocator-level page export
        (shared-prefix pages stay warm in THIS pool's LRU).  The caller
        has already ``pop_request``-ed the id from the scheduler."""
        req = self.requests.pop(rid)
        slot = self._slot_of.pop(rid)
        offset = int(self.offsets[slot])
        last = int(self.last_tok[slot])
        chunks = self._handoff_chunks.pop(rid, [])
        row = None
        if not _chunks_cover(chunks, self.model.n_blocks):
            # whole-prompt fallback: the only device sync outside the
            # per-iteration fetch, taken exactly when streaming was off
            row = jax.device_get(_slice_cache(self.cache, slot))
            chunks = []
        self._free_slots.append(slot)
        self.decoding[slot] = False
        self.stash.pop(rid, None)
        self.n_handoffs_out += 1
        return {"req": req, "prompt": self.prompts.pop(rid),
                "outputs": self.outputs.pop(rid),
                "enc_frames": self.enc_frames.pop(rid, None),
                "offset": offset, "last_tok": last,
                "chunks": chunks, "row": row,
                "kv": self.alloc.export_pages(rid)}

    def import_request(self, payload: dict):
        """Install an exported request on this engine (the decode pool):
        land its pages (warm shared chains link for free), scatter the
        staged chunks — or the fallback full row — into a fresh slot, and
        resume decode exactly where the prefill pool left off.  Returns
        the allocator's ``KVImport`` (linked/moved token split).  The
        caller adopts the request into this engine's scheduler AFTER this
        lands (``Scheduler.adopt`` asserts residency)."""
        req = payload["req"]
        rid = req.req_id
        imp = self.alloc.import_pages(payload["kv"])
        slot = self._free_slots.pop()
        self._slot_of[rid] = slot
        if payload["row"] is not None:
            self.cache = _scatter_cache(self.cache, payload["row"], slot)
        else:
            for b0, b1, rows in payload["chunks"]:
                self._scatter_block_rows(slot, b0, b1, rows)
        self.offsets[slot] = payload["offset"]
        self.last_tok[slot] = payload["last_tok"]
        self.decoding[slot] = True
        self.requests[rid] = req
        self.prompts[rid] = payload["prompt"]
        self.outputs[rid] = payload["outputs"]
        if payload["enc_frames"] is not None:
            self.enc_frames[rid] = payload["enc_frames"]
        self.n_handoffs_in += 1
        return imp

    def release_request(self, rid: int) -> None:
        """Drop every physical resource a SHED request still holds — slot
        row, host swap snapshot, boundary stash, staged handoff chunks —
        without touching its token buffers (the shed stream's partial
        output stays readable in ``outputs``).  The scheduler side (page
        release, queue scrub, DONE state) is ``Scheduler.shed``'s job;
        this is its executor-side mirror, callable in any pre-DONE state
        (WAITING victims hold nothing and every pop is a no-op)."""
        slot = self._slot_of.pop(rid, None)
        if slot is not None:
            self._free_slots.append(slot)
            self.decoding[slot] = False
        self.host_kv.pop(rid, None)
        self.stash.pop(rid, None)
        self._handoff_chunks.pop(rid, None)
        self._prefill_from.pop(rid, None)

    # -------------------------------------------------------------- helpers

    def _preempt(self, rid: int) -> None:
        """Execute a scheduler eviction: release the physical slot row and
        the boundary-activation stash, and fold the tokens generated so far
        into the recompute prompt (matching the scheduler's prompt_len
        fold in ``Scheduler.preempt``).  A demoted SWAPPED victim (the
        scheduler's swap-pin pressure valve) holds no slot — its dead
        host snapshot is dropped instead."""
        slot = self._slot_of.pop(rid, None)
        if slot is not None:
            self._free_slots.append(slot)
            self.decoding[slot] = False
        else:
            self.host_kv.pop(rid, None)
        self.stash.pop(rid, None)
        self._handoff_chunks.pop(rid, None)   # staged KV is void post-fold
        # append only the tokens generated since the last fold — a request
        # preempted twice must not duplicate the already-folded prefix
        tail = self.requests[rid].prompt_len - len(self.prompts[rid])
        if tail:
            self.prompts[rid] = np.concatenate(
                [self.prompts[rid],
                 np.asarray(self.outputs[rid][-tail:], np.int32)])
        assert len(self.prompts[rid]) == self.requests[rid].prompt_len, \
            (rid, len(self.prompts[rid]), self.requests[rid].prompt_len)
        self.n_preempted += 1

    def _swap_out(self, rid: int):
        """Execute a swap-to-host eviction: snapshot the victim's slot row
        (every per-block KV / recurrent-state entry) as ONE device slice
        and release the slot; ``execute_plan`` materialises the host copy
        in the single end-of-iteration ``jax.device_get`` (one batched
        transfer, not a per-leaf ``np.asarray`` stall each).  The snapshot
        is immutable — this iteration's compute builds new cache buffers —
        so deferring the fetch cannot observe later writes.  Until then
        ``host_kv`` holds the device snapshot (hand-stepping drivers that
        call ``_swap_out`` directly stay correct: ``_swap_in`` restores
        either representation verbatim).  The scheduler already moved the
        allocator pages to the host pool."""
        slot = self._slot_of.pop(rid)
        assert rid not in self.stash, rid       # swap victims are DECODE
        row = _slice_cache(self.cache, slot)
        self.host_kv[rid] = (row, int(self.offsets[slot]),
                             int(self.last_tok[slot]))
        self._free_slots.append(slot)
        self.decoding[slot] = False
        self.n_swapped_out += 1
        return rid, row

    def _swap_in(self, rid: int) -> None:
        """DMA-back: restore the host copy into a fresh slot row and resume
        decode exactly where the victim left off (bit-identical KV, so the
        greedy continuation matches an undisturbed run)."""
        slot = self._free_slots.pop()
        self._slot_of[rid] = slot
        row, offset, last = self.host_kv.pop(rid)
        self.cache = _scatter_cache(self.cache, row, slot)
        self.offsets[slot] = offset
        self.last_tok[slot] = last
        self.decoding[slot] = True
        self.n_swapped_in += 1

    def _admit(self, rid: int) -> None:
        slot = self._free_slots.pop()
        self._slot_of[rid] = slot
        self.offsets[slot] = 0
        self.decoding[slot] = False
        hit = self.alloc.prefix_hit(rid)
        if hit.cached_tokens:
            # seed the slot row with the cached prefix KV: the snapshot
            # row holds the registering request's KV for positions
            # 0..usable-1 (usable >= cached_tokens; on a COW hit the tail
            # page's extra positions are overwritten by the re-prefilled
            # token or masked by the offset).  Same scatter machinery as
            # swap-in — a device op, no host sync.
            row, usable = self._prefix_rows[hit.leaf]
            assert usable >= hit.cached_tokens, (rid, usable, hit)
            self.cache = _scatter_cache(self.cache, row, slot)
            self.offsets[slot] = hit.cached_tokens
            self.n_prefix_restores += 1
        if rid in self.enc_frames:
            frames = jnp.asarray(self.enc_frames[rid])[None]
            _, xkv = self._jit_encode(self.params, frames)
            # install cross K/V into this slot's cache rows
            for s, seg in enumerate(xkv):
                for p_idx, kv in enumerate(seg):
                    if kv is None:
                        continue
                    cur = self.cache[s][p_idx]
                    self.cache[s][p_idx] = dict(
                        cur,
                        xk=cur["xk"].at[:, slot].set(kv["xk"][:, 0]),
                        xv=cur["xv"].at[:, slot].set(kv["xv"][:, 0]),
                    )

    def _pack_slices(self, slices: List[PrefillSlice]):
        """Group the plan's prefill slices by their layer-group rectangle:
        every (block_start, block_end, emits_first_token) group executes
        as ONE jitted call over a slot vector.  A request appears at most
        once per plan (scheduler invariant I3), so rows within a group are
        independent — distinct slots, no intra-group KV dependencies.
        With packing disabled each slice is its own group of one (the
        per-slice reference path)."""
        if not self.packed:
            return [(sl.block_start, sl.block_end, sl.emits_first_token,
                     [sl]) for sl in slices]
        grouped: OrderedDict = OrderedDict()
        for sl in slices:
            key = (sl.block_start, sl.block_end, sl.emits_first_token)
            grouped.setdefault(key, []).append(sl)
        return [(start, end, emit, sls)
                for (start, end, emit), sls in grouped.items()]

    def _launch_prefill_group(self, start: int, end: int, emit: bool,
                              slices: List[PrefillSlice]):
        """Launch one packed layer-group batch; returns DEVICE arrays
        (per-block expert-activation mask, per-row first tokens) for the
        end-of-iteration fetch.  Rows pad to a power-of-two batch bucket
        (padding rows carry the out-of-range slot id and an all-False
        valid mask) and tokens to a power-of-two token bucket."""
        b = len(slices)
        b_pad = _bucket(b, minimum=1, cap=self.n_slots)
        if start == 0:
            # fresh rectangle rows: embed every token range in ONE call
            p = _bucket(max(sl.n_tokens for sl in slices), cap=self.max_len)
            toks = np.zeros((b_pad, p), np.int32)
            pos = np.zeros((b_pad, p), np.int32)
            for i, sl in enumerate(slices):
                toks[i, :sl.n_tokens] = \
                    self.prompts[sl.req_id][sl.token_start:sl.token_end]
                pos[i] = sl.token_start + np.arange(p, dtype=np.int32)
            hidden = self._get_embed_fn()(self.params, jnp.asarray(toks),
                                          jnp.asarray(pos))
            self.n_dispatches += 1
        else:
            hidden = self._stash_hidden(slices, b_pad)
            p = hidden.shape[1]
        valid = np.zeros((b_pad, p), bool)
        slots = np.full(b_pad, self.n_slots, np.int32)  # OOB: writes dropped
        offs = np.zeros(b_pad, np.int32)
        lens = np.ones(b_pad, np.int32)
        for i, sl in enumerate(slices):
            valid[i, :sl.n_tokens] = True
            slots[i] = self._slot_of[sl.req_id]
            offs[i] = sl.token_start
            lens[i] = sl.n_tokens
        fn = self._get_prefill_fn(start, end - start, emit, b_pad, p)
        x, self.cache, loads, tokens = fn(
            self.params, self.cache, hidden, jnp.asarray(valid),
            jnp.asarray(slots), jnp.asarray(offs), jnp.asarray(lens))
        self.n_dispatches += 1
        self.n_prefill_dispatches += 1
        if end < self.model.n_blocks:
            # the whole packed boundary activation is stashed ONCE; each
            # request holds a (batch, row) reference into it
            for i, sl in enumerate(slices):
                self.stash[sl.req_id] = (x, i, sl.n_tokens)
        else:
            for sl in slices:
                self.stash.pop(sl.req_id, None)
        return loads, tokens

    def _stash_hidden(self, slices: List[PrefillSlice], b_pad: int) -> Array:
        """Boundary activations for a block_start > 0 group.  The common
        case — a layered cohort whose membership is unchanged since the
        previous group — reuses the stashed packed batch WHOLESALE (zero
        extra dispatches; this is why stash rows are stored as (batch,
        row) references).  After a mid-cohort preemption or under shape
        drift the surviving rows are regathered into a fresh batch."""
        entries = []
        for sl in slices:
            src, row, n_tok = self.stash[sl.req_id]
            assert n_tok == sl.n_tokens, "stash/token-range mismatch"
            entries.append((src, row))
        src0 = entries[0][0]
        rows = [row for _, row in entries]
        same_src = all(src is src0 for src, _ in entries)
        if same_src and rows == list(range(len(slices))) \
                and src0.shape[0] == b_pad:
            return src0
        p = max(src.shape[1] for src, _ in entries)
        if same_src:
            h = jnp.take(src0, jnp.asarray(rows, jnp.int32), axis=0)
            h = jnp.pad(h, ((0, b_pad - h.shape[0]),
                            (0, p - h.shape[1]), (0, 0)))
        else:
            parts = [jnp.pad(src[row], ((0, p - src.shape[1]), (0, 0)))
                     for src, row in entries]
            h = jnp.stack(parts)
            if h.shape[0] < b_pad:
                h = jnp.pad(h, ((0, b_pad - h.shape[0]), (0, 0), (0, 0)))
        self.n_dispatches += 1
        return h

    def _finish_prefill_slice(self, sl: PrefillSlice, tok: int) -> None:
        """Host bookkeeping for one executed slice (post-fetch): offsets,
        the emitted first token, EOS, and the decode handoff."""
        rid = sl.req_id
        slot = self._slot_of[rid]
        req = self.requests[rid]
        if sl.block_end == self.model.n_blocks:
            # tokens fully processed through the stack
            self.offsets[slot] = sl.token_end
        if sl.emits_first_token:
            if self.prefix_cache:
                # snapshot BEFORE _maybe_finish can free the allocator
                # state of an instantly-done (EOS-on-first-token) request
                self._snapshot_prefix_rows(rid, slot)
            self._record_token(rid, tok, first=True)
            self.offsets[slot] = req.prompt_len
            self.last_tok[slot] = tok
            # EOS can terminate on the very first token even when the
            # scheduler already moved the request to DECODE
            self._maybe_finish(rid, tok, after_first=True)
            if req.state == RequestState.DECODE:
                self.decoding[slot] = True

    def _snapshot_prefix_rows(self, rid: int, slot: int) -> None:
        """Slice the completed prompt's KV row once and file it under every
        shared-index chain this request's own pages serve (registration
        happened scheduler-side at plan time — ``owned_chains`` recovers
        the digests).  The slice is an immutable device snapshot (later
        donated calls build new cache buffers), so it stays valid for
        restores arbitrarily many iterations later."""
        chains = self.alloc.owned_chains(
            rid, self.requests[rid].cacheable_prompt)
        missing = [(d, depth) for d, depth in chains
                   if d not in self._prefix_rows]
        if not missing:
            return
        row = _slice_cache(self.cache, slot)
        ps = self.alloc.page_size
        for d, depth in missing:
            self._prefix_rows[d] = (row, depth * ps)

    def _history(self, rid: int) -> np.ndarray:
        """Full token sequence so far (recompute prompt + the generated
        tail not yet folded into it); its last element is last_tok and its
        length is offsets[slot] + 1."""
        req = self.requests[rid]
        tail = self.outputs[rid][req.n_folded:]
        return np.concatenate([self.prompts[rid],
                               np.asarray(tail, np.int32)])

    def _launch_verify(self, plan: IterationPlan):
        """Launch the drafting cohort's verify window (plus, in draft mode,
        the draft-model scan that feeds it); returns host row metadata
        (rid, slot, offset, k_eff), the ids that fell back to plain decode
        this iteration, and the device arrays for the one fetch.

        Window safety: per-row KV writes cover offset..offset+P-1 (the
        BUCKETED window — ``_write_cache`` drops out-of-range token writes,
        but a window that would spill past max_len has nowhere to store
        accepted tokens, so it must not launch).  Rows where the worst-case
        bucket does not fit fall back to plain decode."""
        budgets = sorted(plan.verify_len.items())
        p_worst = _bucket(self.spec_k + 1, minimum=2, cap=self.spec_k + 1)
        rows: List[Tuple[int, int, int, int, Optional[np.ndarray]]] = []
        skipped: List[int] = []
        for rid, k in budgets:
            slot = self._slot_of[rid]
            off = int(self.offsets[slot])
            if off + p_worst > self.max_len:
                skipped.append(rid)
                continue
            if self.spec_mode == "ngram":
                prop = self.drafter.propose(self._history(rid), k)
                if len(prop) == 0:
                    skipped.append(rid)
                    continue
                rows.append((rid, slot, off, len(prop),
                             prop.astype(np.int32)))
            else:
                rows.append((rid, slot, off, k, None))
        if not rows:
            return [], skipped, None

        k_max = max(k_eff for _, _, _, k_eff, _ in rows)
        p = _bucket(k_max + 1, minimum=2, cap=self.spec_k + 1)
        b_pad = _bucket(len(rows), minimum=1, cap=self.n_slots)
        tokens = np.zeros((b_pad, p), np.int32)
        valid = np.zeros((b_pad, p), bool)
        slots = np.full(b_pad, self.n_slots, np.int32)  # OOB: writes dropped
        offs = np.zeros(b_pad, np.int32)
        for i, (rid, slot, off, k_eff, prop) in enumerate(rows):
            tokens[i, 0] = self.last_tok[slot]
            if prop is not None:
                tokens[i, 1:1 + k_eff] = prop
            valid[i, :k_eff + 1] = True
            slots[i] = slot
            offs[i] = off

        props_dev = None
        if self.spec_mode == "draft":
            hists = [self._history(rid) for rid, _, _, _, _ in rows]
            p_hist = _bucket(max(len(h) for h in hists) + k_max,
                             cap=self.max_len + self.spec_k)
            hist_toks = np.zeros((b_pad, p_hist), np.int32)
            hist_lens = np.ones(b_pad, np.int32)
            for i, h in enumerate(hists):
                hist_toks[i, :len(h)] = h
                hist_lens[i] = len(h)
            draft_fn = self._get_draft_fn(k_max, b_pad, p_hist)
            props_dev = draft_fn(self.draft_params, jnp.asarray(hist_toks),
                                 jnp.asarray(hist_lens))
            self.n_dispatches += 1
            self.n_draft_dispatches += 1
            # splice the device proposals into the window without a sync
            tok_dev = jnp.asarray(tokens)
            tok_dev = jax.lax.dynamic_update_slice(
                tok_dev, props_dev.astype(jnp.int32), (0, 1))
        else:
            tok_dev = jnp.asarray(tokens)

        fn = self._get_verify_fn(b_pad, p)
        self.cache, loads, toks = fn(
            self.params, self.cache, tok_dev, jnp.asarray(valid),
            jnp.asarray(slots), jnp.asarray(offs))
        self.n_dispatches += 1
        self.n_verify_dispatches += 1
        return rows, skipped, (loads, toks, props_dev)

    def _finish_verify(self, rows, toks, props) -> None:
        """Host bookkeeping for the fetched verify grid: accept the
        matching draft prefix, emit accepted drafts plus the target's own
        next token, advance the committed offset (the rollback — stale KV
        past it is dead), and feed acceptance back to the scheduler."""
        for i, (rid, slot, off, k_eff, prop) in enumerate(rows):
            if prop is None:
                prop = np.asarray(props[i, :k_eff])
            tgt = np.asarray(toks[i])
            a = accepted_prefix(prop[:k_eff], tgt[:k_eff])
            emitted = [int(t) for t in prop[:a]] + [int(tgt[a])]
            if self.eos_token is not None:
                for j, t in enumerate(emitted):
                    if t == self.eos_token:
                        emitted = emitted[:j + 1]
                        break
            self.offsets[slot] = off + len(emitted)
            self.last_tok[slot] = emitted[-1]
            for t in emitted:
                self._record_token(rid, t, first=False)
            self.n_spec_proposed += k_eff
            self.n_spec_accepted += a
            self.last_verify_executed[rid] = k_eff
            self.scheduler.commit_speculation(
                rid, proposed=k_eff, accepted=a, extra=len(emitted) - 1,
                committed_len=int(self.offsets[slot]))
            self._maybe_finish(rid, emitted[-1])

    def _launch_decode(self, decode_ids: List[int]):
        """Launch the full-pool decode step; returns the slot→request map
        and DEVICE arrays (next tokens, expert-activation mask) for the
        end-of-iteration fetch.  Slots mid-prefill this iteration carry
        stale offsets — harmless, their rows are valid-masked no-ops."""
        tokens = np.zeros((self.n_slots, 1), np.int32)
        valid = np.zeros(self.n_slots, bool)
        slot_req = {}
        for rid in decode_ids:
            slot = self._slot_of[rid]
            tokens[slot, 0] = self.last_tok[slot]
            valid[slot] = True
            slot_req[slot] = rid
        next_tok, self.cache, loads = self._jit_decode(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(self.offsets), jnp.asarray(valid))
        self.n_dispatches += 1
        return slot_req, (next_tok, loads)

    def _record_token(self, rid: int, tok: int, *, first: bool) -> None:
        """Append the token to the request's output and report it as an
        event.  TIMESTAMPS are the ServingRuntime's job (one loop, one
        clock) — the engine only knows WHAT was emitted, not when."""
        self.outputs[rid].append(tok)
        self._step_events.append(TokenEvent(rid, tok, first=first))

    def _maybe_finish(self, rid: int, tok: int,
                      after_first: bool = False) -> None:
        req = self.requests[rid]
        eos = self.eos_token is not None and tok == self.eos_token
        if eos and req.state != RequestState.DONE:
            self.scheduler.finish(rid)
        if req.state == RequestState.DONE:
            slot = self._slot_of.pop(rid)
            self._free_slots.append(slot)
            self.decoding[slot] = False
            if self.alloc.owns(rid):        # EOS path frees via scheduler
                self.alloc.free(rid)
            self.stash.pop(rid, None)
            self._handoff_chunks.pop(rid, None)


class EngineHandoff:
    """``HandoffBridge`` over two Engines sharing one model + params (the
    real-execution realization of DESIGN.md §Disaggregated serving).  With
    ``streaming=True`` the source engine host-stages each completed layer
    group through its per-iteration fetch, so exports assemble from chunks
    with zero extra device syncs; ``streaming=False`` is the whole-prompt
    baseline (one full-row snapshot per migration).  The transfer is
    host-to-host, so ``ready_time == export_time`` — on real two-device
    deployments the simulator's link model prices what this path would
    cost."""

    def __init__(self, src: "Engine", dst: "Engine", *,
                 streaming: bool = True):
        if src.cfg is not dst.cfg and src.cfg != dst.cfg:
            raise ValueError("prefill/decode engines must share the model "
                             "config (KV layouts must match)")
        src.handoff_staging = streaming
        self.src = src
        self.dst = dst

    def decode_free_pages(self) -> int:
        return self.dst.alloc.n_free_pages

    def stage(self, plan, requests, t_end, duration) -> None:
        pass            # the engine stages inside execute_plan

    def export(self, req, now):
        from repro.serving.runtime import Migration
        payload = self.src.export_request(req.req_id)
        blob = [rows for _, _, rows in payload["chunks"]] \
            if payload["row"] is None else payload["row"]
        nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(blob))
        return Migration(req=req, payload=payload, export_time=now,
                         ready_time=now,
                         n_chunks=len(payload["chunks"]),
                         bytes_total=float(nbytes))

    def can_import(self, m) -> bool:
        return bool(self.dst._free_slots) \
            and self.dst.alloc.can_import(m.payload["kv"])

    def do_import(self, m, now) -> Dict[str, int]:
        imp = self.dst.import_request(m.payload)
        return {"linked_tokens": imp.linked_tokens,
                "moved_tokens": imp.moved_tokens}

    def drop(self, req_id: int) -> None:
        self.src._handoff_chunks.pop(req_id, None)

    def abort_export(self, m) -> None:
        """A link failure lost migration ``m``'s payload in flight.
        Reinstall its backend state on the prefill engine — the token
        buffers come back and the generated tail folds into the prompt
        array (the runtime already folded the Request itself to
        PREEMPTED) — so the whole-prompt retry re-prefills bit-
        identically.  The exported KV pages died with the link:
        ``export_pages`` already freed them from this pool, so the drop
        leaks nothing on either allocator."""
        req = m.req
        rid = req.req_id
        p = m.payload
        prompt = np.asarray(p["prompt"], np.int32)
        tail = req.prompt_len - len(prompt)
        if tail:
            prompt = np.concatenate(
                [prompt, np.asarray(p["outputs"][-tail:], np.int32)])
        assert len(prompt) == req.prompt_len, \
            (rid, len(prompt), req.prompt_len)
        self.src.requests[rid] = req
        self.src.prompts[rid] = prompt
        self.src.outputs[rid] = p["outputs"]
        if p["enc_frames"] is not None:
            self.src.enc_frames[rid] = p["enc_frames"]

    def return_to_prefill(self, req) -> None:
        rid = req.req_id
        for src_d, dst_d in ((self.dst.requests, self.src.requests),
                             (self.dst.prompts, self.src.prompts),
                             (self.dst.outputs, self.src.outputs)):
            dst_d[rid] = src_d.pop(rid)
        if rid in self.dst.enc_frames:
            self.src.enc_frames[rid] = self.dst.enc_frames.pop(rid)
