"""Mixture-of-Experts FFN: top-k router, two dispatch layouts, shared
experts, and — central to the paper — *expert-load accounting*.

Dispatch layouts (``moe_dispatch``):

- ``"dense"`` — sort-based capacity dispatch into a dense (E, C, d) buffer;
  the per-expert GEMM is a batched matmul whose leading axis can be sharded
  over the ``model`` mesh axis. GShard-style capacity drops in training;
  ``dropless=True`` sizes C = T (worst case), which computes/streams
  ``E / top_k`` × more rows than were actually routed (16× for
  qwen3-30b-a3b, E=128 top-8).

- ``"ragged"`` — MegaBlocks-style dropless dispatch: assignments are sorted
  by expert id into ONE flat (rows, d) buffer whose per-expert groups are
  padded to row-tile boundaries, so compute and HBM traffic scale with
  ``sum(expert_counts)`` (+ ≤ one tile of alignment padding per active
  expert), never with ``E × T``, and empty experts cost nothing. The Pallas
  ``moe_gmm_ragged`` kernel consumes this layout with scalar-prefetched
  per-tile expert ids, so its weight traffic is ``active_experts ×
  bytes_per_expert`` — the exact quantity the serving engine's
  ``expert_load_bytes`` counter measures (§5.4, Table 7). Ragged dispatch
  never drops an assignment (it is inherently dropless); the serving engine
  uses it by default. Measured traffic/compute ratio vs the dense dropless
  buffer (benchmarks/gmm_ragged_vs_dense.py): GMM rows shrink to
  ``top_k/E`` once coverage saturates — 0.064× at T=32k for qwen3-30b-a3b
  (E=128, top-8) — and the CPU jnp data path runs ~4–16× faster at
  T=2048 (top_k 8 → 1, E=32).

Both layouts run under ``shard_map`` expert parallelism: the ragged "a2a"
path moves per-destination-shard ragged groups (static worst-case chunk
size, per-source counts communicated alongside) through the same pair of
all-to-alls as the dense path; "psum" keeps tokens replicated over the
expert axis and combines with one psum.

Every forward returns an ``aux`` dict containing, per MoE block:
  - ``expert_counts`` (E,) int32 — tokens routed to each expert,
  - ``active_experts`` ()  int32 — #experts with >=1 token: multiplied by
    bytes-per-expert this is exactly the paper's "expert weight load bytes"
    counter (§5.4, Table 7),
  - ``aux_loss`` — Switch-style load-balance loss (training).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import _dense
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.sharding.partition import active_context

Array = jax.Array


def init_moe(cfg: ModelConfig, key) -> dict:
    e = cfg.moe
    d, f = cfg.d_model, e.expert_d_ff
    dt = cfg.param_dtype
    ks = jax.random.split(key, 8)
    p = {
        "router": _dense(ks[0], (d, e.n_experts), dt, scale=0.02),
        "w_gate": _dense(ks[1], (e.n_experts, d, f), dt),
        "w_up": _dense(ks[2], (e.n_experts, d, f), dt),
        "w_down": _dense(ks[3], (e.n_experts, f, d), dt),
    }
    if e.n_shared_experts:
        fs = e.shared_d_ff * e.n_shared_experts
        p["shared"] = {
            "w_gate": _dense(ks[4], (d, fs), dt),
            "w_up": _dense(ks[5], (d, fs), dt),
            "w_down": _dense(ks[6], (fs, d), dt),
        }
    return p


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    e = cfg.moe
    c = int(math.ceil(n_tokens * e.top_k / e.n_experts * e.capacity_factor))
    c = max(c, e.top_k)
    # round up to an MXU-friendly multiple when big enough
    if c > 8:
        c = (c + 7) // 8 * 8
    return min(c, n_tokens)


def route(cfg: ModelConfig, p, x_flat: Array) -> Tuple[Array, Array, Array]:
    """x_flat: (T, d) -> (expert_idx (T,k), weights (T,k), probs (T,E))."""
    e = cfg.moe
    logits = (x_flat.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, e.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)        # Qwen3/DeepSeek renorm
    return idx, w, probs


def dispatch_indices(expert_idx: Array, n_experts: int, cap: int):
    """Sort-based ranking: for each (token,k) assignment compute its slot in
    the (E, C) capacity buffer; assignments beyond capacity are dropped.

    Assignments with expert id == n_experts (masked padding) are dropped.
    Returns (slot (T*k,), keep (T*k,), counts (E,))."""
    flat = expert_idx.reshape(-1)                      # (T*k,)
    counts = jnp.bincount(flat, length=n_experts)      # (E,) — excludes id==E
    order = jnp.argsort(flat, stable=True)             # sorted assignment ids
    # position within the expert group for each sorted element
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    sorted_expert = flat[order]
    pos_sorted = (jnp.arange(flat.shape[0], dtype=jnp.int32)
                  - starts[jnp.minimum(sorted_expert, n_experts - 1)])
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
    keep = (pos < cap) & (flat < n_experts)
    slot = flat * cap + jnp.minimum(pos, cap - 1)
    return slot, keep, counts


def _dispatch_gmm_combine(cfg: ModelConfig, p, xf: Array, idx: Array,
                          w: Array, cap: int, n_local: int, gmm_fn):
    """Core MoE data path over n_local experts: gather tokens into the
    (E, C, d) capacity buffer, batched per-expert GEMM, weighted combine.
    idx entries >= n_local are dropped (masking / non-local experts).

    Layout note (§Perf iteration 4): the buffer is built by ONE gather of
    (E*C, d) via an inverted slot->token map, and the combine reads one
    (t, d) gather per top-k slot — the (t*k, d) duplicated-token tensor of
    the naive formulation (8x token bytes, with fp32 converts) never
    materializes. This halved the memory roofline term of qwen3-moe
    prefill_32k."""
    e = cfg.moe
    t, d = xf.shape
    slot, keep, counts = dispatch_indices(idx, n_local, cap)
    tok_ids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), e.top_k)
    # invert: which token feeds each (expert, slot) cell (0 if unused —
    # the row is computed by the GEMM but never read back by the combine)
    tok_of_slot = jnp.zeros((n_local * cap,), jnp.int32).at[
        jnp.where(keep, slot, n_local * cap).astype(jnp.int32)
    ].set(tok_ids, mode="drop")
    buf = xf[tok_of_slot].reshape(n_local, cap, d)
    if gmm_fn is None:
        gmm_fn = expert_ffn_ref
    y = gmm_fn(cfg, p, buf)                               # (E_loc, C, d)
    y_flat = y.reshape(n_local * cap, d)

    slot_k = slot.reshape(t, e.top_k)
    keep_k = keep.reshape(t, e.top_k)
    out = jnp.zeros((t, d), y_flat.dtype)
    for i in range(e.top_k):                              # static, <= 8
        contrib = y_flat[slot_k[:, i]]                    # (t, d)
        gate = jnp.where(keep_k[:, i], w[:, i], 0.0)
        out = out + contrib * gate[:, None].astype(contrib.dtype)
    dropped = jnp.sum((idx.reshape(-1) < n_local) & ~keep)
    return out, counts, dropped


def ragged_tile_rows(n_assign: int, n_experts: int,
                     m_blk_max: int = 128) -> Tuple[int, int]:
    """Static (row-tile size, padded row count) for the ragged buffer.

    The tile size tracks the ceil-average expert load so tiny batches
    (decode) don't pay E × (m_blk - 1) alignment rows; the row count is the
    worst case ``sum_e ceil(count_e / m_blk) * m_blk`` — at most one tile of
    padding per expert — rounded up to a whole tile."""
    avg = max(1, -(-n_assign // max(n_experts, 1)))
    m_blk = 8
    while m_blk < min(avg, m_blk_max):
        m_blk *= 2
    rows = n_assign + n_experts * (m_blk - 1)
    rows = -(-rows // m_blk) * m_blk
    return m_blk, rows


def _group_ranks(flat: Array, counts: Array, n_experts: int) -> Array:
    """Rank of each flat assignment within its expert group (stable sort
    order). Entries with id >= n_experts get garbage ranks — callers mask
    them via ``keep``."""
    a = flat.shape[0]
    order = jnp.argsort(flat, stable=True)
    sorted_expert = flat[order]
    gstarts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos_sorted = (jnp.arange(a, dtype=jnp.int32)
                  - gstarts[jnp.minimum(sorted_expert, n_experts - 1)])
    return jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)


def _combine_topk(y_flat: Array, slot: Array, keep: Array, w: Array) -> Array:
    """Weighted combine out[t] = sum_i w[t,i] * y_flat[slot[t,i]], masked
    by keep (dropped/masked assignments contribute nothing)."""
    t, top_k = w.shape
    n_rows = y_flat.shape[0]
    slot_k = slot.reshape(t, top_k)
    keep_k = keep.reshape(t, top_k)
    out = jnp.zeros((t, y_flat.shape[1]), y_flat.dtype)
    for i in range(top_k):                             # static, <= 8
        contrib = y_flat[jnp.minimum(slot_k[:, i], n_rows - 1)]
        gate = jnp.where(keep_k[:, i], w[:, i], 0.0)
        out = out + contrib * gate[:, None].astype(contrib.dtype)
    return out


def ragged_dispatch_indices(expert_idx: Array, n_experts: int, m_blk: int,
                            n_rows: int):
    """Ragged tile-aligned ranking: for each (token, k) assignment compute
    its row in the expert-sorted flat buffer whose per-expert groups start
    on ``m_blk`` boundaries. Assignments with expert id == n_experts
    (masked padding) get the out-of-range row ``n_rows`` and keep=False;
    nothing else is ever dropped.

    Returns (slot (T*k,), keep (T*k,), counts (E,),
    tile_expert (n_rows/m_blk,) — the expert owning each row tile, or the
    sentinel ``n_experts`` for alignment-padding tiles)."""
    flat = expert_idx.reshape(-1).astype(jnp.int32)    # (A,)
    counts = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
    padded = (-(-counts // m_blk) * m_blk).astype(jnp.int32)
    pcum = jnp.cumsum(padded).astype(jnp.int32)        # inclusive
    starts = pcum - padded                             # tile-aligned starts
    pos = _group_ranks(flat, counts, n_experts)
    keep = flat < n_experts
    slot = jnp.where(keep, starts[jnp.minimum(flat, n_experts - 1)] + pos,
                     n_rows).astype(jnp.int32)
    # per-tile owner: the expert whose padded group covers the tile's first
    # row (groups are tile-aligned, so one owner per tile); rows beyond the
    # last group -> sentinel n_experts
    row0 = jnp.arange(n_rows // m_blk, dtype=jnp.int32) * m_blk
    tile_expert = jnp.searchsorted(pcum, row0,
                                   side="right").astype(jnp.int32)
    return slot, keep, counts, tile_expert


def ragged_ffn_ref(cfg: ModelConfig, p, rows: Array, tile_expert: Array,
                   m_blk: int, layer=None) -> Array:
    """jnp fallback for the ragged grouped matmul (same contract as
    kernels/ops.ragged_gmm_fn): per row tile, the owning expert's fused
    SwiGLU FFN; sentinel tiles produce zeros. Thin adapter over the single
    oracle in kernels/ref.py — its per-tile weight gather mirrors the
    kernel's scalar-prefetched DMA (only touched experts' weights read).
    With ``layer``, ``p``'s expert weights are the layer stack and are
    read in place at that layer."""
    from repro.kernels.ref import moe_gmm_ragged_ref
    return moe_gmm_ragged_ref(rows, p["w_gate"], p["w_up"], p["w_down"],
                              tile_expert, m_blk, layer=layer)


def _dispatch_gmm_combine_ragged(cfg: ModelConfig, p, xf: Array, idx: Array,
                                 w: Array, n_local: int, gmm_fn, layer=None):
    """Ragged counterpart of ``_dispatch_gmm_combine``: gather tokens into
    the expert-sorted tile-aligned (rows, d) buffer, ragged grouped GEMM,
    weighted combine. Inherently dropless — rows scale with the routed
    assignments, not E × T. idx entries >= n_local are masked out.
    ``layer`` (jnp tile loop only) indexes stacked expert weights."""
    e = cfg.moe
    t, d = xf.shape
    m_blk, n_rows = ragged_tile_rows(t * e.top_k, n_local)
    slot, keep, counts, tile_expert = ragged_dispatch_indices(
        idx, n_local, m_blk, n_rows)
    tok_ids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), e.top_k)
    tok_of_row = jnp.zeros((n_rows,), jnp.int32).at[
        jnp.where(keep, slot, n_rows)
    ].set(tok_ids, mode="drop")
    rows = xf[tok_of_row]                              # (n_rows, d)
    if layer is None:
        y = (gmm_fn or ragged_ffn_ref)(cfg, p, rows, tile_expert, m_blk)
    else:
        y = ragged_ffn_ref(cfg, p, rows, tile_expert, m_blk, layer=layer)
    out = _combine_topk(y, slot, keep, w)
    return out, counts, jnp.zeros((), jnp.int32)


def _sharded_moe_plan(cfg: ModelConfig, b: int, s: int):
    """If a sharding context is active and the shapes divide, return the
    shard_map plan for the expert-parallel MoE path. mode "a2a" partitions
    tokens over batch x model and moves the capacity buffer by all-to-all
    (zero gathers, zero psums); mode "psum" (s not divisible by the TP
    degree, e.g. decode s=1) replicates tokens over the expert axis and
    psums the combine."""
    ctx = active_context()
    if ctx is None:
        return None
    mesh, rules = ctx
    tp = rules.get("tp") or ()
    batch = rules.get("batch") or ()
    tp_n = 1
    for a in tp:
        tp_n *= mesh.shape.get(a, 1)
    b_n = 1
    for a in batch:
        b_n *= mesh.shape.get(a, 1)
    e = cfg.moe
    if tp_n <= 1 or b_n <= 1:
        return None
    if e.n_experts % tp_n or (b * s) % b_n:
        return None
    # Gate by regime: with few tokens (decode) most experts are idle and
    # the XLA-auto path streams only the routed experts' weights; the
    # shard_map region would gather every device's full expert block per
    # layer (measured: qwen3-moe decode_32k collective 0.04 -> 3.2 s when
    # ungated). Threshold: expected tokens/expert >= 16 (~60 % coverage).
    if (b * s) * e.top_k < 16 * e.n_experts:
        return None
    mode = "a2a" if (b % b_n == 0 and s % tp_n == 0
                     and len(tp) == 1) else "psum"
    return mesh, tuple(batch), tuple(tp), b_n, tp_n, mode


# the routed experts' weights, which a layer stack can hand over whole
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def reads_experts_in_place(cfg: ModelConfig, b: int, s: int, gmm_fn,
                           moe_dispatch: str) -> bool:
    """Whether ``apply_moe`` over a (b, s) input reads its routed experts
    through the jnp tile loop outside shard_map, so that it can take the
    layer stack's expert weights whole together with a ``layer`` index."""
    return (moe_dispatch == "ragged" and gmm_fn is None
            and _sharded_moe_plan(cfg, b, s) is None)


def apply_moe(cfg: ModelConfig, p, x: Array, *,
              valid: Optional[Array] = None,
              gmm_fn=None, dropless: bool = False,
              moe_dispatch: str = "dense",
              layer=None) -> Tuple[Array, dict]:
    """x: (B, S, d) -> (out (B,S,d), aux). ``gmm_fn`` optionally overrides the
    per-expert GEMM (the Pallas kernels plug in here; a dense gmm_fn takes
    the (E, C, d) capacity buffer, a ragged one — marked ``fn.ragged=True``
    — takes the expert-sorted (rows, d) buffer + per-tile expert ids).
    ``valid`` (B, S) masks padding tokens out of routing, capacity and the
    expert-load counters (they contribute nothing and load nothing).

    ``moe_dispatch`` picks the layout: "dense" (capacity buffer) or
    "ragged" (tile-aligned sorted buffer; inherently dropless, compute and
    traffic scale with the routed work — the serving engine's default).

    ``dropless=True`` sizes the dense capacity buffer to the worst case
    (every token on one expert) so no assignment is ever dropped — the
    serving engine uses this so outputs are schedule-invariant (vLLM-style
    serving never drops); training keeps GShard capacity dispatch.

    DISTRIBUTION (§Perf iteration 2): when a sharding context is active the
    routed-expert path runs under ``shard_map`` — tokens stay on their batch
    shard (GShard group-wise local dispatch with per-group capacity),
    experts are partitioned over the ``model`` axis, each device's expert
    weight block is gathered once per layer at the shard_map boundary, and
    one psum over the expert axis combines contributions. This replaces the
    XLA-auto path that re-materialized the global (E, C, d) capacity buffer
    with per-layer all-gathers (13.3 TB/device on qwen3-moe prefill_32k).

    ``layer``: ``p``'s ``EXPERT_LEAVES`` are the segment's layer stacks
    (reps, E, ...) and the tile loop reads them at this layer, in place
    (only where ``reads_experts_in_place``)."""
    e = cfg.moe
    if moe_dispatch not in ("dense", "ragged"):
        raise ValueError(f"unknown moe_dispatch {moe_dispatch!r}")
    if gmm_fn is not None and getattr(gmm_fn, "ragged", None) is not None \
            and gmm_fn.ragged != (moe_dispatch == "ragged"):
        raise ValueError(
            f"gmm_fn implements the "
            f"{'ragged' if gmm_fn.ragged else 'dense'} contract but "
            f"moe_dispatch={moe_dispatch!r}")
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    vflat = valid.reshape(t) if valid is not None else None

    plan = _sharded_moe_plan(cfg, b, s)
    if plan is not None:
        out, counts, dropped, pbar = _apply_moe_shard_map(
            cfg, p, xf, vflat, gmm_fn, dropless, plan, moe_dispatch)
    else:
        with jax.named_scope("moe.route"):
            idx, w, probs = route(cfg, p, xf)
            if vflat is not None:
                # invalid tokens route out-of-bounds => dropped from dispatch
                idx = jnp.where(vflat[:, None], idx, e.n_experts)
        with jax.named_scope("moe.experts"):
            if moe_dispatch == "ragged":
                out, counts, dropped = _dispatch_gmm_combine_ragged(
                    cfg, p, xf, idx, w, e.n_experts, gmm_fn, layer)
            else:
                cap = t if dropless else capacity(cfg, t)
                out, counts, dropped = _dispatch_gmm_combine(
                    cfg, p, xf, idx, w, cap, e.n_experts, gmm_fn)
        pbar = jnp.mean(probs, axis=0)

    if e.n_shared_experts:
        sp = p["shared"]
        g = xf @ sp["w_gate"]
        u = xf @ sp["w_up"]
        out = out + (jax.nn.silu(g) * u) @ sp["w_down"]

    # Load-balance aux loss (Switch): E * sum_i f_i * P_i
    f = counts.astype(jnp.float32) / jnp.maximum(t * e.top_k, 1)
    aux_loss = e.n_experts * jnp.sum(f * pbar) * e.router_aux_coef

    aux = {
        "expert_counts": counts.astype(jnp.int32),
        "active_experts": jnp.sum(counts > 0).astype(jnp.int32),
        "dropped": dropped.astype(jnp.int32),
        "aux_loss": aux_loss,
    }
    return out.reshape(b, s, d).astype(x.dtype), aux


def _apply_moe_shard_map(cfg: ModelConfig, p, xf: Array,
                         vflat: Optional[Array], gmm_fn, dropless: bool,
                         plan, moe_dispatch: str = "dense"):
    """Expert-parallel MoE under shard_map (see apply_moe docstring).

    mode "a2a" (§Perf iteration 7): tokens arrive partitioned over
    (batch x model) — aligned with the sequence-parallel residual, so no
    boundary gather. Each device dispatches its t/256 tokens into a
    (E, cap, d) buffer; one all_to_all over the expert axis turns it into
    (E_loc, cap * tp, d) for the local GEMM; the reverse all_to_all brings
    each token's expert outputs home; the combine is local. The only
    per-layer MoE collectives are the two all-to-alls.

    Ragged a2a (moe_dispatch="ragged"): each device lays its assignments
    out destination-shard-major — per shard j, a tile-aligned ragged buffer
    of the tokens routed to shard j's experts, statically sized to the
    worst case (all local assignments on one shard). One symmetric
    all_to_all moves the (tp, S_pair, d) chunk stack; the per-source padded
    group sizes travel through a second (tiny) all_to_all so the receiver
    can rebuild the per-tile expert metadata; the ragged GEMM skips the
    slack tiles, so compute still scales with the routed work even though
    the wire format is worst-case sized. The reverse all_to_all brings each
    source's rows home and the combine is local, exactly as in dense mode.

    mode "psum": tokens replicated over the expert axis; each shard
    processes its local experts (dense capacity or ragged dispatch) and one
    psum combines (used when the sequence does not divide the TP degree,
    e.g. single-token decode)."""
    mesh, batch_axes, expert_axes, b_n, tp_n, mode = plan
    e = cfg.moe
    e_loc = e.n_experts // tp_n
    t = xf.shape[0]

    def route_local(router, xr, vr):
        logits = xr.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, e.top_k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        if vr is not None:
            idx = jnp.where(vr[:, None], idx, e.n_experts)
        return idx, w, probs

    def tele(counts_l, dropped_l, probs, all_axes):
        counts = jax.lax.psum(counts_l, all_axes)
        dropped = jax.lax.psum(dropped_l, all_axes)
        pbar = jax.lax.psum(jnp.sum(probs, axis=0), all_axes) / t
        return counts, dropped, pbar

    if mode == "a2a":
        t_loc = t // (b_n * tp_n)
        tok_axes = batch_axes + expert_axes
        a2a_axis = expert_axes[0]

        if moe_dispatch == "ragged":
            a_loc = t_loc * e.top_k
            m_blk, s_pair = ragged_tile_rows(a_loc, e_loc)
            n_send = tp_n * s_pair

            def body(router, wg, wu, wd, xr, vr):
                d = xr.shape[1]
                idx, w, probs = route_local(router, xr, vr)
                flat = idx.reshape(-1).astype(jnp.int32)       # global ids
                counts_l = jnp.bincount(
                    flat, length=e.n_experts).astype(jnp.int32)
                padded = (-(-counts_l // m_blk) * m_blk).astype(jnp.int32)
                # destination-shard-major layout: shard j's groups live in
                # chunk j of the send buffer, tile-aligned within the chunk
                p2 = padded.reshape(tp_n, e_loc)
                pcum_l = jnp.cumsum(p2, axis=1).astype(jnp.int32)
                starts = ((pcum_l - p2)
                          + jnp.arange(tp_n, dtype=jnp.int32)[:, None]
                          * s_pair).reshape(-1)
                pos = _group_ranks(flat, counts_l, e.n_experts)
                keep = flat < e.n_experts
                slot = jnp.where(
                    keep,
                    starts[jnp.minimum(flat, e.n_experts - 1)] + pos,
                    n_send).astype(jnp.int32)
                tok_ids = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32),
                                     e.top_k)
                tok_of_row = jnp.zeros((n_send,), jnp.int32).at[
                    jnp.where(keep, slot, n_send)
                ].set(tok_ids, mode="drop")
                buf = xr[tok_of_row].reshape(tp_n, s_pair, d)
                # dispatch a2a: chunk j -> device j; symmetric layout, the
                # receiver holds one s_pair chunk per source shard
                buf = jax.lax.all_to_all(buf, a2a_axis, split_axis=0,
                                         concat_axis=0, tiled=True)
                # per-source padded group sizes for MY local experts
                sizes = jax.lax.all_to_all(p2, a2a_axis, split_axis=0,
                                           concat_axis=0, tiled=True)
                ccum = jnp.cumsum(sizes, axis=1).astype(jnp.int32)
                r0 = jnp.arange(s_pair // m_blk, dtype=jnp.int32) * m_blk
                tile_expert = jax.vmap(
                    lambda c: jnp.searchsorted(c, r0, side="right"))(
                        ccum).reshape(-1).astype(jnp.int32)
                rows = buf.reshape(n_send, d)
                pl_ = {"w_gate": wg, "w_up": wu, "w_down": wd}
                y = (gmm_fn or ragged_ffn_ref)(cfg, pl_, rows, tile_expert,
                                               m_blk)
                # combine a2a: each source's rows come home in place
                y = jax.lax.all_to_all(y.reshape(tp_n, s_pair, d), a2a_axis,
                                       split_axis=0, concat_axis=0,
                                       tiled=True)
                out = _combine_topk(y.reshape(n_send, d), slot, keep, w)
                counts, dropped, pbar = tele(counts_l,
                                             jnp.zeros((), jnp.int32),
                                             probs, tok_axes)
                return out, counts, dropped, pbar

            e_spec = P(expert_axes, None, None)
            in_specs = (P(), e_spec, e_spec, e_spec, P(tok_axes, None),
                        P(tok_axes) if vflat is not None else P())
            out_specs = (P(tok_axes, None), P(), P(), P())
            if vflat is None:
                fn = shard_map(lambda r, g_, u_, d_, xr, _:
                               body(r, g_, u_, d_, xr, None), mesh=mesh,
                               in_specs=in_specs, out_specs=out_specs,
                               check_vma=False)
            else:
                fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
            v_arg = vflat if vflat is not None else jnp.ones((), bool)
            return fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], xf,
                      v_arg)

        def body(router, wg, wu, wd, xr, vr):
            idx, w, probs = route_local(router, xr, vr)
            cap = t_loc if dropless else capacity(cfg, t_loc)
            slot, keep, counts_l = dispatch_indices(idx, e.n_experts, cap)
            tok_ids = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32),
                                 e.top_k)
            tok_of_slot = jnp.zeros((e.n_experts * cap,), jnp.int32).at[
                jnp.where(keep, slot, e.n_experts * cap).astype(jnp.int32)
            ].set(tok_ids, mode="drop")
            buf = xr[tok_of_slot].reshape(e.n_experts, cap, d := xr.shape[1])
            # dispatch all-to-all: (E, cap, d) -> (E_loc, cap * tp, d)
            buf = jax.lax.all_to_all(buf, a2a_axis, split_axis=0,
                                     concat_axis=1, tiled=True)
            y = (gmm_fn or expert_ffn_ref)(
                cfg, {"w_gate": wg, "w_up": wu, "w_down": wd}, buf)
            # combine all-to-all: back to (E, cap, d), token-major
            y = jax.lax.all_to_all(y, a2a_axis, split_axis=1,
                                   concat_axis=0, tiled=True)
            y_flat = y.reshape(e.n_experts * cap, d)
            slot_k = slot.reshape(t_loc, e.top_k)
            keep_k = keep.reshape(t_loc, e.top_k)
            out = jnp.zeros((t_loc, d), y_flat.dtype)
            for i in range(e.top_k):
                contrib = y_flat[slot_k[:, i]]
                gate = jnp.where(keep_k[:, i], w[:, i], 0.0)
                out = out + contrib * gate[:, None].astype(contrib.dtype)
            dropped_l = jnp.sum((idx.reshape(-1) < e.n_experts) & ~keep)
            counts, dropped, pbar = tele(counts_l, dropped_l, probs,
                                         tok_axes)
            return out, counts, dropped, pbar

        e_spec = P(expert_axes, None, None)
        in_specs = (P(), e_spec, e_spec, e_spec, P(tok_axes, None),
                    P(tok_axes) if vflat is not None else P())
        out_specs = (P(tok_axes, None), P(), P(), P())
        if vflat is None:
            fn = shard_map(lambda r, g_, u_, d_, xr, _:
                           body(r, g_, u_, d_, xr, None), mesh=mesh,
                           in_specs=in_specs, out_specs=out_specs,
                           check_vma=False)
        else:
            fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        v_arg = vflat if vflat is not None else jnp.ones((), bool)
        return fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], xf,
                  v_arg)

    # -- mode == "psum" -----------------------------------------------------
    t_loc = t // b_n

    def body(router, wg, wu, wd, xr, vr):
        # per-device: xr (t_loc, d); wg/wu/wd hold this shard's e_loc
        # experts with FULL inner dim (gathered at the shard_map boundary).
        j = jax.lax.axis_index(expert_axes)
        idx, w, probs = route_local(router, xr, vr)
        # keep only this shard's experts; others become the drop sentinel
        local = (idx >= j * e_loc) & (idx < (j + 1) * e_loc)
        idx_l = jnp.where(local, idx - j * e_loc, e_loc)
        pl = {"w_gate": wg, "w_up": wu, "w_down": wd}
        if moe_dispatch == "ragged":
            out, counts_l, dropped_l = _dispatch_gmm_combine_ragged(
                cfg, pl, xr, idx_l, w, e_loc, gmm_fn)
        else:
            cap = t_loc if dropless else capacity(cfg, t_loc)
            out, counts_l, dropped_l = _dispatch_gmm_combine(
                cfg, pl, xr, idx_l, w, cap, e_loc, gmm_fn)
        # combine expert contributions across the expert axis
        out = jax.lax.psum(out, expert_axes)
        # counts_l covers this shard's experts only; assemble the global
        # (E,) vector, then sum token groups
        counts = jax.lax.all_gather(counts_l, expert_axes, tiled=True)
        counts = jax.lax.psum(counts, batch_axes)
        dropped = jax.lax.psum(jax.lax.psum(dropped_l, expert_axes),
                               batch_axes)
        pbar = jax.lax.psum(jnp.sum(probs, axis=0), batch_axes) / t
        return out, counts, dropped, pbar

    e_spec = P(expert_axes, None, None)
    in_specs = (P(), e_spec, e_spec, e_spec, P(batch_axes, None),
                P(batch_axes) if vflat is not None else P())
    out_specs = (P(batch_axes, None), P(), P(), P())
    if vflat is None:
        fn = shard_map(lambda r, g_, u_, d_, xr, _:
                       body(r, g_, u_, d_, xr, None), mesh=mesh,
                       in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    else:
        fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    v_arg = vflat if vflat is not None else jnp.ones((), bool)
    return fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], xf, v_arg)


def expert_ffn_ref(cfg: ModelConfig, p, buf: Array) -> Array:
    """Batched per-expert SwiGLU FFN: (E, C, d) -> (E, C, d). This is the
    pure-jnp oracle; kernels/moe_gmm.py implements the same contract."""
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(buf.dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(buf.dtype))


def empty_moe_aux(cfg: ModelConfig) -> dict:
    """Aux pytree with the same structure for non-MoE blocks so scans over
    heterogeneous stacks stay pytree-uniform."""
    n = max(cfg.moe.n_experts, 1)
    return {
        "expert_counts": jnp.zeros((n,), jnp.int32),
        "active_experts": jnp.zeros((), jnp.int32),
        "dropped": jnp.zeros((), jnp.int32),
        "aux_loss": jnp.zeros((), jnp.float32),
    }
