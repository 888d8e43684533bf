"""Jit-wrapped public entry points for the Pallas kernels.

Every wrapper lowers to Mosaic for the TPU unless the caller passes
``interpret=True``, which runs the kernel through the Pallas interpreter
(how the CPU tests validate the algorithms). There is no silent fallback:
a kernel called on a backend other than the TPU without ``interpret=True``
fails when it is lowered. Padding to tile multiples happens here so the
kernels stay shape-strict.

The model plugs these in via ``gmm_fn=`` (MoE) or by swapping the attention
reference path; correctness of the swap is covered by tests/test_kernels.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas,
                                            paged_verify_attention_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.moe_gmm_ragged import moe_gmm_ragged_pallas


def _pad_to(x: jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_blk",
                                             "kv_blk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, q_blk: int = 128,
                    kv_blk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Padding-safe wrapper: pads S to tile multiples; padded queries are
    discarded, padded keys are causally masked out (pos > any real q)."""
    s0 = q.shape[1]
    blk = max(q_blk, kv_blk)
    q_p, _ = _pad_to(q, 1, blk)
    k_p, _ = _pad_to(k, 1, blk)
    v_p, _ = _pad_to(v, 1, blk)
    if not causal and s0 != q_p.shape[1]:
        # non-causal needs an explicit mask for padded keys; window/causal
        # paths mask padding structurally.
        raise ValueError("non-causal flash attention requires S % tile == 0")
    out = flash_attention_pallas(q_p, k_p, v_p, causal=causal, window=window,
                                 q_blk=q_blk, kv_blk=kv_blk,
                                 interpret=interpret)
    return out[:, :s0]


@functools.partial(jax.jit, static_argnames=("window", "kv_blk", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *,
                     window: Optional[int] = None, kv_blk: int = 128,
                     interpret: bool = False) -> jax.Array:
    k_p, _ = _pad_to(k_cache, 1, kv_blk)
    v_p, _ = _pad_to(v_cache, 1, kv_blk)
    return decode_attention_pallas(q, k_p, v_p, lengths, window=window,
                                   kv_blk=kv_blk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           window: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """Decode attention over the PagedKVAllocator's scattered physical
    layout: ``block_tables`` (B, max_pages) holds each sequence's physical
    page ids (``PagedKVAllocator.block_table``, padded with 0 — any valid
    page id works, padded entries are masked by ``lengths``). Page count
    and size come from the pool shape; no padding is needed because pages
    are fixed-size by construction."""
    return paged_decode_attention_pallas(q, k_pages, v_pages, block_tables,
                                         lengths, window=window,
                                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           window: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """Speculative verify-k attention over the paged pool: ``q`` is a
    (B, W, H, hd) window of W = k+1 query tokens per sequence (oldest
    first) whose K/V have already been written; ``lengths`` counts valid
    KV INCLUDING the window.  One KV stream per sequence serves the whole
    window — the dispatch-amortization the speculative scheduler rides."""
    return paged_verify_attention_pallas(q, k_pages, v_pages, block_tables,
                                         lengths, window=window,
                                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("c_blk", "f_blk", "interpret"))
def moe_gmm(x, w_gate, w_up, w_down, *, c_blk: int = 128, f_blk: int = 128,
            interpret: bool = False) -> jax.Array:
    x_p, c0 = _pad_to(x, 1, min(c_blk, max(x.shape[1], 1)))
    wg_p, f0 = _pad_to(w_gate, 2, min(f_blk, max(w_gate.shape[2], 1)))
    wu_p, _ = _pad_to(w_up, 2, min(f_blk, max(w_up.shape[2], 1)))
    wd_p, _ = _pad_to(w_down, 1, min(f_blk, max(w_down.shape[1], 1)))
    out = moe_gmm_pallas(x_p, wg_p, wu_p, wd_p, c_blk=c_blk, f_blk=f_blk,
                         interpret=interpret)
    return out[:, :c0]


def gather_slot_rows(cache, slots: jax.Array):
    """Gather a slot VECTOR of KV-cache rows — one ``jnp.take`` per leaf
    instead of B full-tree dynamic slices (the engine's packed layer-group
    batches; DESIGN.md §Engine hot path).  Leaves are ``(reps, n_slots,
    ...)``; ``slots`` is ``(B,)`` int32.  Padding rows carry the
    out-of-range id ``n_slots``: ``mode="clip"`` reads the last real row
    (its output is masked downstream and its writeback is dropped by
    ``scatter_slot_rows``), never a NaN fill that could poison the batch's
    shared MoE dispatch."""
    return jax.tree_util.tree_map(
        lambda c: jnp.take(c, slots, axis=1, mode="clip"), cache)


def scatter_slot_rows(cache, rows, slots: jax.Array):
    """Scatter gathered rows back into the multi-slot cache with one
    ``.at[:, slots].set`` per leaf.  ``mode="drop"`` discards writes from
    padding rows (slot id ``n_slots`` is out of range), so a bucket-padded
    batch can never corrupt a live slot.  Real slot ids are distinct by
    construction (one resident request per slot), so the scatter has no
    duplicate-index races."""
    return jax.tree_util.tree_map(
        lambda f, r: f.at[:, slots].set(r.astype(f.dtype), mode="drop"),
        cache, rows)


def fetch_expert_ids(tile_expert: jax.Array, n_experts: int) -> jax.Array:
    """Replace sentinel tile ids (== n_experts) with the last active expert
    id (forward fill), so skipped tiles drive the weight DMA at an already-
    resident block instead of fetching a fresh one. All-sentinel inputs
    (fully masked batch) fall back to expert 0."""
    n_tiles = tile_expert.shape[0]
    idx = jnp.where(tile_expert < n_experts,
                    jnp.arange(n_tiles, dtype=jnp.int32), -1)
    last = jax.lax.cummax(idx)
    return jnp.where(last >= 0, tile_expert[jnp.maximum(last, 0)],
                     0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("m_blk", "f_blk", "interpret"))
def moe_gmm_ragged(rows, w_gate, w_up, w_down, tile_expert, *,
                   m_blk: int = 128, f_blk: int = 128,
                   interpret: bool = False) -> jax.Array:
    """Ragged grouped matmul: rows (n_rows, d) sorted by expert with
    tile-aligned groups, tile_expert (n_rows/m_blk,) the per-tile owner
    (n_experts = sentinel). Pads F to the tile multiple; rows must already
    be m_blk-aligned (models.moe.ragged_dispatch pads them)."""
    assert rows.shape[0] % m_blk == 0, (rows.shape, m_blk)
    n_experts = w_gate.shape[0]
    wg_p, f0 = _pad_to(w_gate, 2, min(f_blk, max(w_gate.shape[2], 1)))
    wu_p, _ = _pad_to(w_up, 2, min(f_blk, max(w_up.shape[2], 1)))
    wd_p, _ = _pad_to(w_down, 1, min(f_blk, max(w_down.shape[1], 1)))
    fetch = fetch_expert_ids(tile_expert, n_experts)
    return moe_gmm_ragged_pallas(rows, wg_p, wu_p, wd_p, tile_expert, fetch,
                                 m_blk=m_blk, f_blk=f_blk,
                                 interpret=interpret)


def model_gmm_fn(cfg=None, *, interpret: bool = False):
    """Adapter matching models.moe.apply_moe's dense ``gmm_fn`` contract."""
    def fn(cfg_, p, buf):
        return moe_gmm(buf, p["w_gate"], p["w_up"], p["w_down"],
                       interpret=interpret)
    fn.ragged = False
    return fn


def ragged_gmm_fn(cfg=None, *, interpret: bool = False):
    """Adapter matching models.moe.apply_moe's ragged ``gmm_fn`` contract
    (moe_dispatch="ragged"): receives the expert-sorted row buffer plus the
    per-tile expert metadata and runs the scalar-prefetch Pallas kernel."""
    def fn(cfg_, p, rows, tile_expert, m_blk):
        return moe_gmm_ragged(rows, p["w_gate"], p["w_up"], p["w_down"],
                              tile_expert, m_blk=m_blk, interpret=interpret)
    fn.ragged = True
    return fn
