"""Pure-jnp oracles for every Pallas kernel (the allclose targets for the
per-kernel shape/dtype sweep tests)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: Optional[int] = None,
                        scale: Optional[float] = None) -> jax.Array:
    """q: (B,S,H,hd); k/v: (B,S,Hkv,hd) -> (B,S,H,hd)."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    # g-major GQA grouping (q head h -> kv head h % hkv), matching the
    # model's sharding-friendly convention.
    qf = q.reshape(b, s, g, hkv, hd).astype(jnp.float32) * scale
    scores = jnp.einsum("bqgkd,bskd->bgkqs", qf, k.astype(jnp.float32))
    pos = jnp.arange(s)
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= pos[None, :] <= pos[:, None]
    if window is not None:
        mask &= pos[None, :] > pos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgkqs,bskd->bqgkd", w, v.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         lengths: jax.Array, *,
                         window: Optional[int] = None,
                         scale: Optional[float] = None) -> jax.Array:
    """q: (B,H,hd); caches: (B,S,Hkv,hd); lengths: (B,) -> (B,H,hd)."""
    b, h, hd = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf = q.reshape(b, g, hkv, hd).astype(jnp.float32) * scale
    scores = jnp.einsum("bgkd,bskd->bgks", qf, k_cache.astype(jnp.float32))
    pos = jnp.arange(s)
    mask = pos[None, :] < lengths[:, None]
    if window is not None:
        mask &= pos[None, :] >= (lengths[:, None] - window)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgks,bskd->bgkd", w, v_cache.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


def paged_decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               lengths: jax.Array, *,
                               window: Optional[int] = None,
                               scale: Optional[float] = None) -> jax.Array:
    """Oracle for the page-table-aware decode kernel: gather each
    sequence's pages into a contiguous cache row (the logical view the
    block table encodes), then defer to the contiguous-cache oracle.
    q: (B,H,hd); pages: (Hkv, n_pages, page_size, hd);
    block_tables: (B, max_pages) int32; lengths: (B,) -> (B,H,hd)."""
    b = q.shape[0]
    hkv, n_pages, page_size, hd = k_pages.shape
    max_pages = block_tables.shape[1]

    def rows(pages):         # (Hkv, B, max_pages, page, hd) -> (B, S, Hkv, hd)
        return jnp.moveaxis(pages[:, block_tables], 0, -2).reshape(
            b, max_pages * page_size, hkv, hd)
    k, v = rows(k_pages), rows(v_pages)
    return decode_attention_ref(q, k, v, lengths, window=window, scale=scale)


def paged_verify_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, block_tables: jax.Array,
                               lengths: jax.Array, *,
                               window: Optional[int] = None,
                               scale: Optional[float] = None) -> jax.Array:
    """Oracle for the verify-window paged kernel: run the decode oracle
    once per window position w with the causally-shrunk length
    ``lengths - (W-1) + w``.  q: (B, W, H, hd); lengths include all W
    window tokens' K/V -> (B, W, H, hd)."""
    b, w_len = q.shape[0], q.shape[1]
    outs = []
    for w in range(w_len):
        lens_w = lengths - (w_len - 1 - w)
        outs.append(paged_decode_attention_ref(
            q[:, w], k_pages, v_pages, block_tables, lens_w,
            window=window, scale=scale))
    return jnp.stack(outs, axis=1)


def moe_gmm_ref(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array) -> jax.Array:
    """x: (E,C,d) -> (E,C,d), fused SwiGLU per expert."""
    xf = x.astype(jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", xf, w_gate.astype(jnp.float32))
    u = jnp.einsum("ecd,edf->ecf", xf, w_up.astype(jnp.float32))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h,
                      w_down.astype(jnp.float32)).astype(x.dtype)


def moe_gmm_ragged_ref(rows: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                       w_down: jax.Array, tile_expert: jax.Array,
                       m_blk: int, layer=None) -> jax.Array:
    """Oracle for the ragged grouped matmul: per row-tile, apply the fused
    SwiGLU FFN of the tile's owning expert; sentinel tiles
    (tile_expert == E) produce zero rows. rows: (n_rows, d) -> (n_rows, d).

    Weights are (E, d, F) / (E, F, d), or with ``layer`` (a static or
    traced index) a layer stack (reps, E, d, F) / (reps, E, F, d) read at
    ``[layer, expert]`` inside the loop.  A loop's operand lives in a
    buffer of its own, so handing it one layer's slice would copy that
    layer's every expert (1.21 GB at Qwen3-30B-A3B widths) on each call.

    A loop over tiles reads exactly one expert's weights per tile — the
    same traffic shape as the kernel's scalar-prefetched DMA — and never
    holds more than one tile's weights: a batched per-tile gather would
    materialise (n_tiles, d, F) copies, 4.8 GB for one 1024-token prefill
    at Qwen3-30B-A3B widths."""
    n_rows, d = rows.shape
    e = w_gate.shape[-3]

    def tile_ffn(args):
        x, ex = args
        sel = jnp.minimum(ex, e - 1)
        at = sel if layer is None else (layer, sel)
        xf = x.astype(jnp.float32)
        g = xf @ w_gate[at].astype(jnp.float32)
        u = xf @ w_up[at].astype(jnp.float32)
        y = (jax.nn.silu(g) * u) @ w_down[at].astype(jnp.float32)
        return jnp.where(ex < e, y, 0.0).astype(rows.dtype)

    tiles = rows.reshape(-1, m_blk, d)
    return jax.lax.map(tile_ffn, (tiles, tile_expert)).reshape(n_rows, d)
