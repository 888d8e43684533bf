"""Pallas TPU decode-attention kernels: contiguous and paged.

One new query token per sequence attending over the KV cache with per-row
valid lengths — the memory-bound stage whose stall-freeness the schedulers
protect.

``decode_attention_pallas`` assumes the slot layout: each sequence owns a
contiguous ``max_len`` cache row.  Grid is (batch, kv_heads): each step
streams that kv head's cache once from HBM through VMEM while computing all
``group`` query heads that share it (GQA reuse), with online softmax over
KV tiles.

``paged_decode_attention_pallas`` is the page-table-aware variant backing
the PagedKVAllocator's scattered physical layout: K/V live in a global
``(Hkv, n_pages, page_size, hd)`` pool (head-major, the layout of JAX's own
paged-attention kernel: one page of one kv head is a ``(page_size, hd)``
tile, which the TPU's (8|16, 128) tiling accepts) and each sequence's
*block table* (scalar-prefetched, so the index maps can read it before the
body runs) names the physical pages holding its KV in logical order.  Grid is (batch,
kv_heads, max_pages): the DMA engine streams exactly the pages the block
table names — one page per grid step — while online-softmax state persists
in VMEM scratch across the page axis, exactly the structure of the slot
kernel with the contiguous row replaced by a block-table walk.

``paged_verify_attention_pallas`` generalizes the paged kernel to a
``W``-token query *window* per sequence — the speculative verify-k shape
(W = k+1 drafted-plus-bonus tokens; DESIGN.md §Speculative decode).  The
window's rows are packed into the same per-kv-head register block the GQA
group already occupies (``W*g`` rows), so the KV stream is read from HBM
ONCE for the whole window — the kernel-level expression of the verify-k
amortization the cost model prices.  Inside the window the mask is
causal: query ``w`` sees kv positions ``< length - W + 1 + w``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, kv_blk: int,
                   scale: float, max_len: int, window: Optional[int]):
    q = q_ref[0, 0].astype(jnp.float32) * scale            # (g, hd)
    g = q.shape[0]
    length = len_ref[pl.program_id(0)]                     # valid kv entries

    n_kv = max_len // kv_blk
    hi = jnp.minimum((length + kv_blk - 1) // kv_blk, n_kv)
    if window is not None:
        lo = jnp.maximum((length - window) // kv_blk, 0)
    else:
        lo = 0

    acc = jnp.zeros((g, q.shape[-1]), jnp.float32)
    m = jnp.full((g,), NEG_INF, jnp.float32)
    l = jnp.zeros((g,), jnp.float32)

    def body(t, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(t * kv_blk, kv_blk)].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(t * kv_blk, kv_blk)].astype(jnp.float32)
        s = q @ k.T                                        # (g, kv_blk)
        kv_pos = t * kv_blk + jax.lax.iota(jnp.int32, kv_blk)
        mask = kv_pos[None, :] < length
        if window is not None:
            mask &= kv_pos[None, :] >= length - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_new, l_new

    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc, m, l))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, lengths: jax.Array, *,
                            kv_blk: int = 128,
                            window: Optional[int] = None,
                            scale: Optional[float] = None,
                            interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); caches: (B, S_max, Hkv, hd); lengths: (B,) int32
    (#valid entries INCLUDING the new token's K/V already written).
    Returns (B, H, hd)."""
    b, h, hd = q.shape
    s_max, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    assert s_max % kv_blk == 0, (s_max, kv_blk)
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    # g-major grouping (q head h -> kv head h % hkv): gather each kv
    # head's g query heads into a contiguous block for the kernel.
    qg = q.reshape(b, g, hkv, hd).transpose(0, 2, 1, 3)
    kt = k_cache.transpose(0, 2, 1, 3)    # (B, Hkv, S, hd)
    vt = v_cache.transpose(0, 2, 1, 3)

    kernel = functools.partial(_decode_kernel, kv_blk=kv_blk, scale=scale,
                               max_len=s_max, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                 # lengths
        grid=(b, hkv),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, hi, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s_max, hd),
                         lambda bi, hi, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s_max, hd),
                         lambda bi, hi, lens: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, hi, lens: (bi, hi, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.transpose(0, 2, 1, 3).reshape(b, h, hd)


# ---------------------------------------------------------------------------
# Page-table-aware variant (PagedKVAllocator physical layout)
# ---------------------------------------------------------------------------


def _paged_decode_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, page_size: int,
                         scale: float, max_pages: int,
                         window: Optional[int]):
    bi = pl.program_id(0)
    pi = pl.program_id(2)
    length = len_ref[bi]

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n_seq_pages = (length + page_size - 1) // page_size

    @pl.when(pi < n_seq_pages)
    def _page():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (g, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (page, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = q @ k.T                                        # (g, page)
        kv_pos = pi * page_size + jax.lax.iota(jnp.int32, page_size)
        mask = kv_pos[None, :] < length
        if window is not None:
            mask &= kv_pos[None, :] >= length - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v

    @pl.when(pi == max_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_verify_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, page_size: int,
                         scale: float, max_pages: int, win: int,
                         group: int, window: Optional[int]):
    bi = pl.program_id(0)
    pi = pl.program_id(2)
    length = len_ref[bi]

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    n_seq_pages = (length + page_size - 1) // page_size
    rows = win * group

    @pl.when(pi < n_seq_pages)
    def _page():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (W*g, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (page, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = q @ k.T                                        # (W*g, page)
        kv_pos = pi * page_size + jax.lax.iota(jnp.int32, page_size)
        # row r holds window query w = r // g; its causal KV horizon is
        # length - W + 1 + w valid entries (the last row sees everything)
        w_idx = jax.lax.iota(jnp.int32, rows) // group
        row_len = length - win + 1 + w_idx                 # (W*g,)
        mask = kv_pos[None, :] < row_len[:, None]
        if window is not None:
            mask &= kv_pos[None, :] >= row_len[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v

    @pl.when(pi == max_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_verify_attention_pallas(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array,
                                  block_tables: jax.Array,
                                  lengths: jax.Array, *,
                                  window: Optional[int] = None,
                                  scale: Optional[float] = None,
                                  interpret: bool = False) -> jax.Array:
    """Verify-window paged attention.  q: (B, W, H, hd) — the W = k+1
    window query tokens per sequence, oldest first; k_pages/v_pages:
    (Hkv, n_pages, page_size, hd) global pool; block_tables:
    (B, max_pages) int32; lengths: (B,) int32 valid KV tokens INCLUDING
    all W window tokens' K/V already written.  Returns (B, W, H, hd).
    Each sequence's KV stream is read once for the whole window."""
    b, win, h, hd = q.shape
    hkv, n_pages, page_size, _ = k_pages.shape
    g = h // hkv
    max_pages = block_tables.shape[1]
    assert block_tables.shape == (b, max_pages), block_tables.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    # pack the window into the GQA row block: (B, Hkv, W*g, hd), rows
    # w-major so row r <-> (w = r // g, head-in-group r % g)
    qg = q.reshape(b, win, g, hkv, hd).transpose(0, 3, 1, 2, 4) \
          .reshape(b, hkv, win * g, hd)
    bt_flat = block_tables.reshape(-1).astype(jnp.int32)

    kernel = functools.partial(_paged_verify_kernel, page_size=page_size,
                               scale=scale, max_pages=max_pages, win=win,
                               group=g, window=window)
    rows = win * g
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # lengths, flat block tables
        grid=(b, hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd),
                         lambda bi, hi, pi, lens, bt: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda bi, hi, pi, lens, bt:
                         (hi, bt[bi * max_pages + pi], 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda bi, hi, pi, lens, bt:
                         (hi, bt[bi * max_pages + pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda bi, hi, pi, lens, bt: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),   # acc
            pltpu.VMEM((rows, 1), jnp.float32),    # running max
            pltpu.VMEM((rows, 1), jnp.float32),    # running denom
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), bt_flat, qg, k_pages, v_pages)
    return out.reshape(b, hkv, win, g, hd).transpose(0, 2, 3, 1, 4) \
              .reshape(b, win, h, hd)


def paged_decode_attention_pallas(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array,
                                  block_tables: jax.Array,
                                  lengths: jax.Array, *,
                                  window: Optional[int] = None,
                                  scale: Optional[float] = None,
                                  interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k_pages/v_pages: (Hkv, n_pages, page_size, hd) —
    the global page pool; block_tables: (B, max_pages) int32 physical page
    ids in logical order (entries past a sequence's page count are ignored
    but must be valid indices — pad with 0); lengths: (B,) int32 valid KV
    tokens INCLUDING the new token's K/V already written.
    Returns (B, H, hd)."""
    b, h, hd = q.shape
    hkv, n_pages, page_size, _ = k_pages.shape
    g = h // hkv
    max_pages = block_tables.shape[1]
    assert block_tables.shape == (b, max_pages), block_tables.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qg = q.reshape(b, g, hkv, hd).transpose(0, 2, 1, 3)   # (B, Hkv, g, hd)
    bt_flat = block_tables.reshape(-1).astype(jnp.int32)

    kernel = functools.partial(_paged_decode_kernel, page_size=page_size,
                               scale=scale, max_pages=max_pages,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # lengths, flat block tables
        grid=(b, hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda bi, hi, pi, lens, bt: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda bi, hi, pi, lens, bt:
                         (hi, bt[bi * max_pages + pi], 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda bi, hi, pi, lens, bt:
                         (hi, bt[bi * max_pages + pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, hi, pi, lens, bt: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),   # acc
            pltpu.VMEM((g, 1), jnp.float32),    # running max
            pltpu.VMEM((g, 1), jnp.float32),    # running denom
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), bt_flat, qg, k_pages, v_pages)
    return out.transpose(0, 2, 1, 3).reshape(b, h, hd)
