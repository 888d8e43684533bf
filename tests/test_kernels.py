"""Per-kernel validation: sweep shapes/dtypes and assert_allclose against
the pure-jnp oracles in kernels/ref.py. All kernels execute in Pallas
interpret mode on CPU (``interpret=True`` is always explicit; the TPU
lowering of the same kernels is compiled in tests/test_tpu_compile.py)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

jax.config.update("jax_default_matmul_precision", "highest")


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


def test_kernel_without_interpret_refuses_cpu():
    """No silent interpret mode: off the TPU a kernel runs only when the
    caller asks for the interpreter."""
    q = jnp.zeros((1, 128, 4, 64))
    with pytest.raises(Exception, match="interpret"):
        ops.flash_attention(q, q, q)


# ---------------------------------------------------------------- flash attn

FLASH_SWEEP = [
    # (B, S, H, Hkv, hd)
    (1, 128, 4, 4, 64),      # MHA, single tile
    (2, 256, 4, 2, 64),      # GQA 2:1, two tiles
    (1, 384, 8, 1, 32),      # MQA, non-square tiling
    (2, 100, 4, 4, 64),      # ragged S (padding path)
    (1, 257, 4, 2, 128),     # ragged S + MXU-width head
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,hkv,hd", FLASH_SWEEP)
def test_flash_attention_causal(b, s, h, hkv, hd, dtype):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(s * h), 3)
    q = _rand(kq, (b, s, h, hd), dtype)
    k = _rand(kk, (b, s, hkv, hd), dtype)
    v = _rand(kv, (b, s, hkv, hd), dtype)
    got = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 128, 300])
def test_flash_attention_windowed(window):
    b, s, h, hkv, hd = 1, 256, 4, 2, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(window), 3)
    q = _rand(kq, (b, s, h, hd), jnp.float32)
    k = _rand(kk, (b, s, hkv, hd), jnp.float32)
    v = _rand(kv, (b, s, hkv, hd), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_noncausal():
    b, s, h, hkv, hd = 1, 256, 4, 4, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(kq, (b, s, h, hd), jnp.float32)
    k = _rand(kk, (b, s, hkv, hd), jnp.float32)
    v = _rand(kv, (b, s, hkv, hd), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------- decode attn

DECODE_SWEEP = [
    # (B, S_max, H, Hkv, hd)
    (4, 128, 4, 4, 64),
    (2, 256, 8, 2, 64),
    (3, 200, 4, 1, 32),      # ragged cache length
    (1, 512, 4, 4, 128),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,hkv,hd", DECODE_SWEEP)
def test_decode_attention(b, s, h, hkv, hd, dtype):
    kq, kk, kv, kl = jax.random.split(jax.random.PRNGKey(b * s), 4)
    q = _rand(kq, (b, h, hd), dtype)
    k = _rand(kk, (b, s, hkv, hd), dtype)
    v = _rand(kv, (b, s, hkv, hd), dtype)
    lengths = jax.random.randint(kl, (b,), 1, s + 1)
    got = ops.decode_attention(q, k, v, lengths, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_attention_windowed():
    b, s, h, hkv, hd = 2, 256, 4, 2, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(kq, (b, h, hd), jnp.float32)
    k = _rand(kk, (b, s, hkv, hd), jnp.float32)
    v = _rand(kv, (b, s, hkv, hd), jnp.float32)
    lengths = jnp.asarray([200, 64])
    got = ops.decode_attention(q, k, v, lengths, window=32, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths, window=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------- paged decode attn

PAGED_SWEEP = [
    # (B, H, Hkv, hd, page_size, n_pages, max_pages)
    (3, 4, 2, 64, 16, 24, 6),
    (2, 8, 1, 32, 8, 40, 10),      # MQA, small pages
    (1, 4, 4, 128, 32, 8, 4),      # MHA, MXU-width head
    (4, 4, 2, 64, 16, 20, 4),      # tight pool, short sequences
]


def _ragged_block_tables(rng, b, page_size, n_pages, max_pages):
    """Ragged lengths + SHUFFLED physical page assignment: logical order
    must come entirely from the block table, not from page locality."""
    lengths = rng.integers(1, max_pages * page_size + 1, size=b)
    bt = np.zeros((b, max_pages), np.int32)
    perm = rng.permutation(n_pages)
    k = 0
    for i in range(b):
        n = -(-int(lengths[i]) // page_size)
        bt[i, :n] = perm[k:k + n]
        k += n
    assert k <= n_pages, "sweep entry overcommits the page pool"
    return jnp.asarray(lengths, jnp.int32), jnp.asarray(bt)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,hd,page,npages,maxp", PAGED_SWEEP)
def test_paged_decode_attention(b, h, hkv, hd, page, npages, maxp, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * hd + page), 3)
    q = _rand(ks[0], (b, h, hd), dtype)
    kp = _rand(ks[1], (hkv, npages, page, hd), dtype)
    vp = _rand(ks[2], (hkv, npages, page, hd), dtype)
    lengths, bt = _ragged_block_tables(
        np.random.default_rng(b * page), b, page, npages, maxp)
    got = ops.paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_paged_decode_attention_windowed():
    b, h, hkv, hd, page, npages, maxp = 2, 4, 2, 64, 16, 16, 5
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (b, h, hd), jnp.float32)
    kp = _rand(ks[1], (hkv, npages, page, hd), jnp.float32)
    vp = _rand(ks[2], (hkv, npages, page, hd), jnp.float32)
    lengths, bt = _ragged_block_tables(
        np.random.default_rng(5), b, page, npages, maxp)
    got = ops.paged_decode_attention(q, kp, vp, bt, lengths, window=24,
                                     interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths, window=24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_matches_contiguous_decode_via_allocator_tables():
    """End-to-end mapping check: scatter a contiguous slot cache into the
    page pool with PagedKVAllocator block tables, then the paged kernel
    over the pool must equal the contiguous kernel over the slot rows."""
    from repro.serving.kvcache import PagedKVAllocator
    b, h, hkv, hd, page = 3, 4, 2, 32, 8
    s_max = 64
    kv = PagedKVAllocator(n_pages=b * s_max // page, page_size=page)
    lengths = np.array([50, 17, 8], np.int32)
    for rid, n in enumerate(lengths):
        kv.reserve(rid, int(n))
    max_pages = s_max // page
    bt = np.zeros((b, max_pages), np.int32)
    for rid in range(b):
        t = kv.block_table(rid)
        bt[rid, :len(t)] = t
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (b, h, hd), jnp.float32)
    k_slot = _rand(ks[1], (b, s_max, hkv, hd), jnp.float32)
    v_slot = _rand(ks[2], (b, s_max, hkv, hd), jnp.float32)
    # physical placement: page j of request rid holds slot row tokens
    # [j*page, (j+1)*page) — exactly what the engine's scatter would do
    kp = np.zeros((hkv, kv.n_pages, page, hd), np.float32)
    vp = np.zeros_like(kp)
    for rid in range(b):
        for j, pid in enumerate(kv.block_table(rid)):
            kp[:, pid] = np.asarray(
                k_slot[rid, j * page:(j + 1) * page]).transpose(1, 0, 2)
            vp[:, pid] = np.asarray(
                v_slot[rid, j * page:(j + 1) * page]).transpose(1, 0, 2)
    got = ops.paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                     jnp.asarray(bt),
                                     jnp.asarray(lengths), interpret=True)
    want = ops.decode_attention(q, k_slot, v_slot, jnp.asarray(lengths),
                                interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------ paged verify attn

VERIFY_SWEEP = [
    # (B, W, H, Hkv, hd, page_size, n_pages, max_pages)
    (3, 3, 4, 2, 64, 16, 24, 6),
    (2, 5, 8, 1, 32, 8, 40, 10),     # MQA, small pages, k=4 window
    (1, 2, 4, 4, 128, 32, 8, 4),     # MHA, MXU-width head
    (4, 4, 4, 2, 64, 16, 20, 4),     # tight pool, short sequences
]


def _verify_tables(rng, b, w, page_size, n_pages, max_pages):
    """Like _ragged_block_tables but lengths always cover the W-token
    window (the engine writes the window's K/V before verifying)."""
    lengths = rng.integers(w, max_pages * page_size + 1, size=b)
    bt = np.zeros((b, max_pages), np.int32)
    perm = rng.permutation(n_pages)
    k = 0
    for i in range(b):
        n = -(-int(lengths[i]) // page_size)
        bt[i, :n] = perm[k:k + n]
        k += n
    assert k <= n_pages, "sweep entry overcommits the page pool"
    return jnp.asarray(lengths, jnp.int32), jnp.asarray(bt)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,w,h,hkv,hd,page,npages,maxp", VERIFY_SWEEP)
def test_paged_verify_attention(b, w, h, hkv, hd, page, npages, maxp, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * hd + w), 3)
    q = _rand(ks[0], (b, w, h, hd), dtype)
    kp = _rand(ks[1], (hkv, npages, page, hd), dtype)
    vp = _rand(ks[2], (hkv, npages, page, hd), dtype)
    lengths, bt = _verify_tables(
        np.random.default_rng(b * page + w), b, w, page, npages, maxp)
    got = ops.paged_verify_attention(q, kp, vp, bt, lengths, interpret=True)
    want = ref.paged_verify_attention_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_paged_verify_window_one_matches_decode():
    """W=1 degenerates to plain paged decode — same numbers, not merely
    close: both kernels must agree bit-for-bit on the single-query path
    (the spec-off equivalence the engine relies on)."""
    b, h, hkv, hd, page, npages, maxp = 3, 4, 2, 64, 16, 24, 6
    ks = jax.random.split(jax.random.PRNGKey(17), 3)
    q = _rand(ks[0], (b, h, hd), jnp.float32)
    kp = _rand(ks[1], (hkv, npages, page, hd), jnp.float32)
    vp = _rand(ks[2], (hkv, npages, page, hd), jnp.float32)
    lengths, bt = _ragged_block_tables(
        np.random.default_rng(9), b, page, npages, maxp)
    got = ops.paged_verify_attention(q[:, None], kp, vp, bt, lengths,
                                     interpret=True)[:, 0]
    want = ops.paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_verify_attention_windowed():
    b, w, h, hkv, hd, page, npages, maxp = 2, 3, 4, 2, 64, 16, 16, 5
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = _rand(ks[0], (b, w, h, hd), jnp.float32)
    kp = _rand(ks[1], (hkv, npages, page, hd), jnp.float32)
    vp = _rand(ks[2], (hkv, npages, page, hd), jnp.float32)
    lengths, bt = _verify_tables(
        np.random.default_rng(6), b, w, page, npages, maxp)
    got = ops.paged_verify_attention(q, kp, vp, bt, lengths, window=24,
                                     interpret=True)
    want = ref.paged_verify_attention_ref(q, kp, vp, bt, lengths, window=24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------- moe gmm

GMM_SWEEP = [
    # (E, C, d, f)
    (4, 128, 64, 128),
    (8, 64, 128, 256),       # C below tile size (padding path)
    (2, 300, 64, 100),       # ragged C and f
    (16, 8, 32, 64),         # tiny capacity (decode-like)
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,c,d,f", GMM_SWEEP)
def test_moe_gmm(e, c, d, f, dtype):
    ks = jax.random.split(jax.random.PRNGKey(e * c), 4)
    x = _rand(ks[0], (e, c, d), dtype)
    wg = _rand(ks[1], (e, d, f), dtype) / np.sqrt(d)
    wu = _rand(ks[2], (e, d, f), dtype) / np.sqrt(d)
    wd = _rand(ks[3], (e, f, d), dtype) / np.sqrt(f)
    got = ops.moe_gmm(x, wg, wu, wd, interpret=True)
    want = ref.moe_gmm_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ------------------------------------------------------------ ragged gmm

RAGGED_SWEEP = [
    # (E, d, f, m_blk, counts) — skewed loads, empty experts, sentinel tail
    (4, 64, 128, 8, [16, 0, 3, 1]),          # empty expert + tiny groups
    (8, 64, 256, 16, [64, 0, 0, 0, 0, 0, 0, 1]),   # heavy skew
    (2, 64, 100, 128, [128, 128]),           # exact tiles, ragged f
    (4, 32, 64, 8, [0, 0, 0, 0]),            # fully masked batch
]


def _ragged_layout(e, m_blk, counts):
    """Tile-aligned group layout + metadata from per-expert counts."""
    padded = [-(-c // m_blk) * m_blk for c in counts]
    used = sum(padded)
    n_rows = used + m_blk            # leave a sentinel tail tile
    tile_expert = []
    for ex, p_ in enumerate(padded):
        tile_expert += [ex] * (p_ // m_blk)
    tile_expert += [e] * ((n_rows - used) // m_blk)
    return n_rows, jnp.asarray(tile_expert, jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,d,f,m_blk,counts", RAGGED_SWEEP)
def test_moe_gmm_ragged(e, d, f, m_blk, counts, dtype):
    n_rows, tile_expert = _ragged_layout(e, m_blk, counts)
    ks = jax.random.split(jax.random.PRNGKey(e * d + m_blk), 4)
    rows = _rand(ks[0], (n_rows, d), dtype)
    wg = _rand(ks[1], (e, d, f), dtype) / np.sqrt(d)
    wu = _rand(ks[2], (e, d, f), dtype) / np.sqrt(d)
    wd = _rand(ks[3], (e, f, d), dtype) / np.sqrt(f)
    got = ops.moe_gmm_ragged(rows, wg, wu, wd, tile_expert, m_blk=m_blk,
                             interpret=True)
    want = ref.moe_gmm_ragged_ref(rows, wg, wu, wd, tile_expert, m_blk)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    # sentinel tiles must come out exactly zero
    sent = np.repeat(np.asarray(tile_expert) == e, m_blk)
    assert not np.asarray(got, np.float32)[sent].any()


def test_fetch_expert_ids_forward_fill():
    te = jnp.asarray([1, 1, 3, 4, 4], jnp.int32)
    got = ops.fetch_expert_ids(te, 4)       # id 4 == sentinel
    np.testing.assert_array_equal(np.asarray(got), [1, 1, 3, 3, 3])
    all_sent = ops.fetch_expert_ids(jnp.asarray([4, 4], jnp.int32), 4)
    np.testing.assert_array_equal(np.asarray(all_sent), [0, 0])


def test_ragged_dispatch_matches_expert_ffn_ref():
    """Acceptance: the ragged pipeline (dispatch + Pallas kernel + combine)
    must match the dense path over expert_ffn_ref on skewed routings with
    empty experts and masked padding tokens."""
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from conftest import tiny_moe
    from repro.models import moe

    cfg = tiny_moe()          # E=4, top_k=2
    p = moe.init_moe(cfg, jax.random.PRNGKey(0))
    t = 24
    xf = jax.random.normal(jax.random.PRNGKey(1), (t, cfg.d_model))
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (t, 2)), -1)
    # skew: most tokens on expert 0, expert 1 empty, tail tokens masked
    idx = np.zeros((t, 2), np.int32)
    idx[:, 1] = 2
    idx[5:8, 1] = 3
    idx[-4:] = cfg.moe.n_experts            # masked (padding) tokens
    idx = jnp.asarray(idx)

    dense, counts_d, _ = moe._dispatch_gmm_combine(
        cfg, p, xf, idx, w, t, cfg.moe.n_experts, moe.expert_ffn_ref)
    ragged, counts_r, _ = moe._dispatch_gmm_combine_ragged(
        cfg, p, xf, idx, w, cfg.moe.n_experts,
        lambda c, p_, rows, te, mb: ops.moe_gmm_ragged(
            rows, p_["w_gate"], p_["w_up"], p_["w_down"], te, m_blk=mb,
            interpret=True))
    np.testing.assert_array_equal(np.asarray(counts_d), np.asarray(counts_r))
    assert int(counts_r[1]) == 0            # expert 1 really is empty
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------- kernel <-> model integration

def test_model_forward_with_pallas_gmm_matches_ref():
    """Plugging the Pallas moe_gmm into the real model must not change
    outputs vs the jnp expert FFN."""
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from conftest import tiny_moe
    from repro.models.model import DecoderModel

    cfg = tiny_moe()
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.arange(1, 33, dtype=jnp.int32).reshape(2, 16)
    ref_logits, _, _ = model.forward(params, tokens)
    got_logits, _, _ = model.forward(
        params, tokens, gmm_fn=ops.model_gmm_fn(cfg, interpret=True))
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               atol=1e-4, rtol=1e-4)


def test_model_forward_with_ragged_pallas_gmm_matches_ref():
    """The ragged Pallas pipeline plugged into the real model (dropless
    serving path) must match the dense jnp expert FFN."""
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from conftest import tiny_moe
    from repro.models.model import DecoderModel

    cfg = tiny_moe()
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.arange(1, 33, dtype=jnp.int32).reshape(2, 16)
    ref_logits, _, ref_aux = model.forward(params, tokens, dropless=True)
    got_logits, _, got_aux = model.forward(params, tokens,
                                           gmm_fn=ops.ragged_gmm_fn(
                                               cfg, interpret=True),
                                           moe_dispatch="ragged")
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(ref_aux["expert_counts"]),
                                  np.asarray(got_aux["expert_counts"]))


def test_gmm_fn_dispatch_contract_mismatch_raises():
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from conftest import tiny_moe
    from repro.models import moe

    cfg = tiny_moe()
    p = moe.init_moe(cfg, jax.random.PRNGKey(0))
    x = jnp.zeros((1, 4, cfg.d_model))
    with pytest.raises(ValueError):
        moe.apply_moe(cfg, p, x, gmm_fn=ops.ragged_gmm_fn(cfg),
                      moe_dispatch="dense")
    with pytest.raises(ValueError):
        moe.apply_moe(cfg, p, x, gmm_fn=ops.model_gmm_fn(cfg),
                      moe_dispatch="ragged")
