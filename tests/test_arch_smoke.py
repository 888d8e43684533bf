"""Per-architecture smoke tests (the brief's deliverable f): every assigned
architecture instantiates a REDUCED same-family variant (<=2 layers unless
the mixer pattern needs a full period, d_model<=512, <=4 experts) and runs
one forward pass AND one train step on CPU, asserting output shapes and
no-NaN. Decode-capable archs also run one cached decode step."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config, get_smoke_config, list_configs
from repro.models.model import DecoderModel
from repro.training.optimizer import adamw
from repro.training.train import make_train_step

B, S = 2, 32


def _inputs(cfg):
    kw = {}
    tokens = jnp.arange(1, B * S + 1, dtype=jnp.int32).reshape(B, S) \
        % (cfg.vocab_size - 1) + 1
    if cfg.encoder.enabled:
        kw["enc_frames"] = jnp.ones((B, cfg.encoder.n_frames, cfg.d_model),
                                    cfg.dtype) * 0.01
    if cfg.vision.enabled:
        kw["extra_embeds"] = jnp.ones((B, 8, cfg.d_model), cfg.dtype) * 0.01
    return tokens, kw


@pytest.mark.parametrize("arch", ASSIGNED)
def test_full_config_matches_brief(arch):
    """The FULL config must carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    brief = {
        "qwen3-moe-235b-a22b": dict(n_layers=94, d_model=4096, n_heads=64,
                                    n_kv_heads=4, vocab_size=151936),
        "qwen2-vl-72b": dict(n_layers=80, d_model=8192, n_heads=64,
                             n_kv_heads=8, d_ff=29568, vocab_size=152064),
        "minicpm-2b": dict(n_layers=40, d_model=2304, n_heads=36,
                           n_kv_heads=36, d_ff=5760, vocab_size=122753),
        "stablelm-1.6b": dict(n_layers=24, d_model=2048, n_heads=32,
                              n_kv_heads=32, d_ff=5632, vocab_size=100352),
        "recurrentgemma-9b": dict(n_layers=38, d_model=4096, n_heads=16,
                                  n_kv_heads=1, d_ff=12288, vocab_size=256000),
        "whisper-base": dict(n_layers=6, d_model=512, n_heads=8,
                             n_kv_heads=8, d_ff=2048, vocab_size=51865),
        "yi-34b": dict(n_layers=60, d_model=7168, n_heads=56, n_kv_heads=8,
                       d_ff=20480, vocab_size=64000),
        "phi4-mini-3.8b": dict(n_layers=32, d_model=3072, n_heads=24,
                               n_kv_heads=8, d_ff=8192, vocab_size=200064),
        "xlstm-1.3b": dict(n_layers=48, d_model=2048, n_heads=4,
                           n_kv_heads=4, vocab_size=50304),
        "deepseek-v2-236b": dict(n_layers=60, d_model=5120, n_heads=128,
                                 vocab_size=102400),
    }[arch]
    for k, v in brief.items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)
    if arch == "qwen3-moe-235b-a22b":
        assert (cfg.moe.n_experts, cfg.moe.top_k) == (128, 8)
        assert cfg.moe.expert_d_ff == 1536
    if arch == "deepseek-v2-236b":
        assert (cfg.moe.n_experts, cfg.moe.top_k) == (160, 6)
        assert cfg.moe.n_shared_experts == 2
        assert cfg.mla.kv_lora_rank == 512
    assert cfg.source, f"{arch} missing source citation"


@pytest.mark.parametrize("arch", ASSIGNED)
def test_reduced_variant_bounds(arch):
    cfg = get_smoke_config(arch)
    assert cfg.d_model <= 512
    assert cfg.moe.n_experts <= 4
    # 2 layers, except hybrids that need one full mixer period
    assert cfg.n_layers <= max(2, len(cfg.mixer_pattern) or 0, 3)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_forward(arch):
    cfg = get_smoke_config(arch)
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens, kw = _inputs(cfg)
    enc_out = None
    if cfg.encoder.enabled:
        enc_out = model.encode(params, kw["enc_frames"])
    logits, _, aux = model.forward(params, tokens, enc_out=enc_out,
                                   extra_embeds=kw.get("extra_embeds"))
    s_all = S + (kw["extra_embeds"].shape[1] if "extra_embeds" in kw else 0)
    assert logits.shape == (B, s_all, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), arch
    if cfg.moe.enabled:
        assert int(aux["expert_counts"].sum()) > 0


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw(lr=1e-3, total_steps=10, warmup=1)
    step = jax.jit(make_train_step(model, opt, cfg.encoder.enabled))
    opt_state = opt.init(params)
    tokens, kw = _inputs(cfg)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1),
             "mask": jnp.ones((B, S), bool)}
    if cfg.encoder.enabled:
        batch["enc_out"] = kw["enc_frames"]
    p2, o2, metrics = step(params, opt_state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0, (arch, loss)
    # params actually changed
    delta = jax.tree_util.tree_reduce(
        lambda a, x: a + float(jnp.abs(x).sum()),
        jax.tree_util.tree_map(lambda a, b: a - b, p2, params), 0.0)
    assert delta > 0, arch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_decode_step(arch):
    """One cached decode step (whisper decodes too — enc-dec has a decode
    stage; its encoder output is a stub embedding)."""
    cfg = get_smoke_config(arch)
    model = DecoderModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(B, 64)
    if cfg.encoder.enabled:
        # install cross-KV from a stub encoding
        enc = model.encode(params, jnp.ones((B, cfg.encoder.n_frames,
                                             cfg.d_model), cfg.dtype) * 0.01)
        xkv = model.precompute_cross_kv(params, enc)
        for s, seg in enumerate(xkv):
            for p_idx, kv in enumerate(seg):
                if kv is not None:
                    cache[s][p_idx] = dict(cache[s][p_idx], **kv)
    tokens, _ = _inputs(cfg)
    # prefill S tokens then decode one
    logits, cache, _ = model.forward(params, tokens, cache=cache,
                                     offset=jnp.zeros((B,), jnp.int32),
                                     dropless=cfg.moe.enabled)
    one = tokens[:, -1:]
    logits1, cache, _ = model.forward(params, one, cache=cache,
                                      offset=jnp.full((B,), S, jnp.int32),
                                      dropless=cfg.moe.enabled)
    assert logits1.shape == (B, 1, cfg.vocab_size)
    assert not bool(jnp.isnan(logits1).any()), arch


def test_registry_covers_paper_models():
    names = list_configs()
    assert "qwen3-30b-a3b" in names and "gpt-oss-20b" in names
    q = get_config("qwen3-30b-a3b")
    assert (q.moe.n_experts, q.moe.top_k) == (128, 8)   # paper Table 3
    g = get_config("gpt-oss-20b")
    assert (g.moe.n_experts, g.moe.top_k) == (32, 4)


def test_one_chip_config_cuts_only_depth():
    """qwen3-30b-a3b-1chip keeps every published width; only the layer
    count is cut to what one TPU v5e holds."""
    full, one = get_config("qwen3-30b-a3b"), get_config("qwen3-30b-a3b-1chip")
    assert 4 <= one.n_layers < full.n_layers
    assert dataclasses.replace(one, name=full.name,
                               n_layers=full.n_layers) == full
