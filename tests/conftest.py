"""Shared fixtures: tiny model configs for fast CPU tests.

Device count stays at 1 here (the 512-device forcing happens ONLY inside
repro.launch.dryrun, per the brief).
"""

from __future__ import annotations

import jax
import pytest

from repro.models.config import MLAConfig, ModelConfig, MoEConfig

jax.config.update("jax_enable_x64", False)


def tiny_dense(**kw) -> ModelConfig:
    base = dict(name="tiny-dense", family="dense", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                max_seq_len=256)
    base.update(kw)
    return ModelConfig(**base).validate()


def tiny_moe(**kw) -> ModelConfig:
    base = dict(name="tiny-moe", family="moe", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                max_seq_len=256,
                moe=MoEConfig(n_experts=4, top_k=2, expert_d_ff=64))
    base.update(kw)
    return ModelConfig(**base).validate()


def tiny_mla(**kw) -> ModelConfig:
    base = dict(name="tiny-mla", family="moe", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256,
                max_seq_len=256,
                mla=MLAConfig(kv_lora_rank=32, q_lora_rank=0, qk_rope_dim=8,
                              qk_nope_dim=16, v_head_dim=16))
    base.update(kw)
    return ModelConfig(**base).validate()


def tiny_hybrid(**kw) -> ModelConfig:
    base = dict(name="tiny-hybrid", family="hybrid", n_layers=3, d_model=64,
                n_heads=4, n_kv_heads=1, d_ff=128, vocab_size=256,
                max_seq_len=256, mixer_pattern=("rglru", "rglru", "local_gqa"),
                local_window=32, lru_width=64)
    base.update(kw)
    return ModelConfig(**base).validate()


def tiny_xlstm(**kw) -> ModelConfig:
    base = dict(name="tiny-xlstm", family="ssm", n_layers=2, d_model=64,
                n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=256,
                max_seq_len=256, mixer_pattern=("mlstm", "slstm"))
    base.update(kw)
    return ModelConfig(**base).validate()


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)
