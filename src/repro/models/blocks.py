"""Block-level dispatch: one decoder block = mixer (+ optional cross-attn)
+ FFN (dense / MoE / none), pre-norm residual style.

``apply_block`` is the single entry point used by both execution paths:
the lax.scan full-forward (training / dry-run) and the serving engine's
``run_blocks(start, n)`` partial vertical execution (layered prefill).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.models import attention, layers, moe, rglru, xlstm
from repro.models.config import (FFN_MOE, FFN_NONE, MIXER_MLSTM,
                                 MIXER_RGLRU, MIXER_SLSTM, BlockSpec,
                                 ModelConfig)

Array = jax.Array


def init_block(cfg: ModelConfig, spec: BlockSpec, key) -> dict:
    ks = jax.random.split(key, 4)
    p = {"ln1": layers.init_norm(cfg)}
    if spec.is_attention():
        p["attn"] = attention.init_attn(cfg, spec, ks[0])
    elif spec.mixer == MIXER_RGLRU:
        p["rglru"] = rglru.init_rglru(cfg, ks[0])
    elif spec.mixer == MIXER_MLSTM:
        p["lstm"] = xlstm.init_mlstm(cfg, ks[0])
    elif spec.mixer == MIXER_SLSTM:
        p["lstm"] = xlstm.init_slstm(cfg, ks[0])
    else:
        raise ValueError(spec.mixer)

    if spec.ffn != FFN_NONE:
        p["ln2"] = layers.init_norm(cfg)
        if spec.ffn == FFN_MOE:
            p["moe"] = moe.init_moe(cfg, ks[1])
        else:
            p["mlp"] = layers.init_mlp(cfg, ks[1])
    return p


def init_block_cache(cfg: ModelConfig, spec: BlockSpec, batch: int,
                     max_len: int, dtype=None) -> dict:
    if spec.is_attention():
        return attention.init_cache_attn(cfg, spec, batch, max_len, dtype)
    if spec.mixer == MIXER_RGLRU:
        return rglru.init_cache_rglru(cfg, batch, dtype)
    if spec.mixer == MIXER_MLSTM:
        return xlstm.init_cache_mlstm(cfg, batch, dtype)
    if spec.mixer == MIXER_SLSTM:
        return xlstm.init_cache_slstm(cfg, batch, dtype)
    raise ValueError(spec.mixer)


def apply_block(cfg: ModelConfig, spec: BlockSpec, p, x: Array, *,
                positions: Array, offset: Optional[Array] = None,
                cache: Optional[dict] = None, enc_out: Optional[Array] = None,
                valid: Optional[Array] = None,
                positions3: Optional[Array] = None,
                gmm_fn=None, dropless: bool = False,
                moe_dispatch: str = "dense", expert_layer=None
                ) -> Tuple[Array, Optional[dict], dict]:
    """x: (B,S,D) -> (x', new_cache, aux). aux has uniform pytree structure
    across block kinds so heterogeneous stacks scan cleanly.
    ``expert_layer``: ``p["moe"]`` holds the layer stacks of the routed
    experts, read at this layer (``moe.apply_moe``'s ``layer``)."""
    h = layers.apply_norm(cfg, p["ln1"], x)
    if spec.is_attention():
        with jax.named_scope("attention"):
            out, new_cache = attention.apply_mixer_attn(
                cfg, spec, p["attn"], h, positions=positions, offset=offset,
                cache=cache, valid=valid, positions3=positions3)
        x = x + out
        if spec.cross_attn:
            hx = layers.apply_norm(cfg, p["attn"]["x_norm"], x)
            # fresh encoder output takes precedence over cached cross-K/V
            if enc_out is not None:
                xk, xv = attention.encode_cross_kv(cfg, p["attn"], enc_out)
                xc = {"xk": xk, "xv": xv}
            else:
                assert cache is not None and "xk" in cache, \
                    "cross-attn needs enc_out or cached K/V"
                xc = cache
            x = x + attention.apply_cross_attn(cfg, p["attn"], hx, xc)
            if new_cache is not None and "xk" in (cache or {}):
                new_cache = dict(new_cache, xk=cache["xk"], xv=cache["xv"])
    elif spec.mixer == MIXER_RGLRU:
        out, new_cache = rglru.apply_rglru(cfg, p["rglru"], h, cache=cache,
                                           valid=valid)
        x = x + out
    elif spec.mixer == MIXER_MLSTM:
        out, new_cache = xlstm.apply_mlstm(cfg, p["lstm"], h, cache=cache,
                                           valid=valid)
        x = x + out
    elif spec.mixer == MIXER_SLSTM:
        out, new_cache = xlstm.apply_slstm(cfg, p["lstm"], h, cache=cache,
                                           valid=valid)
        x = x + out
    else:
        raise ValueError(spec.mixer)

    aux = moe.empty_moe_aux(cfg)
    if spec.ffn != FFN_NONE:
        h2 = layers.apply_norm(cfg, p["ln2"], x)
        if spec.ffn == FFN_MOE:
            out2, aux = moe.apply_moe(cfg, p["moe"], h2, valid=valid,
                                      gmm_fn=gmm_fn, dropless=dropless,
                                      moe_dispatch=moe_dispatch,
                                      layer=expert_layer)
        else:
            out2 = layers.apply_mlp(cfg, p["mlp"], h2)
        x = x + out2
    return x, new_cache, aux
