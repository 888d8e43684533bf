"""Model FLOPs from a configuration file's published shapes.

Counted per token: two FLOPs per multiply-add of every weight the token
passes through (attention projections, the router and the top-k experts
or the dense MLP, and the LM head where a token's logits are taken), plus
causal attention over its context (QK^T and PV, 2 x 2 x heads x head_dim
x context).  Work the program adds on top -- padding to buckets, masked
attention over a whole slot row, experts no token was routed to -- is not
model work and is not counted."""

from __future__ import annotations


def per_layer_weight_flops(c: dict) -> float:
    d, hd = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    attn = 2 * d * hd * (2 * nq + 2 * nkv)
    if c.get("num_experts"):
        ffn = 2 * d * c["num_experts"] + \
            c["num_experts_per_tok"] * 2 * 3 * d * c["moe_intermediate_size"]
    else:
        ffn = 2 * 3 * d * c["intermediate_size"]
    return float(attn + ffn)


def attention_flops(c: dict, ctx_sum: float) -> float:
    """QK^T and PV for tokens whose contexts add up to ``ctx_sum``."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"] * ctx_sum


def head_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def prefill_slice(c: dict, start: int, end: int, n_layers: int,
                  emits: bool) -> float:
    """Prompt positions [start, end) through ``n_layers`` layers, plus the
    LM head for the last position when the slice emits the first token."""
    n = end - start
    ctx = (start + 1 + end) * n / 2.0            # contexts start+1 .. end
    f = n_layers * (n * per_layer_weight_flops(c) + attention_flops(c, ctx))
    return f + (head_flops(c) if emits else 0.0)


def decode_token(c: dict, position: int) -> float:
    """One decoded token at ``position`` (its context is position + 1)."""
    return (c["num_hidden_layers"] * (per_layer_weight_flops(c)
                                      + attention_flops(c, position + 1))
            + head_flops(c))
