"""The plain reference: a decoder's full forward pass in float32 at
``highest`` matmul precision, teacher-forced over each prompt and the
tokens the program served for it, built from the configuration file's
published keys and the seeded weights of ``chipbench.weights``.  It
imports nothing of the program and takes nothing the program made.

What it computes, per layer (pre-norm residual blocks):
  RMSNorm -> q, k, v projections -> rotary embedding on the first
  ``partial_rotary_factor`` of each head (rotate-half form) -> causal
  softmax attention with grouped K/V -> output projection;
  RMSNorm -> either a softmax router over ``num_experts`` whose top
  ``num_experts_per_tok`` weights are renormalised and SwiGLU experts
  (MoE), or one SwiGLU MLP (dense).
Then a final RMSNorm and the LM head (the token embedding, transposed,
where ``tie_word_embeddings``).  The weights are in the serving layout:
query head h reads K/V head h mod num_key_value_heads.

It runs layer by layer, regenerating each layer's weights from the seed,
so that only one layer's float32 weights are on the device at a time.

``control=True`` also runs the same forward with every matmul's weight
and activation rounded to float8 e4m3 with one scale per tensor, as
float8 serving commonly scales them: the next precision below the
bfloat16 the configurations state.

The number compared is a gap in logits: for a served token, how far its
reference logit lies below the reference's largest logit at that
position.  For the control, the same gap of the token the float8 forward
puts first.
"""

from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x):
    """Round ``x`` to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, quant: bool):
    if quant:
        x, w = _q8(x), _q8(w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta, frac):
    hd = x.shape[-1]
    rot = int(hd * frac)
    rot -= rot % 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[..., None] * inv          # (B, L, rot/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("c", "quant", "q_chunk"))
def _attention(h, p, *, c, quant, q_chunk):
    """h (B, L, d) -> h + attention(norm(h)); causal over each row."""
    b, n, d = h.shape
    hd, nq = c["head_dim"], c["num_attention_heads"]
    nkv = c["num_key_value_heads"]
    x = _rms(h, p["ln1/scale"], c["rms_norm_eps"])
    pos = jnp.broadcast_to(jnp.arange(n)[None], (b, n))
    frac = c.get("partial_rotary_factor", 1.0)
    q = _rope(_mm(x, p["attn/w_q"], quant).reshape(b, n, nq, hd), pos,
              c["rope_theta"], frac)
    k = _rope(_mm(x, p["attn/w_k"], quant).reshape(b, n, nkv, hd), pos,
              c["rope_theta"], frac)
    v = _mm(x, p["attn/w_v"], quant).reshape(b, n, nkv, hd)
    kv_of = jnp.arange(nq) % nkv
    k, v = k[:, :, kv_of], v[:, :, kv_of]                   # (B, L, nq, hd)

    def chunk(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        qpos = i * q_chunk + jnp.arange(q_chunk)
        s = jnp.where(jnp.arange(n)[None, :] <= qpos[:, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", a, v,
                          precision=jax.lax.Precision.HIGHEST)
    o = jax.lax.map(chunk, jnp.arange(n // q_chunk))        # (C, B, qc, nq, hd)
    o = o.transpose(1, 0, 2, 3, 4).reshape(b, n, nq * hd)
    return h + _mm(o, p["attn/w_o"], quant)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _dense_ffn(h, p, *, c, quant):
    x = _rms(h, p["ln2/scale"], c["rms_norm_eps"])
    g = _mm(x, p["mlp/w_gate"], quant)
    u = _mm(x, p["mlp/w_up"], quant)
    return h + _mm(jax.nn.silu(g) * u, p["mlp/w_down"], quant)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _route(h, p, valid, *, c, quant):
    """Normed tokens (T, d), each token's experts (T, k) -- the id
    num_experts for padding tokens -- and renormalised weights (T, k)."""
    d, e, k = c["hidden_size"], c["num_experts"], c["num_experts_per_tok"]
    x = _rms(h, p["ln2/scale"], c["rms_norm_eps"]).reshape(-1, d)
    probs = jax.nn.softmax(_mm(x, p["moe/router"], quant), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    if c.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    idx = jnp.where(valid.reshape(-1, 1), idx, e)
    return x, idx, w


@functools.partial(jax.jit, static_argnames=("c", "quant", "cap"))
def _experts(h, x, idx, w, p, *, c, quant, cap):
    """Each expert runs on the tokens routed to it (at most ``cap``),
    and the weighted outputs add back into the residual."""
    e, k = c["num_experts"], c["num_experts_per_tok"]
    t, d = x.shape
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    tok = (jnp.arange(t * k) // k)[order]
    wts = w.reshape(-1)[order]
    counts = jnp.bincount(flat, length=e)
    starts = jnp.cumsum(counts) - counts

    def one(i, out):
        sel = starts[i] + jnp.arange(cap)
        ok = jnp.arange(cap) < counts[i]
        sel = jnp.where(ok, sel, 0)
        rows = x[tok[sel]]
        g = _mm(rows, p["moe/w_gate"][i], quant)
        u = _mm(rows, p["moe/w_up"][i], quant)
        y = _mm(jax.nn.silu(g) * u, p["moe/w_down"][i], quant)
        y = y * jnp.where(ok, wts[sel], 0.0)[:, None]
        return out.at[tok[sel]].add(y)
    out = jax.lax.fori_loop(0, e, one, jnp.zeros((t, d), jnp.float32))
    return h + out.reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("path", "shape", "dtype"))
def _layer_leaf(key, layer, *, path, shape, dtype):
    return W.leaf(key, path, shape, layer, dtype).astype(jnp.float32)


def _layer_weights(lay, key, layer, dtype):
    return {path[len(W.LAYER):]: _layer_leaf(
        key, layer, path=path, shape=shape[1:], dtype=dtype)
        for path, (shape, stacked) in lay.items() if stacked}


class _Frozen(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _head_logits(hs, tok, lm_head, final, *, c, quant):
    x = _rms(hs, final, c["rms_norm_eps"])
    w = tok.T if lm_head is None else lm_head
    return _mm(x, w, quant)


def gaps(config: dict, seed: int, seqs: Sequence[Tuple[Sequence[int],
                                                       Sequence[int]]],
         max_len: int, control: bool = False, rows: int = 0) -> dict:
    """Teacher-forced reference over ``seqs`` = [(prompt, served), ...],
    run as a batch of max(rows, len(seqs)) rows of ``max_len`` (the same
    shapes every run, so its programs come from the compile cache).
    Returns {"served": [per-sequence gaps of the served tokens]} and, with
    ``control``, also {"control": [per-sequence gaps of the float8
    forward's first choices]}."""
    c = _Frozen({k: v for k, v in config.items()
                 if isinstance(v, (int, float, str, bool, type(None)))})
    dtype = jnp.dtype(config["torch_dtype"])
    lay = W.layout(config)
    key = W.seed_key(seed)
    b = max(rows, len(seqs))
    toks = np.zeros((b, max_len), np.int32)
    valid = np.zeros((b, max_len), bool)
    for i, (p, s) in enumerate(seqs):
        full = list(p) + list(s)[:-1]
        toks[i, :len(full)] = full
        valid[i, :len(full)] = True
    q_chunk = min(max_len, 512, max(128, 2 ** 21 // max_len))
    with jax.default_matmul_precision("highest"):
        tok_emb = _layer_leaf(key, 0, path="embed/tok",
                              shape=lay["embed/tok"][0], dtype=dtype)
        h0 = tok_emb[jnp.asarray(toks)]
        hs = {"served": h0}
        if control:
            hs["control"] = h0
        for layer in range(config["num_hidden_layers"]):
            p = _layer_weights(lay, key, layer, dtype)
            for mode in hs:
                quant = mode == "control"
                h = _attention(hs[mode], p, c=c, quant=quant,
                               q_chunk=q_chunk)
                if config.get("num_experts"):
                    x, idx, w = _route(h, p, jnp.asarray(valid), c=c,
                                       quant=quant)
                    most = int(np.bincount(
                        np.asarray(idx).reshape(-1),
                        minlength=config["num_experts"] + 1)[:-1].max())
                    cap = 256
                    while cap < most:        # few shapes, few compiles
                        cap *= 2
                    h = _experts(h, x, idx, w, p, c=c, quant=quant, cap=cap)
                else:
                    h = _dense_ffn(h, p, c=c, quant=quant)
                hs[mode] = h
            del p
        final = _layer_leaf(key, 0, path="final_norm/scale",
                            shape=lay["final_norm/scale"][0], dtype=dtype)
        lm_head = None
        if "embed/lm_head" in lay:
            lm_head = _layer_leaf(key, 0, path="embed/lm_head",
                                  shape=lay["embed/lm_head"][0], dtype=dtype)
        out = {mode: [] for mode in hs}
        for i, (p_, s) in enumerate(seqs):
            n = len(s)
            width = -(-n // 256) * 256          # few shapes, few compiles
            at = np.minimum(len(p_) - 1 + np.arange(width), max_len - 1)
            served = np.zeros(width, np.int32)
            served[:n] = s
            ref = _head_logits(hs["served"][i][jnp.asarray(at)], tok_emb,
                               lm_head, final, c=c, quant=False)
            best = jnp.max(ref, -1)
            picked = jnp.take_along_axis(
                ref, jnp.asarray(served)[:, None], 1)[:, 0]
            out["served"].append(np.asarray(best - picked)[:n].tolist())
            if control:
                low = _head_logits(hs["control"][i][jnp.asarray(at)],
                                   tok_emb, lm_head, final, c=c, quant=True)
                first = jnp.argmax(low, -1)
                picked = jnp.take_along_axis(ref, first[:, None], 1)[:, 0]
                out["control"].append(np.asarray(best - picked)[:n].tolist())
    return out


def summary(per_seq: List[List[float]]) -> dict:
    """The widest gap and, beside it, the gaps' 99th percentile, their
    mean and the share of tokens that are not the reference's first
    choice."""
    g = np.sort(np.concatenate([np.asarray(s, np.float64)
                                for s in per_seq if s]))
    return {"widest": float(g[-1]), "p99": float(g[int(0.99 * (len(g) - 1))]),
            "mean": float(g.mean()), "not_first": float(np.mean(g > 0)),
            "tokens": int(len(g))}
