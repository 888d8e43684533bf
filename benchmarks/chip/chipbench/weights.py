"""Seeded random weights, made by the benchmark and not by the program.

``layout(config)`` lists every weight of a homogeneous decoder in the
serving program's parameter tree (``embed``, ``final_norm`` and one scan
segment whose leaves are stacked over the layers), with its shape, from
the configuration file's published keys alone.  ``make(layout, seed,
dtype)`` builds the whole tree on the device in one jitted call;
``leaf(...)`` rebuilds one layer of one weight bit for bit, which is how
the reference gets its weights without taking anything the program holds.

Every value is uniform in [-sqrt(3), sqrt(3)] times the weight's standard
deviation (uniform bits convert to floats exactly, so a layer built alone
equals the same layer built in the stack): norm scales are 1, the token
embedding has std 0.02, the router 3/sqrt(fan_in) and every other matrix
1/sqrt(fan_in).

The router's logits thus spread with a standard deviation of about 3, so
that, as in a trained router, a token's first experts carry most of its
weight.  With a flat router (std 0.02, logits of spread 0.9) each of the
eight experts carries about an eighth, a rounding flip at the eighth place
moves a token's output by an eighth of the MoE output, and the widest
logit gap of the bfloat16 program read as wide as that of the float8
control (0.16-0.75 against 0.43-0.76 on qwen3-30b-a3b-1chip, TPU v5e).
"""

from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Layout = Dict[str, Tuple[Tuple[int, ...], bool]]     # path -> (shape, stacked)
LAYER = "segments/0/pattern/0/"


def layout(c: dict) -> Layout:
    d, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    hd = c["head_dim"]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    out: Layout = {"embed/tok": ((v, d), False),
                   "final_norm/scale": ((d,), False)}
    if not c["tie_word_embeddings"]:
        out["embed/lm_head"] = ((d, v), False)
    per_layer = {"ln1/scale": (d,), "ln2/scale": (d,),
                 "attn/w_q": (d, nq * hd), "attn/w_k": (d, nkv * hd),
                 "attn/w_v": (d, nkv * hd), "attn/w_o": (nq * hd, d)}
    if c.get("num_experts"):
        e, f = c["num_experts"], c["moe_intermediate_size"]
        per_layer.update({"moe/router": (d, e), "moe/w_gate": (e, d, f),
                          "moe/w_up": (e, d, f), "moe/w_down": (e, f, d)})
    else:
        f = c["intermediate_size"]
        per_layer.update({"mlp/w_gate": (d, f), "mlp/w_up": (d, f),
                          "mlp/w_down": (f, d)})
    for name, shape in per_layer.items():
        out[LAYER + name] = ((n,) + shape, True)
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (large ones included)."""
    s = int(np.random.SeedSequence([seed, 2]).generate_state(1)[0])
    return jax.random.PRNGKey(s & 0x7FFFFFFF)


def _std(path: str, shape) -> float:
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return 0.0
    if name == "tok":
        return 0.02
    return (3.0 if name == "router" else 1.0) * float(shape[-2]) ** -0.5


def leaf(key: jax.Array, path: str, shape, layer, dtype) -> jax.Array:
    """One layer (``layer``, traced or not; 0 for unstacked weights) of
    the weight at ``path``, of per-layer ``shape``."""
    std = _std(path, shape)
    if std == 0.0:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(
        key, zlib.crc32(path.encode()) & 0x7FFFFFFF), layer)
    u = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0)
    return (u * (std * 3.0 ** 0.5)).astype(dtype)


def _nest(flat: Dict[str, jax.Array]):
    tree: dict = {}
    for path, arr in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr

    def lists(node):
        if not isinstance(node, dict):
            return node
        if all(k.isdigit() for k in node):
            return [lists(node[k]) for k in sorted(node, key=int)]
        return {k: lists(v) for k, v in node.items()}
    return lists(tree)


def make(lay: Layout, seed: int, dtype):
    """The whole parameter tree, on the device, in one jitted call."""
    def build(key):
        flat = {}
        for path, (shape, stacked) in lay.items():
            if stacked:
                flat[path] = jax.lax.map(
                    lambda i, p=path, s=shape[1:]: leaf(key, p, s, i, dtype),
                    jnp.arange(shape[0]))
            else:
                flat[path] = leaf(key, path, shape, 0, dtype)
        return _nest(flat)
    return jax.jit(build)(seed_key(seed))


def tree_layout(params) -> Dict[str, Tuple[int, ...]]:
    """path -> shape of a parameter tree (to check the program's layout
    against ``layout``)."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out[key] = tuple(x.shape)
    return out
