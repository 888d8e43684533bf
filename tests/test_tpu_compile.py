"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

Interpret-mode tests cannot see what Mosaic refuses (block shapes off the
(8|16, 128) tiling, rank-1 blocks, too much VMEM), nor whether a program
fits the chip's 16 GB.  These tests compile the Pallas kernels at the widths
of the one-chip Qwen3-30B-A3B configuration and chip_smoke.py's sizes, and
the engine's largest decode and prefill executables at that configuration,
for the chip's compiler.  The topology is described inside a fixture, never
at import, so every test worker collects the same tests."""

from __future__ import annotations

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models.model import DecoderModel  # noqa: E402
from repro.models.moe import ragged_tile_rows  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402

HBM_BYTES = 16e9          # one TPU v5e
HEADROOM = 1.5e9          # what the one-chip configuration leaves free
PAGE = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=one_chip)
    return make


@pytest.fixture(scope="module")
def cfg():
    return get_config(chip_smoke.ARCH)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_cases(cfg, shape):
    b, h, hkv, hd = (chip_smoke.SLOTS, cfg.n_heads, cfg.n_kv_heads,
                     cfg.head_dim_)
    s, i32 = chip_smoke.MAX_LEN, jnp.int32
    n_pages, max_pages = b * s // PAGE, s // PAGE
    e, d, f = cfg.moe.n_experts, cfg.d_model, cfg.moe.expert_d_ff
    pool = shape((hkv, n_pages, PAGE, hd))
    paged = (pool, pool, shape((b, max_pages), i32), shape((b,), i32))
    experts = (shape((e, d, f)), shape((e, d, f)), shape((e, f, d)))
    cases = {
        "paged_decode_attention": (ops.paged_decode_attention,
                                   (shape((b, h, hd)),) + paged),
        "paged_verify_attention": (ops.paged_verify_attention,
                                   (shape((b, 5, h, hd)),) + paged),
        "decode_attention": (ops.decode_attention,
                             (shape((b, h, hd)), shape((b, s, hkv, hd)),
                              shape((b, s, hkv, hd)), shape((b,), i32))),
        "flash_attention": (ops.flash_attention,
                            (shape((1, s, h, hd)), shape((1, s, hkv, hd)),
                             shape((1, s, hkv, hd)))),
    }
    # ragged grouped matmul at a decode batch and at a 1024-token prefill
    for n_tok in (b, 1024):
        m_blk, n_rows = ragged_tile_rows(n_tok * cfg.moe.top_k, e)
        cases[f"moe_gmm_ragged[{n_tok} tokens]"] = (
            functools.partial(ops.moe_gmm_ragged, m_blk=m_blk),
            (shape((n_rows, d)),) + experts
            + (shape((n_rows // m_blk,), i32),))
    return cases


@pytest.mark.parametrize("name", [
    "paged_decode_attention", "paged_verify_attention", "decode_attention",
    "flash_attention", "moe_gmm_ragged[8 tokens]",
    "moe_gmm_ragged[1024 tokens]"])
def test_kernel_compiles_for_v5e(name, cfg, shape):
    fn, args = _kernel_cases(cfg, shape)[name]
    hlo = _compile(fn, *args).as_text()
    assert "tpu_custom_call" in hlo, name


@pytest.fixture(scope="module")
def serving(cfg, shape):
    """The weights' and KV rows' bytes, and the compiled largest decode and
    prefill executables that chip_smoke.py runs."""
    model = DecoderModel(cfg)
    eng = Engine(model, None, "layered", n_slots=1, max_len=PAGE)
    on_chip = functools.partial(jax.tree_util.tree_map,
                                lambda a: shape(a.shape, a.dtype))
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    b, s = chip_smoke.SLOTS, chip_smoke.MAX_LEN
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(b, s)))
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves((params, cache)))
    i32 = jnp.int32
    decode = jax.jit(eng._decode_step_impl, donate_argnums=(1,)).lower(
        params, cache, shape((b, 1), i32), shape((b,), i32),
        shape((b,), bool)).compile()
    # the layered cohort's last group: every slot's 1024-token prompt
    # through the last block, then the LM head for its first token
    p = 1024
    last = jax.jit(functools.partial(eng._prefill_impl, model.n_blocks - 1,
                                     1, True), donate_argnums=(1,))
    prefill = last.lower(params, cache, shape((b, p, cfg.d_model)),
                         shape((b, p), bool), shape((b,), i32),
                         shape((b,), i32), shape((b,), i32)).compile()
    return {"nbytes": nbytes, "kv": cfg.kv_bytes_per_token() * b * s,
            "decode": decode, "prefill": prefill}


def test_one_chip_serving_fits_v5e(serving):
    """Weights, the KV slot rows (and as much again in prefix-cache row
    snapshots) and the temporaries of the largest decode and prefill
    executables chip_smoke.py runs leave the configuration's headroom."""
    temp = max(serving[k].memory_analysis().temp_size_in_bytes
               for k in ("decode", "prefill"))
    used = serving["nbytes"] + serving["kv"] + temp
    assert used <= HBM_BYTES - HEADROOM, (serving["nbytes"], serving["kv"],
                                          temp)


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_routed_experts_read_in_place(name, cfg, serving):
    """No value in the decode step or the last prefill group has one
    layer's expert-stack shape, (E, d, F) or (E, F, d): the ragged tile
    loop reads the layer stack in place rather than a per-layer copy.  The
    decode step's temporaries stay below one layer's expert weights."""
    e, d, f = cfg.moe.n_experts, cfg.d_model, cfg.moe.expert_d_ff
    hlo = serving[name].as_text()
    copies = re.findall(rf"\b[a-z]+[0-9]*\[(?:{e},{d},{f}|{e},{f},{d})\]", hlo)
    assert not copies, (name, len(copies))
    if name == "decode":
        layer_experts = 3 * e * d * f * jnp.dtype(cfg.param_dtype).itemsize
        temp = serving[name].memory_analysis().temp_size_in_bytes
        assert temp < layer_experts, (temp, layer_experts)
