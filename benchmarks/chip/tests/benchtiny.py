"""Tiny stand-ins for the benchmark's configurations and mixes, small
enough for the CPU: the same published keys, the same program registry
entries, widths cut down."""

from __future__ import annotations

import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
for p in (CHIP, CHIP.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def config(kind: str = "moe", dtype: str = "float32") -> dict:
    """In float32 the program computes the reference's function to
    rounding, so a sound run's logit gaps are under 1e-3 and any
    fault or lower precision stands out.  (In bfloat16 at this size, with
    4 experts of which 2 serve a token, a rounding flip in routing moves a
    logit by up to 0.8.)"""
    from repro.models.config import MoEConfig
    name = "qwen3-30b-a3b-1chip" if kind == "moe" else "phi4-mini-3.8b"
    conf = json.loads((CHIP / "configs" / f"{name}.json").read_text())
    shape = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, vocab_size=512,
                 torch_dtype=dtype)
    over = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                head_dim=16, vocab_size=512, dtype=dtype, param_dtype=dtype)
    if kind == "moe":
        shape.update(num_experts=4, num_experts_per_tok=2,
                     moe_intermediate_size=32)
        over["moe"] = MoEConfig(n_experts=4, top_k=2, expert_d_ff=32)
    else:
        shape.update(intermediate_size=96)
        over["d_ff"] = 96
    conf.update(shape)
    conf["program"]["overrides"] = dict(conf["program"]["overrides"], **over)
    conf["serve"] = dict(conf["serve"], slots=4, max_len=128, quantum=32)
    conf["check"] = {"mean_logit_gap": 1e-3, "widest_logit_gap": 1e-3}
    return conf


def mix() -> dict:
    m = json.loads((CHIP / "traffic" / "sharegpt-poisson-a3b.json")
                   .read_text())
    m.update(rate_rps=4.0,
             input_len={"mean": 40, "std": 20, "lo": 17, "hi": 40},
             output_len={"mean": 8, "std": 4, "lo": 4, "hi": 12},
             ref_requests=8)
    return m


def cell(kind: str = "moe") -> dict:
    from chipbench import spec
    b = spec.load_benchmark()
    metrics = b["end_to_end"] + b["per_layer"]
    return {"config": config(kind), "traffic": mix(),
            "workload": {"chips": 1},
            "end_to_end": [m["name"] for m in b["end_to_end"]],
            "per_layer": [m["name"] for m in b["per_layer"]],
            "units": {m["name"]: m["unit"] for m in metrics}}
