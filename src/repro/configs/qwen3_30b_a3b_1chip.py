"""Qwen3-30B-A3B cut to one TPU v5e chip (16 GB HBM): the paper's primary
evaluation model at its published widths, served end to end on one chip.

source:   hf:Qwen/Qwen3-30B-A3B config.json; paper Table 3
reduced:  n_layers 48 -> 7
kept:     d_model 2048, 32 q / 4 kv heads x 128, 128 experts top-8, expert
          d_ff 768, vocab 151936, bfloat16 weights and activations
deployment: the 48 layers as a pipeline of 7 stages (six of 7 layers, the
          last of 6).  This chip holds one 7-layer stage whole: every
          expert, every head and the full vocabulary.  The other 41 layers
          would sit on further chips.  The embedding and the LM head, which
          a pipeline splits between its first and last stage, are both held
          here.

Why 7 layers: one layer is 1.25 GB in bf16, and the embedding plus the LM
head are another 1.25 GB, so 7 layers hold 9.97 GB of weights.  chip_smoke.py
serves 8 slots x 2048 tokens, which is 0.24 GB of KV rows, and the prefix
cache may keep as much again in row snapshots.  The largest executable it
runs is the last layer group of a layered prefill of 8 x 1024 tokens, which
needs about 2.9 GB of temporaries (``compiled.memory_analysis()`` of the v5e
compile; tests/test_tpu_compile.py checks the sum).  That leaves about
2.7 GB of 16 GB free.  An 8th layer would leave 1.3 GB, below the 1.5 GB
margin this configuration keeps.
"""

import dataclasses

from repro.configs.qwen3_30b_a3b import CONFIG as FULL

CONFIG = dataclasses.replace(FULL, name="qwen3-30b-a3b-1chip", n_layers=7)
