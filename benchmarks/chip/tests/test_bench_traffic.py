"""The benchmark's traffic generator: seed-determinism, the same sizes for
every seed, lengths that match the paper's Table 4, and warm-up lengths
that cover every prefill shape."""

import math

import numpy as np
import pytest

import benchtiny as tiny  # noqa: F401  (puts the benchmark and the program on the path)
from chipbench import traffic as T

SHAREGPT = {"mean": 2340, "std": 2088}
ARXIV = {"mean": 9194, "std": 5754}


@pytest.mark.parametrize("table", [SHAREGPT, ARXIV])
def test_lengths_match_table4_means(table):
    m = T.LengthModel(table["mean"], table["std"], 1, 10 ** 9)
    q = m.quantiles(20000)
    assert abs(q.mean() / table["mean"] - 1) < 0.01
    assert abs(q.std() / table["std"] - 1) < 0.05


def _mix(rate=0.5):
    m = tiny.mix()
    m.update(rate_rps=rate, input_len={"mean": 2340, "std": 2088, "lo": 129},
             output_len={"mean": 438, "std": 265, "lo": 16, "hi": 1024})
    return m


def test_open_loop_is_seed_deterministic():
    a = T.open_loop(_mix(), 40, 2 ** 33 + 5, 4096, 1000)
    b = T.open_loop(_mix(), 40, 2 ** 33 + 5, 4096, 1000)
    c = T.open_loop(_mix(), 40, 2 ** 33 + 6, 4096, 1000)
    assert a == b
    assert a != c


def test_every_seed_replays_the_same_schedule():
    runs = [T.open_loop(_mix(), 40, s, 4096, 1000) for s in (1, 2, 3)]
    for r in runs:
        assert len(r) == 20
        assert r[0].arrival_s == 0 and r[-1].arrival_s < 40
        assert all(len(q.prompt) + q.max_new <= 4096 for q in r)
    shape = [[(q.arrival_s, len(q.prompt), q.max_new) for q in r]
             for r in runs]
    assert shape[0] == shape[1] == shape[2]
    assert runs[0][0].prompt != runs[1][0].prompt
    other = T.open_loop(dict(_mix(), schedule_seed=7), 40, 1, 4096, 1000)
    assert [len(q.prompt) for q in other] != [len(q.prompt) for q in runs[0]]
    assert sorted(len(q.prompt) for q in other) == \
        sorted(len(q.prompt) for q in runs[0])


def test_input_clip_leaves_room_for_the_longest_output():
    ins, outs = T.length_models(_mix(), 4096)
    assert ins.hi + outs.hi == 4096
    with pytest.raises(ValueError):
        T.length_models(_mix(), 1000)


def _shape(n, quantum, n_blocks=7, max_len=4096):
    b = 16
    while b < n:
        b *= 2
    return min(b, max_len), min(max(1, math.ceil(n / quantum)), n_blocks)


@pytest.mark.parametrize("lo,hi,max_len", [(129, 3072, 4096),
                                           (2049, 15872, 16384)])
def test_edge_lengths_cover_every_prefill_shape(lo, hi, max_len):
    q = 512
    edges = T.edge_lengths(lo, hi, q, 7)
    assert edges[0] == lo and edges[-1] == hi
    assert len(edges) <= 16
    assert {_shape(n, q, max_len=max_len) for n in range(lo, hi + 1)} == \
        {_shape(n, q, max_len=max_len) for n in edges}
