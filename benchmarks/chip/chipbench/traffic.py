"""Traffic generation from a mix file (``traffic/<mix>.json``) and a seed.

Lengths follow lognormals fitted to a (mean, std) pair, as the program's
``serving/traffic.py`` fits them to the paper's Table 4; the fit is copied
here so that the yardstick cannot move with the program.

The mix's ``schedule_seed`` fixes the schedule once: the inter-arrival
gaps are the stratified quantiles of an exponential and the prompt and
output lengths those of their lognormals, each list shuffled by that
seed.  Every run's ``--seed`` replays the same schedule and draws only its
token ids (and the weights), so two seeds offer the same work at the same
times and differ in content alone.  (With the order drawn from the run's
seed instead, ttft_p90_s read 0.56-6.2 s across seeds at one load on the
chip: a burst of long prompts early in a window queues the serial prefill
cohorts behind each other.)

Arrivals are open loop: independent users, each request due at its
time whatever the system is doing.

Mix keys:
  rate_rps      offered requests per second
  input_len / output_len  {"mean", "std", "lo", "hi"}; a missing input
                "hi" is the configuration's max_len minus the largest
                output, so that prompt + output never exceeds max_len
  slo           {"ttft_s", "tbt_s"}: the limits ``slo_ok_pct`` judges
  ref_requests  how many finished requests the correctness check samples
  schedule_seed the seed of the schedule (gaps and lengths)
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Tuple

import numpy as np


@dataclass(frozen=True)
class LengthModel:
    mean: float
    std: float
    lo: int
    hi: int

    def mu_sigma(self) -> Tuple[float, float]:
        # lognormal with mean m and std s:
        # sigma^2 = ln(1 + s^2/m^2); mu = ln m - sigma^2/2
        m, s = self.mean, self.std
        sigma2 = math.log(1.0 + (s * s) / (m * m))
        return math.log(m) - sigma2 / 2.0, math.sqrt(sigma2)

    def quantiles(self, n: int) -> np.ndarray:
        """The ``n`` stratified quantiles (i + 0.5) / n, clipped to
        [lo, hi], in ascending order."""
        mu, sigma = self.mu_sigma()
        z = NormalDist()
        x = [math.exp(mu + sigma * z.inv_cdf((i + 0.5) / n))
             for i in range(n)]
        return np.clip(np.rint(x), self.lo, self.hi).astype(np.int64)


@dataclass(frozen=True)
class Request:
    arrival_s: float            # due time, seconds after the window opens
    prompt: Tuple[int, ...]
    max_new: int


def length_models(mix: dict, max_len: int) -> Tuple[LengthModel,
                                                     LengthModel]:
    o = mix["output_len"]
    out = LengthModel(o["mean"], o["std"], o["lo"], o["hi"])
    i = mix["input_len"]
    hi = i.get("hi") or max_len - out.hi
    if hi + out.hi > max_len or hi < i["lo"]:
        raise ValueError(f"input hi {hi} + output hi {out.hi} does not fit "
                         f"max_len {max_len}")
    return LengthModel(i["mean"], i["std"], i["lo"], hi), out


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def open_loop(mix: dict, seconds: float, seed: int, max_len: int,
              vocab: int) -> List[Request]:
    """round(rate x seconds) requests due in [0, seconds): gaps are the
    stratified quantiles of an exponential, shuffled and scaled so that
    they add up to the window."""
    n = max(1, int(round(mix["rate_rps"] * seconds)))
    ins, outs = length_models(mix, max_len)
    rng = _rng(mix["schedule_seed"], 0)
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = rng.permutation(gaps)
    gaps *= seconds / gaps.sum()
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    p_len = rng.permutation(ins.quantiles(n))
    o_len = rng.permutation(outs.quantiles(n))
    tok = _rng(seed, 1)
    return [Request(float(a), tuple(int(t) for t in
                                    tok.integers(1, vocab, int(p))), int(o))
            for a, p, o in zip(arrivals, p_len, o_len)]


def edge_lengths(lo: int, hi: int, quantum: int, n_layers: int) -> List[int]:
    """Prompt lengths at every power-of-two edge and at every multiple of
    the scheduler's work quantum up to ``quantum x n_layers`` (past it the
    layered scheduler's group count, ceil(n / quantum), is capped at the
    number of layers) in [lo, hi], plus both ends.  Between two neighbours
    of this list, a prompt's padded token bucket and its layer group
    count stay the same, so serving one prompt of each length compiles
    every prefill shape that any prompt in [lo, hi] can use."""
    pts = {lo, hi}
    k = 1
    while k <= hi:
        pts.update((k, k + 1))
        k *= 2
    for m in range(quantum, min(hi, quantum * n_layers) + 1, quantum):
        pts.update((m, m + 1))
    return sorted(p for p in pts if lo <= p <= hi)
