"""The one general traffic generator: lengths, arrivals and tokens from a
traffic file's parameters and `--seed`.

Every seed gets the SAME prompt lengths, output lengths and inter-arrival
gaps in the SAME order: the values are the quantile grid of the stated
distribution, shuffled once by the traffic file's `order_seed`; `--seed`
draws the token ids (and the weights). Two seeds therefore differ in what
the tokens are, never in the amount of work or in how it queues. (Shuffling
by `--seed` was tried first: with one prefill lane at four fifths of its
capacity the order alone moved a 95th percentile by a third, PERF.md 6.)
The length and arrival formulas are copied from
`ray_lightning_tpu/loadgen/generator.py` (`_bounded_pareto`, exponential
gaps of a Poisson process); arrivals here are in wall seconds, not ticks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class PlannedRequest:
    rid: str
    prompt: np.ndarray            # int32 token ids
    max_new_tokens: int
    temperature: float
    top_k: Optional[int]
    seed: int
    due_s: float = 0.0            # open loop: seconds after the window opens


def bounded_pareto(u: float, lo: int, hi: int, alpha: float) -> int:
    """Inverse CDF of the Pareto truncated to [lo, hi]."""
    if hi <= lo:
        return lo
    ratio = (lo / hi) ** alpha
    x = lo * (1.0 - u * (1.0 - ratio)) ** (-1.0 / alpha)
    return int(min(hi, max(lo, x)))


def quantile_grid(spec: dict, n: int) -> np.ndarray:
    """`n` values of the distribution at u = (i + 1/2) / n."""
    if spec["dist"] == "bounded_pareto":
        return np.array([bounded_pareto((i + 0.5) / n, spec["lo"], spec["hi"],
                                        spec["alpha"]) for i in range(n)],
                        np.int64)
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def exponential_gaps(n: int, rate_per_s: float) -> np.ndarray:
    """Quantile grid of a Poisson process's gaps, scaled so that `n` of them
    span exactly n / rate seconds."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    return gaps * (n / rate_per_s) / gaps.sum()


def _sampling(i: int, s: dict):
    greedy = i % int(s.get("greedy_every", 1)) == 0
    temperature = 0.0 if greedy else float(s["temperature"])
    every = int(s.get("top_k_every", 0))
    top_k = int(s["top_k"]) if (not greedy and every
                                and i % every == every - 1) else None
    return temperature, top_k


def _requests(rng, prompt_lens, output_lens, vocab: int, sampling: dict,
              start: int = 0) -> List[PlannedRequest]:
    out = []
    for j, (pl, ol) in enumerate(zip(prompt_lens, output_lens)):
        i = start + j
        temperature, top_k = _sampling(i, sampling)
        out.append(PlannedRequest(
            rid=f"r{i:05d}",
            prompt=rng.integers(0, vocab, int(pl)).astype(np.int32),
            max_new_tokens=int(ol), temperature=temperature, top_k=top_k,
            seed=100 + i))
    return out


def open_loop(traffic: dict, vocab: int, seed: int,
              seconds: float) -> List[PlannedRequest]:
    """Requests due in [0, seconds), Poisson-like gaps at the fixed rate."""
    rate = float(traffic["arrivals"]["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    order = np.random.default_rng(int(traffic.get("order_seed", 0)))
    gaps = order.permutation(exponential_gaps(n, rate))
    plens = order.permutation(quantile_grid(traffic["prompt_len"], n))
    olens = order.permutation(quantile_grid(traffic["output_len"], n))
    due = np.cumsum(gaps) - gaps[0] * 0.5
    due = np.minimum(due, math.nextafter(seconds, 0.0))
    reqs = _requests(np.random.default_rng(int(seed)), plens, olens, vocab,
                     traffic["sampling"])
    for r, t in zip(reqs, due):
        r.due_s = float(t)
    return reqs


class ClosedLoopSource:
    """An endless stream for waiting clients: the same cycle of `pool_size`
    requests (lengths in the traffic file's fixed order) again and again,
    the token ids drawn from the seed."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.traffic, self.vocab = traffic, vocab
        self.rng = np.random.default_rng(int(seed))
        n = int(traffic["pool_size"])
        order = np.random.default_rng(int(traffic.get("order_seed", 0)))
        self.plens = order.permutation(quantile_grid(traffic["prompt_len"], n))
        self.olens = order.permutation(quantile_grid(traffic["output_len"], n))
        self._buf: List[PlannedRequest] = []
        self._made = 0

    def next(self) -> PlannedRequest:
        if not self._buf:
            self._buf = _requests(self.rng, self.plens, self.olens,
                                  self.vocab, self.traffic["sampling"],
                                  self._made)
            self._made += len(self.plens)
            self._buf.reverse()
        return self._buf.pop()


def train_tokens(vocab: int, seed: int, rows: int, seq: int) -> np.ndarray:
    """[rows, seq + 1] int32 token ids; every row differs."""
    rng = np.random.default_rng(int(seed))
    return rng.integers(0, vocab, (rows, seq + 1)).astype(np.int32)
