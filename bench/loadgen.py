"""The one traffic generator: request plans from a mix file and a seed.

Every seed gets the same set of requests in another order. The set: prompt
and output lengths at their distributions' quantiles (i + 0.5) / n,
paired, and a share of them marked to sample, by one fixed shuffle (the
two lengths are independent). Poisson gaps are the exponential's
quantiles scaled so the window holds exactly ``round(rate * seconds)``
arrivals. The run's seed orders the requests and, apart, the gaps, and
draws the prompt tokens, each request's task (half the requests each way
over two tasks), the sampling streams and the weights (bench/system.py).

Arrival kinds:

* ``poisson``: open loop at the cell's ``rate_rps`` (bench/cells); each
  request falls due at its arrival time, whether or not earlier ones
  have finished.
* ``backlog``: offline work; the queue is topped up to ``backlog``
  waiting requests before every tick, in blocks of ``block`` requests
  that each hold the same sizes, each block in its own order.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np


@dataclass
class Spec:
    rid: int
    due: Optional[float]        # seconds after the window opens (None: backlog)
    prompt: np.ndarray          # (s,) int32
    task: int
    max_new: int
    temperature: float          # 0.0 = greedy
    top_p: float
    sample_seed: int


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``dist``."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = lo + q * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, 1]))


def _order(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, 3]))


def requests(mix: dict, n: int):
    """The set of ``n`` requests, the same for every seed: (prompt
    lengths, output lengths, sampled flags)."""
    fixed = np.random.default_rng(np.random.SeedSequence([0, 4]))
    prompts = quantile_lengths(mix["prompt_len"], n)
    outs = fixed.permutation(quantile_lengths(mix["output_len"], n))
    sampled = np.zeros(n, bool)
    n_sampled = int(round(mix.get("sampled_share", 0.0) * n))
    sampled[fixed.permutation(n)[:n_sampled]] = True
    return prompts, outs, sampled


def _specs(mix: dict, order, rng, n: int, rid0: int, n_tasks: int,
           vocab: int, dues) -> List[Spec]:
    idx = order.permutation(n)
    prompts, outs, sampled = (a[idx] for a in requests(mix, n))
    tasks = rng.permutation(np.arange(n) % n_tasks)
    specs = []
    for i in range(n):
        temp = float(mix["temperature"]) if sampled[i] else 0.0
        specs.append(Spec(
            rid=rid0 + i, due=None if dues is None else float(dues[i]),
            prompt=rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
            task=int(tasks[i]), max_new=int(outs[i]), temperature=temp,
            top_p=float(mix.get("top_p", 1.0)) if sampled[i] else 1.0,
            sample_seed=int(rng.integers(0, 2**31 - 1))))
    return specs


def poisson_plan(mix: dict, rate: float, seconds: float, seed: int,
                 n_tasks: int, vocab: int) -> List[Spec]:
    """The window's requests, in order of their due times in (0, seconds)."""
    n = max(1, int(round(rate * seconds)))
    order = _order(seed)
    q = (np.arange(n + 1) + 0.5) / (n + 1)
    gaps = order.permutation(-np.log1p(-q))
    dues = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    return _specs(mix, order, _rng(seed), n, 0, n_tasks, vocab, dues)


def backlog(mix: dict, seed: int, n_tasks: int, vocab: int) -> Iterator[Spec]:
    """An endless backlog in blocks of ``mix['block']`` requests, every
    block the same sizes, each in an order drawn from the seed."""
    rng, order = _rng(seed), _order(seed)
    block = int(mix["block"])
    rid = 0
    while True:
        yield from _specs(mix, order, rng, block, rid, n_tasks, vocab, None)
        rid += block

