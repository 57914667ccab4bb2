"""Requests from a traffic mix file and a seed.

A mix gives the lengths of prompts and outputs as clipped lognormals and,
for open-loop traffic, an arrival rate.  Requests come in blocks of
``block`` requests.  Every block holds the same multiset of sizes and
inter-arrival gaps: the stratified quantiles ``(i + 0.5) / block`` of the
mix's distributions.  The seed only shuffles them within each block and
draws the token ids.  So every seed offers the same work, in another
order, and runs with different seeds differ as little as the order lets
them.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    due_s: float            # seconds after the traffic's origin
    prompt: List[int]
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(spec: dict, n: int) -> np.ndarray:
    """n stratified sizes of a lognormal, rounded and clipped to the spec's
    [min, max]."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    v = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """n stratified gaps of a Poisson process at ``rate_per_s``."""
    return -np.log1p(-_quantiles(n)) / rate_per_s


def stream(mix: dict, seed: int, vocab: int) -> Iterator[Req]:
    """Endless, deterministic request stream of a mix for one seed."""
    rng = np.random.default_rng(seed)
    block = int(mix.get("block", 64))
    prompts = lognormal_sizes(mix["prompt"], block)
    outputs = lognormal_sizes(mix["output"], block)
    rate = mix.get("rate_per_s")
    gaps = exponential_gaps(rate, block) if rate else np.zeros(block)
    rid, due = 0, 0.0
    while True:
        p = rng.permutation(prompts)
        o = rng.permutation(outputs)
        g = rng.permutation(gaps)
        toks = rng.integers(0, vocab, size=int(p.sum()))
        cut = np.concatenate([[0], np.cumsum(p)])
        for i in range(block):
            due += float(g[i])
            yield Req(rid=rid, due_s=due,
                      prompt=toks[cut[i]:cut[i + 1]].tolist(),
                      max_new=int(o[i]))
            rid += 1


def take(mix: dict, seed: int, vocab: int, n: int) -> List[Req]:
    it = stream(mix, seed, vocab)
    return [next(it) for _ in range(n)]
