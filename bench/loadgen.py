"""The one traffic generator: a mix's data file in, a seeded request list out.

A mix (``bench/traffic/<mix>.json``) holds only parameters. This module
turns them into requests:

* ``"loop": "open"`` — independent users: Poisson arrivals at
  ``rate_per_s``, each request due at a fixed time whether or not the
  earlier ones have finished;
* ``"loop": "closed"`` — ``clients`` callers, each sending its next request
  when its last one has finished.

Every seed gets the same work in another order. Lengths and inter-arrival
gaps are the quantiles of their distributions at ``(i + 0.5) / n``, so the
multiset of prompt lengths, output lengths and gaps is the same for every
seed; the seed permutes them and draws the token ids. An open loop draws
exactly the ``rate * seconds`` requests due in the window, the gaps scaled
to sum to ``n / rate``, so the last one is due before the close. Runs with different
seeds then differ by where the long requests fall, not by how much work
there is.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

__all__ = ["Req", "quantiles", "rng_for", "pool_size", "make_requests"]


@dataclasses.dataclass(frozen=True)
class Req:
    due_s: float            # open loop: due time from the window's start
    prompt: np.ndarray      # int32 token ids
    max_new: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one purpose of one seed (any non-negative
    int, also past 32 bits)."""
    return np.random.default_rng([int(seed), int(stream)])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution, as ints, sorted.

    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` (both ends included)."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif dist["dist"] == "uniform":
        v = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def pool_size(mix: dict, seconds: float) -> int:
    """How many requests one run draws: an open loop the ones due in the
    window at its rate; a closed loop the mix's pool (cycled if the run
    outlasts it)."""
    if mix["loop"] == "open":
        return max(1, int(math.floor(float(mix["rate_per_s"]) * seconds)))
    return int(mix["pool"])


def make_requests(mix: dict, seed: int, seconds: float,
                  vocab: int) -> List[Req]:
    """The run's requests, in the order they are sent (open loop: by due
    time; closed loop: each caller sends the next one when its last one
    has finished)."""
    n = pool_size(mix, seconds)
    perm = rng_for(seed, 1)
    plen = perm.permutation(quantiles(mix["prompt_len"], n))
    olen = perm.permutation(quantiles(mix["output_len"], n))
    if mix["loop"] == "open":
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u)
        gaps *= n / float(mix["rate_per_s"]) / gaps.sum()
        due = np.cumsum(perm.permutation(gaps)) - gaps.min()
    elif mix["loop"] == "closed":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    toks = rng_for(seed, 2)
    return [Req(due_s=float(due[i]),
                prompt=toks.integers(0, vocab, size=int(plen[i]),
                                     dtype=np.int32),
                max_new=int(olen[i]))
            for i in range(n)]
