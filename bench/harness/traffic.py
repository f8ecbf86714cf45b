"""The one traffic generator: reads a traffic file and makes the requests
of one run from the seed.

Every run gets the same work in the same order: the prompt lengths,
output lengths and inter-arrival gaps are evenly spaced quantiles of the
file's distributions, put in an order drawn from the file's own
``schedule_seed``. The run's seed draws the token ids (and, elsewhere,
the weights). So two runs differ in what they compute, not in how much
or when: with eight slots, a burst that happens to meet the longest
outputs moves a 95th percentile of time to first token threefold, and
an order drawn from the run's seed would make the seed, not the program,
set the number. Two loops:

* ``open``: independent users. ``round(rate * seconds)`` requests, due at
  the running sum of the gaps; the gaps have mean ``1 / rate`` and the
  file's coefficient of variation (Gamma, CV 1 is Poisson). Due times are
  fixed in advance and kept whatever the server does.
* ``closed``: a queue of documents all due at 0, admitted as slots free.
  The queue is made of blocks of ``block`` documents, each block another
  order of the same lengths, so any whole number of blocks holds the same
  work.

Lengths are lognormal (median, sigma), clipped to [min, max] and rounded
up to a multiple of ``grid``: the served program compiles one prefill
program per padded width, and every width of the grid is warmed up.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np
from scipy.special import gammaincinv


@dataclasses.dataclass
class Item:
    rid: int
    prompt: np.ndarray          # int32 token ids
    out_len: int
    due: float                  # seconds after the window opens


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """n lengths at evenly spaced quantiles of the lognormal, clipped and
    rounded up to the grid."""
    z = np.array([NormalDist().inv_cdf(p) for p in quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.ceil(x), spec["min"], spec["max"])
    g = spec.get("grid", 1)
    return (np.ceil(x / g) * g).astype(np.int64)


def grid(spec: Dict) -> List[int]:
    """Every length the rounding can give."""
    g = spec.get("grid", 1)
    lo = -(-spec["min"] // g) * g
    return list(range(lo, spec["max"] + 1, g))


def gaps(rate: float, cv: float, n: int) -> np.ndarray:
    """n Gamma gaps with mean 1/rate and coefficient of variation cv, at
    evenly spaced quantiles, rescaled to that mean exactly."""
    k = 1.0 / (cv * cv)
    g = gammaincinv(k, quantiles(n))
    return g / g.mean() / rate


def make(traffic: Dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The requests of one run."""
    rng = np.random.default_rng(seed)
    sched = np.random.default_rng(traffic["schedule_seed"])
    if traffic["loop"] == "open":
        n = max(1, round(traffic["rate_per_s"] * seconds))
        gap = sched.permutation(gaps(traffic["rate_per_s"],
                                     traffic["gap_cv"], n))
        due = np.cumsum(gap) - gap[0]
        plen = sched.permutation(lengths(traffic["prompt"], n))
        olen = sched.permutation(lengths(traffic["output"], n))
    elif traffic["loop"] == "closed":
        b, nb = traffic["block"], traffic["blocks"]
        base_p = lengths(traffic["prompt"], b)
        base_o = lengths(traffic["output"], b)
        order = [sched.permutation(b) for _ in range(nb)]
        plen = np.concatenate([base_p[o] for o in order])
        olen = np.concatenate([base_o[o] for o in order])
        n = b * nb
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    return [Item(rid=i, prompt=rng.integers(0, vocab, size=int(p),
                                            dtype=np.int32),
                 out_len=int(o), due=float(t))
            for i, (p, o, t) in enumerate(zip(plen, olen, due))]

