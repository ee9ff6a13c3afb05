"""Traffic primitives shared by the generator kinds (traffic_kinds/*.py).

Every seed gets the same multiset of request sizes and inter-arrival gaps,
each drawn at the stratified quantiles (i + 0.5) / n of its distribution;
the seed only orders them and picks the prompt token ids.  So two seeds
offer the same work in a different order, and run-to-run spread is not the
spread of the work itself.  A traffic file may fix the order with
"schedule_seed", as a recorded trace does; the token ids still follow the
run's seed.

Distributions (a dict in the traffic file):
  {"dist": "lognormal", "median": m, "sigma": s, "min": lo, "max": hi}
  {"dist": "uniform", "min": lo, "max": hi}          inclusive integers
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request: due `t` seconds after the traffic starts."""

    t: float
    prompt: np.ndarray            # int32 token ids
    max_new: int


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """n integer lengths at the stratified quantiles of `dist`, sorted."""
    u = quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = np.round(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
    elif kind == "uniform":
        v = np.floor(lo + u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """n gaps at the stratified quantiles of Exp(rate): a Poisson process's
    inter-arrival times, sorted."""
    return -np.log1p(-quantiles(n)) / float(rate)


def order_rng(spec: dict, seed: int) -> np.random.Generator:
    """The generator that orders sizes and gaps: the traffic file's
    "schedule_seed" where it has one, else the run's seed."""
    return np.random.default_rng([int(spec.get("schedule_seed", seed)), 1])


def sizes(rng, n: int, prompt: dict, output: dict):
    """n prompt and n output lengths, each multiset fixed, in rng's order."""
    return (rng.permutation(lengths(prompt, n)),
            rng.permutation(lengths(output, n)))


def poisson_times(rng, rate: float, n: int, span: float) -> np.ndarray:
    """n arrival times in (0, span): n + 1 stratified exponential gaps in
    rng's order, scaled so that together they fill `span`.  A Poisson
    process of this rate holds n arrivals in `span` on average, and given
    that count its arrivals are spread like these."""
    gaps = rng.permutation(exponential_gaps(rate, n + 1))
    return np.cumsum(gaps)[:n] * (float(span) / gaps.sum())


def arrivals(seed: int, t, p_len, o_len, vocab: int) -> List[Arrival]:
    """Requests due at `t` with the given lengths; token ids from the
    seed."""
    rng = np.random.default_rng([int(seed), 0])
    return [Arrival(t=float(t[i]),
                    prompt=rng.integers(0, vocab, int(p_len[i]),
                                        dtype=np.int32),
                    max_new=int(o_len[i]))
            for i in range(len(t))]


def count_for(rate: float, seconds: float) -> int:
    return int(math.ceil(rate * seconds))
