"""Lengths and arrival gaps drawn so that every seed gets the SAME set of
sizes in another order: the values are the distribution's quantiles at
(i + 0.5) / n, and the seed only permutes them.  Runs with different seeds
then do the same work, and differ as two runs of one seed do."""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import List, Mapping


def quantile(spec: Mapping, u: float) -> float:
    """The u-quantile (0 < u < 1) of a length distribution given as data:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}``; clipped to [min, max]."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = math.exp(math.log(spec["median"]) + spec["sigma"] * NormalDist().inv_cdf(u))
    elif dist == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "fixed":
        x = spec["value"]
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return min(max(x, spec.get("min", x)), spec.get("max", x))


def stratified_ints(spec: Mapping, n: int, rng: random.Random) -> List[int]:
    """n whole lengths: the n quantiles of ``spec``, permuted by ``rng``."""
    xs = [int(round(quantile(spec, (i + 0.5) / n))) for i in range(n)]
    rng.shuffle(xs)
    return xs


def exponential_gaps(rate: float, horizon_s: float, rng: random.Random) -> List[float]:
    """Gaps between the arrivals of a Poisson process of ``rate`` over
    ``horizon_s``: the n quantiles of the exponential distribution with mean
    1/rate, permuted by ``rng`` and scaled so that the last arrival falls just
    inside the horizon."""
    n = max(1, int(round(rate * horizon_s)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    rng.shuffle(gaps)
    scale = horizon_s / sum(gaps) * (n - 0.5) / n
    return [g * scale for g in gaps]


def prompt_tokens(n: int, vocab: int, rng: random.Random) -> List[int]:
    """n token ids in [1, vocab): no shared prefix between requests."""
    return [rng.randrange(1, vocab) for _ in range(n)]
