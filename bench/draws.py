"""Request sizes drawn by stratified quantiles.

Every block of ``stratum`` requests takes the quantiles ``(i + 0.5) / stratum``
of a distribution, shuffled by the seed's generator: every seed then sends the
same amount of work, in another order and with other token ids.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_UNIT = NormalDist()


def quantile(spec: dict, u: float) -> float:
    """The ``u`` quantile of a length distribution, clipped to its range."""
    if spec["dist"] == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * _UNIT.inv_cdf(u))
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    elif spec["dist"] == "exponential":
        x = -spec["mean"] * math.log1p(-u)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return min(max(x, spec.get("min", -math.inf)), spec.get("max", math.inf))


def stratified(spec: dict, rng: np.random.Generator, stratum: int):
    """Endless draws of ``spec``: each block of ``stratum`` is a shuffled
    copy of the same quantiles."""
    us = (np.arange(stratum) + 0.5) / stratum
    while True:
        for u in rng.permutation(us):
            yield quantile(spec, float(u))


def poisson_gaps(rate: float, rng: np.random.Generator, stratum: int):
    """Endless inter-arrival gaps of a Poisson process at ``rate``: each block
    of ``stratum`` is a shuffled copy of the exponential's quantiles, scaled
    so that the block's mean gap is exactly ``1 / rate``."""
    us = (np.arange(stratum) + 0.5) / stratum
    q = np.array([quantile({"dist": "exponential", "mean": 1.0}, u) for u in us])
    q *= stratum / (rate * q.sum())
    while True:
        yield from rng.permutation(q).tolist()


def lengths(spec: dict, rng: np.random.Generator, stratum: int):
    for x in stratified(spec, rng, stratum):
        yield int(x)
