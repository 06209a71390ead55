"""Seeded draws for traffic: every length and arrival of a run comes from
``--seed``, and the amount of work in a run is as near constant across
seeds as a random draw allows.

Lengths are drawn *stratified*: n draws take one value from each of n equal
slices of the distribution, in a seeded order. The sum over a run then
barely moves with the seed, while which request gets which length does.
Without it the offered tokens of a 250-request run swing by ~6% from seed
to seed, and a tail latency near the knee by much more.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def lengths(dist: dict, n: int, rng: np.random.Generator,
            order=None) -> np.ndarray:
    """n integer lengths from ``dist``, stratified, in seeded order (or in
    ``order``, a permutation of range(n): which slice each draw takes).

    ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
    ``{"dist": "uniform", "min", "max"}`` or ``{"dist": "const", "value"}``;
    ``multiple_of`` rounds down to a multiple (page-aligned documents)."""
    kind = dist["dist"]
    if kind == "const":
        out = np.full((n,), float(dist["value"]))
    else:
        slices = rng.permutation(n) if order is None else np.asarray(order)
        u = (slices + rng.random(n)) / n
        if kind == "uniform":
            out = dist["min"] + u * (dist["max"] - dist["min"])
        elif kind == "lognormal":
            z = np.array([NormalDist().inv_cdf(x) for x in
                          np.clip(u, 1e-9, 1 - 1e-9)])
            out = dist["median"] * np.exp(dist["sigma"] * z)
        else:
            raise ValueError(f"unknown length distribution {kind!r}")
        out = np.clip(out, dist["min"], dist["max"])
    mult = int(dist.get("multiple_of", 1))
    return (out.astype(np.int64) // mult * mult).astype(np.int64)


def arrivals(kind: str, rate: float, duration: float,
             rng: np.random.Generator) -> np.ndarray:
    """Sorted send instants in [0, duration). ``stratified``: one request
    in each slot of 1 / rate seconds, at a seeded instant inside it —
    independent users, and every seed offers the same number of requests.
    A mix with another arrival process adds its kind here."""
    n = max(int(round(rate * duration)), 1)
    if kind == "stratified":
        return (np.arange(n) + rng.random(n)) / rate
    raise ValueError(f"unknown arrival process {kind!r}")
