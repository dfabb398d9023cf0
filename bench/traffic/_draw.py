"""Stratified draws shared by the traffic generators.

Every seed gets the same multiset of lengths and gaps in each block of
`block` consecutive draws, in another order: block i holds the values of
the distribution at probabilities (j + 0.5) / block, j = 0..block-1,
permuted by the seed.  So a window that covers whole blocks sees the same
work under every seed, and the seed changes only the order and the
token contents.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The n stratified values of `spec` (probabilities (j+0.5)/n).

    spec: {"dist": "lognormal", "median", "sigma"} |
          {"dist": "uniform", "min", "max"} (whole numbers, inclusive) |
          {"dist": "exponential", "mean"};
    "min" / "max" clip lognormal and exponential values."""
    p = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(q)) for q in p])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        lo, hi = spec["min"], spec["max"]
        return np.minimum(lo + np.floor(p * (hi - lo + 1)), hi)
    elif dist == "exponential":
        v = -spec["mean"] * np.log1p(-p)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.clip(v, spec.get("min", -np.inf), spec.get("max", np.inf))


def blocked(spec: dict, n: int, block: int,
            rng: np.random.Generator) -> np.ndarray:
    """n draws: whole blocks of stratified values, each block permuted."""
    base = quantiles(spec, block)
    out = [base[rng.permutation(block)] for _ in range(-(-n // block))]
    return np.concatenate(out)[:n]


def lengths(spec: dict, n: int, block: int,
            rng: np.random.Generator) -> np.ndarray:
    return np.maximum(np.rint(blocked(spec, n, block, rng)), 1).astype(int)


def tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(n)).astype(np.int32)


class Arrival:
    """One request as the generator hands it to the harness."""

    __slots__ = ("rid", "prompt", "max_new", "due")

    def __init__(self, rid: int, prompt: np.ndarray, max_new: int,
                 due: float):
        self.rid = rid
        self.prompt = prompt
        self.max_new = int(max_new)
        self.due = float(due)            # seconds after the first due
