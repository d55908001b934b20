"""Seeded draws shared by the generator kinds.

Every seed gets the same set of sizes and gaps, in another order: a
quantity is drawn as a stratified set of ``block`` values (one value at
each quantile ``(i + 0.5) / block``), visited in a golden-ratio order that
starts where the seed says. Any few consecutive requests then spread over
the whole distribution, so a window that holds only a handful of them (a
closed loop admits few requests in a window) does about the same work on
every seed, and the spread between runs is the system's and not the
traffic's. The seed also draws the token ids.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any non-negative seed."""
    return np.random.default_rng([int(seed), int(stream)])


def quantiles(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def lognormal_set(median: float, sigma: float, lo: int, hi: int, block: int) -> np.ndarray:
    """``block`` integer sizes at the stratified quantiles of a lognormal
    with this median and log-sigma, rounded and clipped to [lo, hi]."""
    z = np.array([_NORMAL.inv_cdf(p) for p in quantiles(block)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


def exponential_set(mean: float, block: int) -> np.ndarray:
    """``block`` gaps at the stratified quantiles of an exponential."""
    return -mean * np.log1p(-quantiles(block))


GOLDEN = 0.6180339887498949
SILVER = 0.4142135623730951  # a second irrational step, so two streams do not move in step


def golden_order(block: int, start: int, ratio: float = GOLDEN) -> np.ndarray:
    """A permutation of ``range(block)`` that steps by ``ratio`` of the block
    from ``start``: consecutive entries lie far apart."""
    step = max(1, round(block * ratio))
    while math.gcd(step, block) != 1:
        step += 1
    return (start + step * np.arange(block)) % block


def ordered_blocks(values: np.ndarray, n: int, gen: np.random.Generator,
                   ratio: float = GOLDEN) -> np.ndarray:
    """``n`` values: ``values`` sorted, then visited block after block in
    golden-ratio order from a start the generator draws."""
    values = np.sort(values)
    order = golden_order(len(values), int(gen.integers(len(values))), ratio)
    return np.resize(values[order], n)


def residual_lives(lengths: np.ndarray, n: int, gen: np.random.Generator) -> np.ndarray:
    """Remaining lengths of ``n`` requests caught in flight at a random
    instant: a request is caught with probability proportional to its
    length, and the remainder is uniform over it. Both are stratified, so
    every seed gets the same ``n`` remainders in another order."""
    lengths = np.sort(lengths)
    cdf = np.cumsum(lengths) / lengths.sum()  # length-biased
    picks = lengths[np.searchsorted(cdf, quantiles(n))]
    u = quantiles(n)[golden_order(n, 0)]
    return gen.permutation(np.maximum(1, np.ceil(u * picks)).astype(np.int64))


def token_ids(gen: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return gen.integers(0, vocab, size=int(n), dtype=np.int64).astype(np.int32)
