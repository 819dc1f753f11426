"""Request sizes and gaps that are the same work for every seed.

A mix draws its prompt and output lengths from clipped lognormals and its
open-loop gaps from an exponential, but by quantiles, not by sampling:
``n`` evenly spaced quantiles make one cycle's multiset of sizes and gaps.
They come in one spread-out order (``spread_order``), the same for every
seed: consecutive requests take quantiles far apart, so any stretch of a
few requests holds short and long ones alike.  The seed draws the token
ids (and the weights), which change no work.

Why not let the seed permute them: a window of 51 s sees about 14 chat
arrivals, fewer than live through it (a request lives about 70 s), so
which requests fill the window depended on the order, and six seeds read
43-56 tokens/s on the chip where two runs of one seed agreed within 2%.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Spec:
    """One request a generator sends."""
    index: int
    due: float                 # seconds after the traffic starts
    prompt_len: int
    out_len: int


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at quantiles (i + 0.5) / n of a lognormal
    with ``spec``'s median and sigma, clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """``n`` gaps at exponential quantiles, scaled to a mean of exactly
    1 / rate."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g / g.mean() / rate_per_s


# one irrational step per stream, so that the orders of prompts, answers
# and gaps are not alike
_STEPS = (0.6180339887498949, 0.41421356237309515, 0.7320508075688772)


def spread_order(n: int, stream: int, cycle: int) -> np.ndarray:
    """A permutation of range(n) for ``cycle`` of a stream: the ranks of
    frac(k * step + 1/2) over the cycle's positions k (a Weyl sequence:
    any run of consecutive positions is spread evenly over the ranks)."""
    k = cycle * n + np.arange(n)
    return np.argsort(np.argsort((k * _STEPS[stream] + 0.5) % 1.0))


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent stream per (seed, purpose, index)."""
    return np.random.default_rng([int(seed) % 2**63, *stream])


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> list:
    return rng_for(seed, 7, index).integers(0, vocab, size=length).tolist()
