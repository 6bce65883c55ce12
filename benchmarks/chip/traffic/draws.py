"""What the traffic generators share: the request they hand the harness,
and draws that give every seed the same work.

A seed must not change how much work a run holds: runs on different
seeds are compared against one bound.  So each size is taken from a
fixed grid of quantiles of its distribution and assigned by a stream
that does not depend on the seed; the seed draws the token ids (and the
program's weights), which change no work.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np


@dataclass
class Spec:
    """One request as the generator sends it: an id, the prompt's token
    ids and how many tokens it asks for after the first (the program
    counts the first token, made by prefill, apart)."""

    id: str
    prompt: Tuple[int, ...]
    max_new: int
    meta: Dict = field(default_factory=dict)

    @property
    def out_len(self) -> int:
        """Tokens served in all: the prefill token and ``max_new``."""
        return self.max_new + 1


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...)."""
    return np.random.default_rng([int(seed) % (1 << 63), *stream])


def grid(n: int) -> np.ndarray:
    """Mid-point quantile levels (i + 1/2) / n."""
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(n: int, median: float, sigma: float, lo: int,
                    hi: int) -> np.ndarray:
    """``n`` sizes at the quantile grid of a lognormal, clipped."""
    from statistics import NormalDist
    z = np.asarray([NormalDist().inv_cdf(float(p)) for p in grid(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def uniform_sizes(n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` integers on the quantile grid of uniform [lo, hi]."""
    return np.floor(lo + grid(n) * (hi - lo + 1)).astype(int)


def exponential_gaps(n: int, mean: float) -> np.ndarray:
    return -mean * np.log1p(-grid(n))


def shuffled(g: np.random.Generator, values) -> List:
    values = list(values)
    order = g.permutation(len(values))
    return [values[i] for i in order]


def tokens(g: np.random.Generator, n: int, vocab: int) -> Tuple[int, ...]:
    return tuple(int(t) for t in g.integers(0, vocab, n))


def zipf_counts(n: int, k: int, s: float) -> List[int]:
    """How many of ``n`` draws fall on each of ``k`` items of Zipf(s)
    popularity, rounded so that they add up to ``n``."""
    w = np.asarray([1.0 / (i + 1) ** s for i in range(k)])
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[:n - counts.sum()]:
        counts[i] += 1
    return [int(c) for c in counts]


def pages(tokens_: int, page: int = 128) -> int:
    return int(math.ceil(tokens_ / page))
