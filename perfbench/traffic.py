"""The one traffic generator: a mix file's parameters and a seed in,
request sizes and send times out.

A mix is ``traffic/<mix>.json``::

    {"loop": "open", "rate_per_s": 60.0,
     "sizes": [16, 64], "weights": [0.5, 0.5], "why": "..."}
    {"loop": "closed", "clients": 512,
     "sizes": [16, 64], "weights": [0.5, 0.5], "why": "..."}

Every seed gets the same work in another order.  Requests come in
blocks of ``BLOCK``: in each block the sizes come in exact proportion
to the weights and the gaps are the same set of exponential quantiles
(a Poisson process's gaps at mean ``1/rate``), both shuffled within the
block by the seed.  So rare long requests are spread over the window
alike for every seed, and seeds differ in order, not in how the work
clusters.  An open loop sends exactly ``round(rate * seconds)``
requests; a closed loop draws its sizes from the same blocks.
"""

from __future__ import annotations

import math

import numpy as np

BLOCK = 100


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    s = int(seed) % (1 << 64)
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, *stream.encode()])


def class_counts(weights, n: int) -> list:
    """``n`` split by ``weights`` with largest remainders: the counts sum
    to ``n`` and each is within one of its exact share."""
    total = float(sum(weights))
    exact = [w * n / total for w in weights]
    counts = [math.floor(x) for x in exact]
    order = sorted(range(len(exact)), key=lambda i: counts[i] - exact[i])
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def _sizes(mix: dict, n: int, r: np.random.Generator) -> list:
    sizes = [s for s, c in zip(mix["sizes"],
                               class_counts(mix["weights"], n))
             for _ in range(c)]
    r.shuffle(sizes)
    return [int(s) for s in sizes]


def _gaps(n: int, r: np.random.Generator) -> np.ndarray:
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)   # Exp(1) quantiles
    r.shuffle(gaps)
    return gaps


def open_schedule(mix: dict, seed: int, seconds: float) -> list:
    """``[(offset_s, size)]`` of an open loop over ``[0, seconds)``."""
    if mix["loop"] != "open":
        raise ValueError(f"not an open-loop mix: {mix['loop']!r}")
    n = max(1, round(float(mix["rate_per_s"]) * seconds))
    r = rng(seed, "open")
    blocks = [min(BLOCK, n - k) for k in range(0, n, BLOCK)]
    gaps = np.concatenate([_gaps(b, r) for b in blocks])
    sizes = [s for b in blocks for s in _sizes(mix, b, r)]
    offsets = np.cumsum(gaps) - gaps[0]
    offsets *= seconds / (offsets[-1] + gaps.mean())   # rate is n/seconds
    return list(zip(offsets.tolist(), sizes))


def closed_sizes(mix: dict, seed: int):
    """Endless request sizes of a closed loop, in shuffled blocks."""
    if mix["loop"] != "closed":
        raise ValueError(f"not a closed-loop mix: {mix['loop']!r}")
    r = rng(seed, "closed")
    while True:
        yield from _sizes(mix, BLOCK, r)


def geometries(mix: dict) -> list:
    """Every request size the mix can send: what set-up must warm."""
    return sorted({int(s) for s, w in zip(mix["sizes"], mix["weights"])
                   if w > 0})
