"""Traffic from a mix file: request sizes and arrivals, from the seed.

A mix is data (``traffic/<mix>.json``) read by this one generator:

* ``prompt`` / ``output``: a length distribution, ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min",
  "max"}``;
* ``arrival``: ``{"kind": "poisson", "rate"}`` (open loop, requests due on
  a schedule) or ``{"kind": "backlog", "depth"}`` (a queue of ``depth``
  requests waiting beyond the engine's rows, refilled as requests finish);
* ``block``: requests come in blocks of this many. Every block holds the
  same sizes and inter-arrival gaps, the quantiles of the distributions at
  ``(i + 0.5) / block``, placed in an order drawn from the mix's own
  ``order_seed``. The run's seed then swaps neighbouring prompt lengths
  in each block (the shortest with the next, the third with the fourth,
  and so on; the gaps of an open-loop mix alike), each pair with
  probability one half. So every seed asks for the same work in an order
  of its own, and a window of tens of long requests still measures the
  server and not which requests the seed put in it. Output lengths keep
  the mix's order: on the long-document backlog a free reshuffle per
  seed moves a 45 s window's output tokens by about 5% between seeds,
  swapping neighbouring outputs by about 2%, neighbouring prompts by
  under 0.5% (PERF.md). The seed also fills the prompts (and the
  weights).

Generalised from the serve driver's ``make_trace`` / ``requests_from_trace``
(which cycle prompt lengths from a list and draw ``max_new`` uniformly).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator

import numpy as np


@dataclass(frozen=True)
class Draw:
    """One request as the mix asks for it."""
    rid: int
    gap_s: float          # seconds after the previous request is due
    prompt_len: int
    max_new: int


def quantile(dist: dict, u: float) -> int:
    """The length at quantile ``u`` of ``dist``, clipped and rounded."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"]
                                      * NormalDist().inv_cdf(u))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(x), dist["min"]), dist["max"]))


def block_sizes(mix: dict) -> tuple[list, list, list]:
    """The (prompt lengths, output lengths, gaps) every block permutes."""
    n = int(mix["block"])
    us = [(i + 0.5) / n for i in range(n)]
    prompts = [quantile(mix["prompt"], u) for u in us]
    outputs = [quantile(mix["output"], u) for u in us]
    arrival = mix["arrival"]
    if arrival["kind"] == "poisson":
        gaps = [-math.log(1.0 - u) / float(arrival["rate"]) for u in us]
    elif arrival["kind"] == "backlog":
        gaps = [0.0] * n
    else:
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    return prompts, outputs, gaps


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), *stream])


def _swap_neighbours(idx: np.ndarray, rng: np.random.Generator
                     ) -> np.ndarray:
    """``idx`` (a permutation of quantile ranks) with each pair of ranks
    ``2j, 2j + 1`` exchanged where ``rng`` says so: still a permutation."""
    n = len(idx)
    flip = rng.integers(0, 2, size=(n + 1) // 2).astype(bool)
    partner = idx ^ 1
    return np.where(flip[idx // 2] & (partner < n), partner, idx)


def draws(mix: dict, seed: int) -> Iterator[Draw]:
    """The endless request sequence of ``mix`` for ``seed``: sizes and
    gaps only, every block the same quantiles, prompts and gaps in an
    order of the seed's."""
    prompts, outputs, gaps = block_sizes(mix)
    n = len(prompts)
    rid = 0
    order = int(mix.get("order_seed", 0))
    for block in range(1 << 62):
        base, swap = _rng(order, 1, block), _rng(seed, 3, block)
        p, o, g = (base.permutation(n) for _ in range(3))
        p, g = _swap_neighbours(p, swap), _swap_neighbours(g, swap)
        for i in range(n):
            yield Draw(rid, gaps[g[i]], prompts[p[i]], outputs[o[i]])
            rid += 1


def prompt_tokens(seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    """The prompt of request ``rid``: uniform token ids from the seed."""
    return _rng(seed, 2, rid).integers(0, vocab, size=(1, length),
                                       dtype=np.int32)

