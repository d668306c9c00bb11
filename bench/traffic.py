"""The one traffic generator: reads a mix's parameters, draws from a seed.

A mix file (``bench/traffic/<mix>.json``) gives:

- ``loop``: ``"open"`` (requests arrive on a schedule, ``rate_rps``
  Poisson) or ``"closed"`` (``clients`` callers, each sending its next
  request when its last one finishes);
- ``prompt_tokens`` and ``output_tokens``: a distribution each, with
  ``dist`` one of ``lognormal`` (``median``, ``sigma``), ``loguniform``
  or ``uniform``, clipped to ``[min, max]``;
- ``block``: requests per stratified block (closed loop only; an open
  loop's block is the whole window).

Every seed gets the same work in another order. Sizes and inter-arrival
gaps are the distribution's quantiles at the midpoints of ``block``
equal-probability strata, shuffled by the seed, block after block; the
seed also draws the prompts' token ids. So two seeds differ in order and
content, not in how many tokens or requests they ask for.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

DISTS = ("lognormal", "loguniform", "uniform")


def quantile(dist: Dict, u: float) -> int:
    """``dist``'s u-quantile, rounded and clipped to its ``[min, max]``."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        x = math.exp(lo + u * (hi - lo))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown dist {kind!r}; one of {DISTS}")
    return int(min(max(round(x), dist["min"]), dist["max"]))


def strata(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


@dataclass
class Request:
    prompt: List[int]
    max_new: int
    arrival_s: float = 0.0        # open loop: due time from window start


class Traffic:
    """Requests of one mix for one seed.

    ``vocab`` bounds the token ids; ``seconds`` is the window an open
    loop's schedule spans (``round(rate_rps * seconds)`` arrivals).
    """

    def __init__(self, spec: Dict, seed: int, seconds: float, vocab: int):
        self.spec, self.vocab = spec, vocab
        self.loop = spec["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"loop must be open or closed, got {self.loop}")
        self.rng = np.random.default_rng([seed, 0x7AF1C])
        if self.loop == "open":
            self.block = max(1, round(spec["rate_rps"] * seconds))
        else:
            self.block = int(spec["block"])
        self._us = strata(self.block)
        self._sizes = self._block_sizes()

    def _block_sizes(self):
        p = [quantile(self.spec["prompt_tokens"], u) for u in self._us]
        o = [quantile(self.spec["output_tokens"], u) for u in self._us]
        return p, o

    def prompt_lengths(self) -> List[int]:
        """Every prompt length a block holds: the same for every seed."""
        return sorted(set(self._sizes[0]))

    def max_sequence(self) -> int:
        """Longest prompt plus longest output any request can have."""
        return self.spec["prompt_tokens"]["max"] + \
            self.spec["output_tokens"]["max"]

    def _request(self, p: int, o: int, t: float = 0.0) -> Request:
        return Request(self.rng.integers(0, self.vocab, p).tolist(), o, t)

    def blocks(self) -> Iterator[List[Request]]:
        """Closed loop: stratified blocks of requests, without end."""
        while True:
            p, o = (self.rng.permutation(x) for x in self._sizes)
            yield [self._request(int(a), int(b)) for a, b in zip(p, o)]

    def schedule(self) -> List[Request]:
        """Open loop: the window's requests with their due times."""
        rate = self.spec["rate_rps"]
        gaps = self.rng.permutation([-math.log1p(-u) / rate
                                     for u in self._us])
        p, o = (self.rng.permutation(x) for x in self._sizes)
        t = np.cumsum(gaps)
        return [self._request(int(a), int(b), float(ti))
                for a, b, ti in zip(p, o, t)]

    def closed_requests(self) -> Iterator[Request]:
        for blk in self.blocks():
            yield from blk
