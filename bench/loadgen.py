"""Requests of a traffic mix, from its data file and a seed.

A mix (``bench/traffic/<name>.json``) gives a distribution for the prompt
length and one for the output length, each clipped to ``[min, max]``, and
``max_total`` that bounds prompt plus output.  Request ``j`` takes the
quantiles ``u_j = frac(j (sqrt(5) - 1) / 2)`` and ``v_j = frac(j (sqrt(2) -
1))`` of the two distributions.  Both steps are badly approximable
irrationals, so the quantiles are stratified in every run of consecutive
requests: any ``n`` of them leave no gap wider than about ``2/n`` in
``(0, 1)``, and the pairs spread over the square.  The heavy tails are
drawn as often as they should be, in any window.

The lengths are the same under every seed; the seed draws the token ids.
A window serves a few requests to a few tens, and a seed that chose which
lengths fall into it would change the work the window does (a closed-loop
rehearsal at the chip's step times put the spread of tokens/s between
seeds at 19% for four slots of chat traffic), where the seed is to change
the data and not the amount of work."""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

_STEP = ((math.sqrt(5.0) - 1.0) / 2.0, math.sqrt(2.0) - 1.0)
_UNIT = NormalDist()


def quantile(dist: dict, u: float) -> float:
    """The ``u`` quantile of a length distribution, before clipping."""
    if dist["dist"] == "lognormal":
        return dist["median"] * math.exp(dist["sigma"] * _UNIT.inv_cdf(u))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def _length(dist: dict, u: float, hi: int) -> int:
    return int(min(max(round(quantile(dist, u)), dist["min"]),
                   dist["max"], hi))


@dataclass(frozen=True)
class Request:
    prompt: List[int]
    max_new: int


class LoadGen:
    """The mix's request sequence for one seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab

    def quantiles(self, j: int):
        """The quantiles ``(u_j, v_j)`` of request ``j``."""
        return tuple(min(max((j + 1) * d % 1.0, 1e-9), 1 - 1e-9)
                     for d in _STEP)

    def lengths(self, j: int):
        """``(prompt_len, max_new)`` of request ``j``."""
        u, v = self.quantiles(j)
        total = self.mix["max_total"]
        n_prompt = _length(self.mix["prompt"], u, total - 1)
        return n_prompt, _length(self.mix["output"], v, total - n_prompt)

    def request(self, j: int) -> Request:
        n_prompt, max_new = self.lengths(j)
        rng = np.random.default_rng([self.seed, 1, j])
        return Request(rng.integers(0, self.vocab, n_prompt).tolist(),
                       max_new)
