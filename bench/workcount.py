"""The work one step program call needs, from the configuration's shapes and
the live lengths of the sequences it advanced.

A call advances each live sequence by ``k`` tokens after ``p`` cached ones,
and ``emits`` a token for some of them.  What it needs at the least:

* operations: the reference's ``slot_flops`` for each sequence (two per
  weight of every block projection per token, and attention over the live
  context), plus two per head weight for each emitted token;
* bytes: every weight once except the embedding table, one embedding row
  per token, the live cache read once and the new entries written.

These are lower bounds of what any exact implementation must do, so a
share of the roofline built on them cannot pass 100%: reading the whole
allocated cache, or the weights once per chunk column, is work the count
leaves out."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple

import jax.numpy as jnp

from .reference import reference_module
from .weights import n_values


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def seconds(self, peak: dict) -> Tuple[float, str]:
        """The least time the chip could take, and the bound that sets it
        (``"compute"`` or ``"bytes"``)."""
        tc = self.flops / peak["bf16_flops_per_s"]
        tb = self.bytes / peak["hbm_bytes_per_s"]
        return (tc, "compute") if tc > tb else (tb, "bytes")


class WorkCounter:
    """Per-call work of one configuration's step programs."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.ref = reference_module(cfg)
        size = jnp.dtype(cfg["dtype"]).itemsize
        d, V = cfg["hidden_size"], cfg["vocab_size"]
        self.weight_bytes = (n_values(self.ref.layout(cfg)) - V * d) * size
        self.row_bytes = d * size
        self.cache_bytes_per_token = \
            self.ref.cache_values_per_token(cfg) * size
        self.head_flops = 2 * V * d

    def call(self, slots: Iterable[Tuple[int, int, int]]) -> Work:
        """``slots``: ``(p, k, emits)`` for each sequence the call
        advanced."""
        flops = bytes_ = 0
        for p, k, emits in slots:
            flops += self.ref.slot_flops(self.cfg, p, k) \
                + emits * self.head_flops
            bytes_ += k * self.row_bytes \
                + (p + k) * self.cache_bytes_per_token
        return Work(float(flops), float(self.weight_bytes + bytes_))
