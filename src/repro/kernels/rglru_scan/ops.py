"""Jitted wrapper with padding.  Note: zero-padding time is safe (h carries
through; padded outputs are sliced off) and padded channels stay zero."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import rglru_scan_kernel


@partial(jax.jit, static_argnames=("bt", "bw", "interpret"))
def rglru_scan(a, bx, *, bt: int = 128, bw: int = 128,
               interpret: Optional[bool] = None) -> jax.Array:
    """``interpret=None`` interprets the kernel unless the backend is a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, w = a.shape
    pt, pw = (-T) % bt, (-w) % bw
    if pt or pw:
        a = jnp.pad(a, ((0, 0), (0, pt), (0, pw)))
        bx = jnp.pad(bx, ((0, 0), (0, pt), (0, pw)))
    h = rglru_scan_kernel(a, bx, bt=bt, bw=bw, interpret=interpret)
    return h[:, :T, :w]
