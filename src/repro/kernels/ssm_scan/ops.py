"""Jitted wrapper with padding over time/channel tiles."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import ssm_scan_kernel


@partial(jax.jit, static_argnames=("bt", "bd", "interpret"))
def ssm_scan(x, dt, A, Bm, C, *, bt: int = 128, bd: int = 128,
             interpret: Optional[bool] = None) -> jax.Array:
    """``interpret=None`` interprets the kernel unless the backend is a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, d = x.shape
    pt, pd = (-T) % bt, (-d) % bd
    if pt or pd:
        x = jnp.pad(x, ((0, 0), (0, pt), (0, pd)))
        dt = jnp.pad(dt, ((0, 0), (0, pt), (0, pd)))
        A = jnp.pad(A, ((0, pd), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pt), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pt), (0, 0)))
    y = ssm_scan_kernel(x, dt, A, Bm, C, bt=bt, bd=bd, interpret=interpret)
    return y[:, :T, :d]
