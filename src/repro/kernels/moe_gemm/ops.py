"""Jitted wrapper with padding over (capacity, feature, contraction) tiles."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import moe_gemm_kernel


@partial(jax.jit, static_argnames=("bc", "bf", "bk", "depth", "interpret"))
def moe_gemm(x, w, *, bc: int = 128, bf: int = 128, bk: int = 128,
             depth: int = 2, interpret: Optional[bool] = None) -> jax.Array:
    """``interpret=None`` interprets the kernel unless the backend is a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    E, C, d = x.shape
    f = w.shape[2]
    pc, pk, pf = (-C) % bc, (-d) % bk, (-f) % bf
    if pc or pk:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, pk)))
    if pk or pf:
        w = jnp.pad(w, ((0, 0), (0, pk), (0, pf)))
    y = moe_gemm_kernel(x, w, bc=bc, bf=bf, bk=bk, depth=depth,
                        interpret=interpret)
    return y[:, :C, :f]
