"""Jitted wrapper: (B, H, S, D) API with GQA expansion + padding."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import flash_attention_kernel


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D) — GQA expands KV heads.
    ``interpret=None`` interprets the kernel unless the backend is a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    if Hkv != Hq:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(B * Hq, S, D)
    kf = k.reshape(B * Hq, S, D)
    vf = v.reshape(B * Hq, S, D)
    pad = (-S) % max(bq, bk)
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
    out = flash_attention_kernel(qf, kf, vf, bq=bq, bk=bk, causal=causal,
                                 window=window, seq_k=S, interpret=interpret)
    return out[:, :S].reshape(B, Hq, S, D)
