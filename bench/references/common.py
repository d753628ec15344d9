"""Pieces shared by the plain references: float32 matmuls, RMSNorm, RoPE,
SwiGLU, causal softmax attention, and the fp8 rounding of the control.

Written from the published equations, with no import of the program.  Every
function computes in float32; a matmul goes through ``mm``, which is either
:func:`mm_f32` (the reference: float32 at ``highest`` precision, since a TPU
otherwise runs a float32 matmul in bf16 passes) or :func:`mm_fp8` (the
control: both operands rounded to float8 e4m3 with a scale per output
channel of the weight and per row of the activations, as a W8A8 serving path
would)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3 = jnp.float8_e4m3fn
E4M3_MAX = 448.0


def mm_f32(x, w, contract: int = 1):
    """``x @ w`` in float32, contracting the last ``contract`` axes of ``x``
    with the first ``contract`` axes of ``w``."""
    return jnp.tensordot(x.astype(jnp.float32), w.astype(jnp.float32),
                         axes=contract, precision=HIGHEST)


def _fp8(a, axes):
    """``a`` rounded to e4m3 with one scale per slice over ``axes``."""
    a = a.astype(jnp.float32)
    amax = jnp.max(jnp.abs(a), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (a / scale).astype(E4M3).astype(jnp.float32) * scale


def mm_fp8(x, w, contract: int = 1):
    """:func:`mm_f32` of operands rounded to float8 e4m3: activations scaled
    per row, weights per output channel."""
    xq = _fp8(x, tuple(range(x.ndim - contract, x.ndim)))
    wq = _fp8(w, tuple(range(contract)))
    return mm_f32(xq, wq, contract)


def rms_norm(x, g, eps):
    """RMSNorm with the gain stored as its offset from 1 (``w = 1 + g``),
    the layout the served weights use."""
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g.astype(jnp.float32))


def rope(x, pos, theta):
    """Rotary embedding, rotate-half form (GPT-NeoX / Hugging Face): the
    first and second halves of the last axis are the pair's two parts.
    ``x``: (..., S, D) with ``pos`` (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q, k, v):
    """softmax(q k^T / sqrt(d_qk)) v over earlier positions only.
    q, k: (B, H, S, d_qk); v: (B, H, S, d_v)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST)
    s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
    S = q.shape[2]
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(mask, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision=HIGHEST)


def dense_fields(c) -> dict:
    """Fields of the program's model configuration, by name, that a dense
    decoder with a SwiGLU feed-forward, RoPE and an untied head must hold
    for the configuration file ``c``.  A reference adds its attention's."""
    if c["hidden_act"] != "silu" or c["tie_word_embeddings"]:
        raise ValueError("the references compute SwiGLU (silu) and an "
                         "untied head only")
    return {"family": "dense", "causal": True, "rope": True,
            "ffn_act": "swiglu", "tie_embeddings": False, "moe": None,
            "ssm": None, "rglru": None, "frontend": None,
            "d_model": c["hidden_size"], "n_layers": c["num_hidden_layers"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "norm_eps": c["rms_norm_eps"], "rope_theta": c["rope_theta"]}


def swiglu(p, x, mm):
    """SwiGLU feed-forward: ``(silu(x W_gate) * (x W_up)) W_down``."""
    return mm(jax.nn.silu(mm(x, p["wg"])) * mm(x, p["wi"]), p["wo"])
