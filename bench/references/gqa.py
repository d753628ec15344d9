"""Plain reference of a dense decoder with grouped-query attention
(Phi-3, arXiv:2404.14219; Llama-style block).

Per layer, with RMSNorm gains stored as offsets from 1::

    h = x + Attn(RMSNorm(x))          q, k, v = h W_q, h W_k, h W_v
                                      RoPE on q and k; causal softmax
                                      attention, head i reads KV head
                                      i // (H / H_kv); output W_o
    x' = h + SwiGLU(RMSNorm(h))

then a final RMSNorm and an untied LM head.  The weights' layout is the
tree the served model takes, so one set of weights made from the seed
feeds both."""
from __future__ import annotations

import jax.numpy as jnp

from .common import causal_attention, dense_fields, rms_norm, rope, swiglu


def dims(c):
    d, H, Hkv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    return d, H, Hkv, c.get("head_dim") or d // H


def program_fields(c) -> dict:
    """What the program's model configuration must hold, field by field,
    for this reference to describe it: plain GQA, no latent attention."""
    return {**dense_fields(c), "resolved_head_dim": dims(c)[3], "mla": None}


def layout(c):
    """The weights as a tree of ``(shape, kind, fan_in)``: ``matrix``
    leaves are drawn with std 1/sqrt(fan_in), ``embed`` rows with std 0.02,
    and norm ``gain`` offsets with std 0.1."""
    d, H, Hkv, hd = dims(c)
    L, f, V = c["num_hidden_layers"], c["intermediate_size"], c["vocab_size"]
    m = lambda *shape, fan: ((L, *shape), "matrix", fan)
    return {
        "embed": ((V, d), "embed", None),
        "head": ((V, d), "embed", None),
        "final_norm": ((d,), "gain", None),
        "blocks": {
            "ln1": ((L, d), "gain", None),
            "ln2": ((L, d), "gain", None),
            "attn": {"wq": m(d, H, hd, fan=d), "wk": m(d, Hkv, hd, fan=d),
                     "wv": m(d, Hkv, hd, fan=d), "wo": m(H, hd, d, fan=H * hd)},
            "ffn": {"wi": m(d, f, fan=d), "wg": m(d, f, fan=d),
                    "wo": m(f, d, fan=f)},
        },
    }


def block(p, x, pos, c, mm):
    """One layer on ``x`` (B, S, d) in float32; ``p`` holds this layer's
    weights."""
    d, H, Hkv, hd = dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    a = p["attn"]
    h = rms_norm(x, p["ln1"], eps)
    q = rope(mm(h, a["wq"]).transpose(0, 2, 1, 3), pos, theta)
    k = rope(mm(h, a["wk"]).transpose(0, 2, 1, 3), pos, theta)
    v = mm(h, a["wv"]).transpose(0, 2, 1, 3)
    k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
    o = causal_attention(q, k, v).transpose(0, 2, 1, 3)
    x = x + mm(o, a["wo"], 2)
    return x + swiglu(p["ffn"], rms_norm(x, p["ln2"], eps), mm)


def cache_values_per_token(c) -> int:
    """Cache entries one token adds: K and V in every layer."""
    _, _, Hkv, hd = dims(c)
    return 2 * c["num_hidden_layers"] * Hkv * hd


def slot_flops(c, p: int, k: int) -> int:
    """Operations one sequence needs to take ``k`` new tokens through the
    blocks after ``p`` cached ones: two per weight of every projection per
    token, and QK^T and PV over each query's live context."""
    d, H, Hkv, hd = dims(c)
    L, f = c["num_hidden_layers"], c["intermediate_size"]
    params = L * (d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * f)
    keys = k * p + k * (k + 1) // 2
    return 2 * params * k + L * 2 * H * 2 * hd * keys
