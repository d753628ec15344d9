"""Plain reference of a dense decoder with multi-head latent attention
(MiniCPM3 and DeepSeek-V2, arXiv:2405.04434, section 2.1).

Per layer, with RMSNorm gains stored as offsets from 1::

    c_q = RMSNorm(h W_dq)             q = c_q W_uq = [q_nope, q_rope]
    [c_kv, k_rope] = h W_dkv          c_kv = RMSNorm(c_kv)
    k_nope = c_kv W_uk, v = c_kv W_uv
    RoPE on q_rope and on k_rope, which every head shares
    k = [k_nope, k_rope]; causal softmax(q k^T / sqrt(d_nope + d_rope)) v
    output W_o, then a SwiGLU feed-forward as in a dense block

then a final RMSNorm and an untied LM head.  This is the expanded form of
the equations.  A server caches only (c_kv, k_rope) and may absorb W_uk
into the query; the algebra is the same."""
from __future__ import annotations

import jax.numpy as jnp

from .common import causal_attention, dense_fields, rms_norm, rope, swiglu


def dims(c):
    return (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"])


def program_fields(c) -> dict:
    """What the program's model configuration must hold, field by field
    (``mla.<name>`` inside its latent-attention group), for this reference
    to describe it."""
    keys = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim")
    return {**dense_fields(c), **{f"mla.{k}": c[k] for k in keys}}


def layout(c):
    """The weights as a tree of ``(shape, kind, fan_in)``, as in
    :func:`gqa.layout`."""
    d, H, qr, r, nope, rp, dv = dims(c)
    L, f, V = c["num_hidden_layers"], c["intermediate_size"], c["vocab_size"]
    m = lambda *shape, fan: ((L, *shape), "matrix", fan)
    return {
        "embed": ((V, d), "embed", None),
        "head": ((V, d), "embed", None),
        "final_norm": ((d,), "gain", None),
        "blocks": {
            "ln1": ((L, d), "gain", None),
            "ln2": ((L, d), "gain", None),
            "attn": {"wdq": m(d, qr, fan=d),
                     "q_norm": ((L, qr), "gain", None),
                     "wuq": m(qr, H, nope + rp, fan=qr),
                     "wdkv": m(d, r + rp, fan=d),
                     "kv_norm": ((L, r), "gain", None),
                     "wuk": m(r, H, nope, fan=r),
                     "wuv": m(r, H, dv, fan=r),
                     "wo": m(H, dv, d, fan=H * dv)},
            "ffn": {"wi": m(d, f, fan=d), "wg": m(d, f, fan=d),
                    "wo": m(f, d, fan=f)},
        },
    }


def block(p, x, pos, c, mm):
    """One layer on ``x`` (B, S, d) in float32."""
    d, H, qr, r, nope, rp, dv = dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    a = p["attn"]
    h = rms_norm(x, p["ln1"], eps)
    q = mm(rms_norm(mm(h, a["wdq"]), a["q_norm"], eps), a["wuq"])
    q = q.transpose(0, 2, 1, 3)                           # (B, H, S, nope+rp)
    ckv = mm(h, a["wdkv"])
    lat = rms_norm(ckv[..., :r], a["kv_norm"], eps)
    k_rope = rope(ckv[..., r:], pos, theta)               # (B, S, rp)
    k_nope = mm(lat, a["wuk"]).transpose(0, 2, 1, 3)
    v = mm(lat, a["wuv"]).transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (*k_nope.shape[:3], rp))],
        -1)
    o = causal_attention(q, k, v).transpose(0, 2, 1, 3)
    x = x + mm(o, a["wo"], 2)
    return x + swiglu(p["ffn"], rms_norm(x, p["ln2"], eps), mm)


def cache_values_per_token(c) -> int:
    """Cache entries one token adds: the latent and the shared RoPE key in
    every layer."""
    _, _, _, r, _, rp, _ = dims(c)
    return c["num_hidden_layers"] * (r + rp)


def slot_flops(c, p: int, k: int) -> int:
    """Operations one sequence needs to take ``k`` new tokens through the
    blocks after ``p`` cached ones.  Of the two exact forms of the
    attention, the cheaper one for this call counts: absorbed (W_uk and
    W_uv applied per query, scores against the latent) or expanded (K and
    V formed once per live position)."""
    d, H, qr, r, nope, rp, dv = dims(c)
    L, f = c["num_hidden_layers"], c["intermediate_size"]
    up = r * H * (nope + dv)                               # W_uk and W_uv
    rest = d * qr + qr * H * (nope + rp) + d * (r + rp) + H * dv * d + 3 * d * f
    keys = k * p + k * (k + 1) // 2
    absorbed = 2 * (rest + up) * k + 2 * H * (2 * r + rp) * keys
    expanded = (2 * rest * k + 2 * up * (p + k)
                + 2 * H * (nope + rp + dv) * keys)
    return L * min(absorbed, expanded)
