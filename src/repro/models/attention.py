"""Attention layers: GQA (full/causal/local-window), MLA, and the decode and
per-slot chunk paths over a serving cache.

All sequence-level attention goes through :func:`flash_attention_ref` — a
blockwise online-softmax implementation in pure jnp (the oracle for the
Pallas kernel in ``repro.kernels.flash_attention``).  Materializing S² scores
at 32k context would need terabytes; blockwise keeps the working set at
(block_q × block_k) per head.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from .layers import ParamSpec, apply_rope, rms_norm, rope_angles

NEG_INF = -1e30


def batch_shard_constraint(*arrays):
    """Pin the leading (batch) dim of attention activations to the combined
    (data, model) mesh axes when legal — a no-op outside a mesh context or
    when the batch does not divide.  See RunConfig.attn_batch_shard."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or "model" not in mesh.axis_names:
            return arrays if len(arrays) > 1 else arrays[0]
        axes = tuple(a for a in ("pod", "data", "model")
                     if a in mesh.axis_names)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out = []
        for x in arrays:
            if x.shape[0] % size == 0 and x.shape[0] >= size:
                spec = jax.sharding.PartitionSpec(axes, *([None] * (x.ndim - 1)))
                x = jax.lax.with_sharding_constraint(x, spec)
            out.append(x)
        return tuple(out) if len(out) > 1 else out[0]
    except Exception:
        return arrays if len(arrays) > 1 else arrays[0]


# ---------------------------------------------------------------------------
# blockwise attention reference (flash-style, pure jnp)
# ---------------------------------------------------------------------------

def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: Optional[int] = None,
                        block_q: int = 512, block_k: int = 1024,
                        q_offset: int = 0, unroll: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D[v]); GQA via head grouping.
    ``q_offset`` is the absolute position of q[0] (for decode/chunked use).
    Returns (B, Hq, Sq, Dv)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, Dv = v.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    orig_sq = Sq

    pad_q = (-Sq) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        Sq = q.shape[2]
    pad_k = (-Sk) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        Sk_p = k.shape[2]
    else:
        Sk_p = Sk

    qb = q.reshape(B, Hkv, G, Sq // block_q, block_q, D)
    kb = k.reshape(B, Hkv, Sk_p // block_k, block_k, D)
    vb = v.reshape(B, Hkv, Sk_p // block_k, block_k, Dv)
    nq, nk = Sq // block_q, Sk_p // block_k

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, block_q)
    k_pos = jnp.arange(Sk_p).reshape(nk, block_k)

    def q_block(qi, q_i):
        # online softmax over k blocks
        def kv_step(carry, ki):
            m, l, acc = carry
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q_i.astype(jnp.float32),
                           kb[:, :, ki].astype(jnp.float32)) * scale
            mask = k_pos[ki][None, :] <= Sk - 1          # strip k padding
            if causal:
                mask = mask & (k_pos[ki][None, :] <= q_pos[qi][:, None])
            if window is not None:
                mask = mask & (k_pos[ki][None, :]
                               > q_pos[qi][:, None] - window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vb[:, :, ki].astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, block_q, Dv), jnp.float32)
        if unroll:
            carry = (m0, l0, a0)
            for ki in range(nk):
                # block skipping: drop blocks that are fully masked (causal
                # future blocks; blocks beyond the sliding window) — on TPU
                # the Pallas kernel skips these via its grid/masking too
                if causal and ki * block_k > q_offset + (qi + 1) * block_q - 1:
                    continue
                if (window is not None and (ki + 1) * block_k - 1
                        <= q_offset + qi * block_q - window):
                    continue
                carry, _ = kv_step(carry, ki)
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          jnp.arange(nk))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    if unroll:
        out = jnp.stack([q_block(qi, qb[:, :, :, qi]) for qi in range(nq)])
    else:
        out = jax.lax.map(lambda qi: q_block(qi, qb[:, :, :, qi]),
                          jnp.arange(nq))
    # out: (nq, B, Hkv, G, block_q, Dv) -> (B, Hq, Sq, Dv)
    out = jnp.moveaxis(out, 0, 3).reshape(B, Hkv, G, Sq, Dv)
    out = out.reshape(B, Hq, Sq, Dv)[:, :, :orig_sq]
    return out.astype(v.dtype)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         length: jax.Array, *, window: Optional[int] = None
                         ) -> jax.Array:
    """Single-token attention: q (B, Hq, 1, D); caches (B, Hkv, T, D).
    ``length`` (scalar int32, or per-sequence (B,) int32 for continuous
    batching) = number of valid cache entries per sequence."""
    B, Hq, _, D = q.shape
    _, Hkv, T, Dv = v_cache.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bhtd->bhgt", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(T)
    lv = jnp.reshape(jnp.asarray(length), (-1, 1))   # (B, 1) or (1, 1)
    mask = pos[None] < lv
    if window is not None:
        mask = mask & (pos[None] >= lv - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgt,bhtd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, 1, Dv).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def gqa_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return {
        "wq": ParamSpec((d, cfg.n_heads, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, cfg.n_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((cfg.n_heads, hd, d), ("heads", "head_dim", "embed")),
    }


def gqa_apply(p, x: jax.Array, cfg: ModelConfig, *,
              window: Optional[int] = None, q_offset: int = 0,
              analysis: bool = False, batch_shard: bool = False) -> jax.Array:
    """Full-sequence GQA attention.  x: (B, S, d)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
    if cfg.rope:
        pos = q_offset + jnp.arange(S)
        cos, sin = rope_angles(pos, cfg.resolved_head_dim, cfg.rope_theta)
        q = apply_rope(q, cos[None, None], sin[None, None])
        k = apply_rope(k, cos[None, None], sin[None, None])
    if batch_shard:
        q, k, v = batch_shard_constraint(q, k, v)
    if analysis:
        S_ = x.shape[1]
        o = flash_attention_ref(q, k, v, causal=cfg.causal, window=window,
                                q_offset=q_offset, unroll=True,
                                block_q=min(4096, S_), block_k=min(4096, S_))
    else:
        o = flash_attention_ref(q, k, v, causal=cfg.causal, window=window,
                                q_offset=q_offset)
    if batch_shard:
        o = batch_shard_constraint(o)
    return jnp.einsum("bhsk,hkd->bsd", o, p["wo"])


def gqa_prefill_kv(p, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """K/V for the whole prompt (cache fill)."""
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
    if cfg.rope:
        pos = jnp.arange(x.shape[1])
        cos, sin = rope_angles(pos, cfg.resolved_head_dim, cfg.rope_theta)
        k = apply_rope(k, cos[None, None], sin[None, None])
    return k, v


def gqa_decode(p, x: jax.Array, cfg: ModelConfig, k_cache, v_cache,
               length: jax.Array, *, window: Optional[int] = None):
    """One-token step.  x: (B, 1, d); caches (B, Hkv, T, hd).
    ``length`` is scalar or per-sequence (B,) — continuous batching admits
    requests mid-run, so every sequence carries its own position.
    Returns (out (B,1,d), new_k_cache, new_v_cache)."""
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (x.shape[0],))
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
    if cfg.rope:
        cos, sin = rope_angles(length, cfg.resolved_head_dim, cfg.rope_theta)
        q = apply_rope(q, cos[:, None, None], sin[:, None, None])
        k = apply_rope(k, cos[:, None, None], sin[:, None, None])
    T = k_cache.shape[2]
    slot = length % T                      # ring for windowed layers
    upd = lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (0, s, 0))
    k_cache = jax.vmap(upd)(k_cache, k.astype(k_cache.dtype), slot)
    v_cache = jax.vmap(upd)(v_cache, v.astype(v_cache.dtype), slot)
    if window is None:
        o = decode_attention_ref(q, k_cache, v_cache, length + 1)
    else:
        # ring cache: all T slots valid once full; positions are implicit
        valid = jnp.minimum(length + 1, T)
        o = decode_attention_ref(q, k_cache, v_cache, valid)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
    return out, k_cache, v_cache


def _chunk_positions(length: jax.Array, n_tokens: jax.Array, C: int, T: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Cache position of each chunk column, ``len[i] + j`` (B, C), and the
    row each column writes: its position where ``j < n_tokens[i]``, else
    ``T``, which a ``mode="drop"`` scatter discards.  (A
    ``dynamic_update_slice`` would clamp a chunk that ends at ``T`` back
    onto valid rows.)"""
    pos = length[:, None] + jnp.arange(C, dtype=jnp.int32)
    rows = jnp.where(jnp.arange(C) < n_tokens[:, None], pos, T)
    return pos, rows


def gqa_chunk(p, x: jax.Array, cfg: ModelConfig, k_cache, v_cache,
              length: jax.Array, n_tokens: jax.Array):
    """A chunk of C tokens per slot in one pass.  x: (B, C, d); caches
    (B, Hkv, T, hd); ``length`` and ``n_tokens`` (B,).  Column ``j`` of
    slot ``i`` sits at position ``length[i] + j`` and writes its K/V row
    there only if ``j < n_tokens[i]``; then each query attends to the cache
    rows up to its own position, with :func:`gqa_decode`'s f32 scores and
    masking.  Returns (out (B, C, d), new_k_cache, new_v_cache)."""
    B, C, _ = x.shape
    _, Hkv, T, hd = k_cache.shape
    pos, rows = _chunk_positions(length, n_tokens, C, T)
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
    if cfg.rope:
        cos, sin = rope_angles(pos, cfg.resolved_head_dim, cfg.rope_theta)
        q = apply_rope(q, cos[:, None], sin[:, None])
        k = apply_rope(k, cos[:, None], sin[:, None])
    b = jnp.arange(B)[:, None]
    with jax.named_scope("cache_write"):
        # advanced indices around a slice: the update is (B, C, Hkv, hd)
        k_cache = k_cache.at[b, :, rows].set(
            k.transpose(0, 2, 1, 3).astype(k_cache.dtype), mode="drop")
        v_cache = v_cache.at[b, :, rows].set(
            v.transpose(0, 2, 1, 3).astype(v_cache.dtype), mode="drop")
    G = q.shape[1] // Hkv
    qg = q.reshape(B, Hkv, G, C, hd)
    s = jnp.einsum("bhgcd,bhtd->bhgct", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * (1.0 / math.sqrt(hd))
    mask = jnp.arange(T)[None, None] <= pos[:, :, None]          # (B, C, T)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgct,bhtd->bhgcd", pattn, v_cache.astype(jnp.float32))
    o = o.reshape(B, Hkv * G, C, hd).astype(v_cache.dtype)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention, MiniCPM3/DeepSeek-style)
# ---------------------------------------------------------------------------

def mla_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, m, H = cfg.d_model, cfg.mla, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wdq": ParamSpec((d, m.q_lora_rank), ("embed", "lora")),
        "q_norm": ParamSpec((m.q_lora_rank,), ("lora",), init="zeros"),
        "wuq": ParamSpec((m.q_lora_rank, H, qk), ("lora", "heads", "head_dim")),
        "wdkv": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim),
                          ("embed", "lora")),
        "kv_norm": ParamSpec((m.kv_lora_rank,), ("lora",), init="zeros"),
        "wuk": ParamSpec((m.kv_lora_rank, H, m.qk_nope_head_dim),
                         ("lora", "heads", "head_dim")),
        "wuv": ParamSpec((m.kv_lora_rank, H, m.v_head_dim),
                         ("lora", "heads", "head_dim")),
        "wo": ParamSpec((H, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


def _mla_qkv(p, x, cfg, q_offset: int):
    m = cfg.mla
    B, S, _ = x.shape
    cq = rms_norm(x @ p["wdq"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bhsk", cq, p["wuq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    ckv = x @ p["wdkv"]
    latent, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    latent = rms_norm(latent, p["kv_norm"], cfg.norm_eps)
    pos = q_offset + jnp.arange(S)
    cos, sin = rope_angles(pos, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos[None, None], sin[None, None])
    k_rope = apply_rope(k_rope, cos[None], sin[None])      # (B, S, rope_dim)
    return q_nope, q_rope, latent, k_rope


def mla_apply(p, x: jax.Array, cfg: ModelConfig, *, q_offset: int = 0,
              analysis: bool = False, batch_shard: bool = False) -> jax.Array:
    """Naive (expanded) MLA for train/prefill."""
    m = cfg.mla
    q_nope, q_rope, latent, k_rope = _mla_qkv(p, x, cfg, q_offset)
    k_nope = jnp.einsum("bsr,rhk->bhsk", latent, p["wuk"])
    v = jnp.einsum("bsr,rhk->bhsk", latent, p["wuv"])
    H = cfg.n_heads
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None],
                                  (*k_nope.shape[:3], m.qk_rope_head_dim))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    if batch_shard:
        q, k, v = batch_shard_constraint(q, k, v)
    if analysis:
        S_ = x.shape[1]
        o = flash_attention_ref(q, k, v, causal=True, q_offset=q_offset,
                                unroll=True, block_q=min(4096, S_),
                                block_k=min(4096, S_))
    else:
        o = flash_attention_ref(q, k, v, causal=True, q_offset=q_offset)
    return jnp.einsum("bhsk,hkd->bsd", o, p["wo"])


def mla_decode(p, x: jax.Array, cfg: ModelConfig, latent_cache, rope_cache,
               length: jax.Array):
    """Absorbed MLA decode: the cache holds only (latent, k_rope) —
    (B, T, r) and (B, T, rope_dim).  ``length`` is scalar or per-sequence
    (B,).  Score = q_nope·W_uk·latent + q_rope·k_rope."""
    m = cfg.mla
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (x.shape[0],))
    cos, sin = rope_angles(length, m.qk_rope_head_dim, cfg.rope_theta)
    cq = rms_norm(x @ p["wdq"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bhsk", cq, p["wuq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    ckv = x @ p["wdkv"]
    lat_t, k_rope_t = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    lat_t = rms_norm(lat_t, p["kv_norm"], cfg.norm_eps)
    q_rope = apply_rope(q_rope, cos[:, None, None], sin[:, None, None])
    k_rope_t = apply_rope(k_rope_t, cos[:, None], sin[:, None])

    upd = lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (s, 0))
    latent_cache = jax.vmap(upd)(latent_cache,
                                 lat_t.astype(latent_cache.dtype), length)
    rope_cache = jax.vmap(upd)(rope_cache,
                               k_rope_t.astype(rope_cache.dtype), length)

    # absorbed attention
    q_eff = jnp.einsum("bhsk,rhk->bhsr", q_nope, p["wuk"])    # (B,H,1,r)
    s = (jnp.einsum("bhsr,btr->bhst", q_eff.astype(jnp.float32),
                    latent_cache.astype(jnp.float32))
         + jnp.einsum("bhsk,btk->bhst", q_rope.astype(jnp.float32),
                      rope_cache.astype(jnp.float32)))
    s = s / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    T = latent_cache.shape[1]
    mask = jnp.arange(T)[None] <= length[:, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bhsr", pattn,
                       latent_cache.astype(jnp.float32))
    o = jnp.einsum("bhsr,rhk->bhsk", o_lat.astype(x.dtype), p["wuv"])
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
    return out, latent_cache, rope_cache


def mla_chunk(p, x: jax.Array, cfg: ModelConfig, latent_cache, rope_cache,
              length: jax.Array, n_tokens: jax.Array):
    """A chunk of C tokens per slot in one pass, in :func:`mla_decode`'s
    absorbed form.  x: (B, C, d); caches (B, T, r) and (B, T, rope_dim);
    ``length`` and ``n_tokens`` (B,).  Column ``j`` of slot ``i`` writes its
    latent and k_rope rows at ``length[i] + j`` only if ``j < n_tokens[i]``,
    and attends to the cache rows up to that position."""
    m = cfg.mla
    B, C, _ = x.shape
    T = latent_cache.shape[1]
    pos, rows = _chunk_positions(length, n_tokens, C, T)
    cos, sin = rope_angles(pos, m.qk_rope_head_dim, cfg.rope_theta)
    cq = rms_norm(x @ p["wdq"], p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bhsk", cq, p["wuq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    ckv = x @ p["wdkv"]
    lat_t, k_rope_t = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    lat_t = rms_norm(lat_t, p["kv_norm"], cfg.norm_eps)
    q_rope = apply_rope(q_rope, cos[:, None], sin[:, None])
    k_rope_t = apply_rope(k_rope_t, cos, sin)

    b = jnp.arange(B)[:, None]
    with jax.named_scope("cache_write"):
        latent_cache = latent_cache.at[b, rows].set(
            lat_t.astype(latent_cache.dtype), mode="drop")
        rope_cache = rope_cache.at[b, rows].set(
            k_rope_t.astype(rope_cache.dtype), mode="drop")

    q_eff = jnp.einsum("bhsk,rhk->bhsr", q_nope, p["wuk"])    # (B,H,C,r)
    s = (jnp.einsum("bhsr,btr->bhst", q_eff.astype(jnp.float32),
                    latent_cache.astype(jnp.float32))
         + jnp.einsum("bhsk,btk->bhst", q_rope.astype(jnp.float32),
                      rope_cache.astype(jnp.float32)))
    s = s / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    mask = jnp.arange(T)[None, None] <= pos[:, :, None]          # (B, C, T)
    s = jnp.where(mask[:, None], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bhsr", pattn,
                       latent_cache.astype(jnp.float32))
    o = jnp.einsum("bhsr,rhk->bhsk", o_lat.astype(x.dtype), p["wuv"])
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
    return out, latent_cache, rope_cache
