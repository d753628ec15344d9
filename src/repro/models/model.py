"""Model assembly: parameter trees, forward pass, KV/state caches and decode
steps for every assigned architecture family (dense / moe / ssm / hybrid /
vlm / audio).  Homogeneous layer stacks are scanned (`lax.scan` over stacked
params — compile time stays flat in depth); the hybrid family scans over its
repeating (rec, rec, attn) macro-block with an unrolled tail."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig, RunConfig, ShapeConfig
from . import attention as attn
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .layers import (ParamSpec, ffn_apply, ffn_specs, init_params, rms_norm,
                     shape_tree)

Pytree = Any


# ---------------------------------------------------------------------------
# parameter trees
# ---------------------------------------------------------------------------

def _stack_specs(specs: Pytree, n: int) -> Pytree:
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((n, *s.shape), ("layers", *s.axes), s.init, s.scale),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def _dense_block_specs(cfg: ModelConfig) -> Dict[str, Pytree]:
    d = cfg.d_model
    block: Dict[str, Pytree] = {"ln1": ParamSpec((d,), ("embed",), init="zeros"),
                                "ln2": ParamSpec((d,), ("embed",), init="zeros")}
    block["attn"] = attn.mla_specs(cfg) if cfg.mla else attn.gqa_specs(cfg)
    block["ffn"] = (moe_mod.moe_specs(cfg) if cfg.moe
                    else ffn_specs(d, cfg.d_ff, cfg.ffn_act))
    return block


def _rec_block_specs(cfg: ModelConfig) -> Dict[str, Pytree]:
    d = cfg.d_model
    return {"ln1": ParamSpec((d,), ("embed",), init="zeros"),
            "ln2": ParamSpec((d,), ("embed",), init="zeros"),
            "rglru": rglru_mod.rglru_specs(cfg),
            "ffn": ffn_specs(d, cfg.d_ff, cfg.ffn_act)}


def _hybrid_layout(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...]]:
    pat = cfg.rglru.pattern
    n_full = cfg.n_layers // len(pat)
    tail = tuple(pat[:cfg.n_layers % len(pat)])
    return n_full, tail


def param_specs(cfg: ModelConfig) -> Pytree:
    d = cfg.d_model
    tree: Dict[str, Pytree] = {
        "embed": ParamSpec((cfg.vocab, d), ("vocab", "embed"),
                           init="embed", scale=0.02),
        "final_norm": ParamSpec((d,), ("embed",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        tree["head"] = ParamSpec((cfg.vocab, d), ("vocab", "embed"),
                                 init="embed", scale=0.02)
    if cfg.family == "ssm":
        block = {"ln1": ParamSpec((d,), ("embed",), init="zeros"),
                 "mamba": ssm_mod.mamba_specs(cfg)}
        tree["blocks"] = _stack_specs(block, cfg.n_layers)
    elif cfg.family == "hybrid":
        n_full, tail = _hybrid_layout(cfg)
        macro = {}
        for j, kind in enumerate(cfg.rglru.pattern):
            macro[f"{j}_{kind}"] = (_rec_block_specs(cfg) if kind == "rec"
                                    else _dense_block_specs(cfg))
        tree["macros"] = _stack_specs(macro, n_full)
        for j, kind in enumerate(tail):
            tree[f"tail_{j}_{kind}"] = (_rec_block_specs(cfg) if kind == "rec"
                                        else _dense_block_specs(cfg))
    else:
        tree["blocks"] = _stack_specs(_dense_block_specs(cfg), cfg.n_layers)
    return tree


def init_model_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Pytree:
    return init_params(key, param_specs(cfg), dtype)


def param_shapes(cfg: ModelConfig, dtype) -> Pytree:
    return shape_tree(param_specs(cfg), dtype)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _dense_block_apply(p, x, cfg: ModelConfig, rc: RunConfig,
                       q_offset: int = 0, window: Optional[int] = None):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla:
        h = attn.mla_apply(p["attn"], h, cfg, q_offset=q_offset,
                           analysis=rc.analysis_mode,
                           batch_shard=rc.attn_batch_shard)
    else:
        h = attn.gqa_apply(p["attn"], h, cfg, window=window,
                           q_offset=q_offset, analysis=rc.analysis_mode,
                           batch_shard=rc.attn_batch_shard)
    x = x + h
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe:
        h = (moe_mod.moe_apply_grouped(p["ffn"], h, cfg)
             if rc.moe_dispatch == "grouped"
             else moe_mod.moe_apply(p["ffn"], h, cfg))
    else:
        h = ffn_apply(p["ffn"], h, cfg.ffn_act)
    return x + h


def _rec_block_apply(p, x, cfg: ModelConfig, rc: RunConfig):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + rglru_mod.rglru_apply(p["rglru"], h, cfg,
                                  unroll=rc.analysis_mode)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    return x + ffn_apply(p["ffn"], h, cfg.ffn_act)


def _ssm_block_apply(p, x, cfg: ModelConfig, rc: RunConfig):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    return x + ssm_mod.mamba_apply(p["mamba"], h, cfg,
                                   unroll=rc.analysis_mode)


def _stack_scan(body, x, xs, rc: RunConfig):
    """lax.scan over stacked layers, or a Python unroll in analysis mode
    (XLA cost_analysis counts while bodies once — unrolling restores true
    FLOP/byte/collective totals for the roofline)."""
    if rc.analysis_mode:
        leaves = jax.tree_util.tree_leaves(xs)
        L = leaves[0].shape[0]
        outs = []
        for i in range(L):
            sl = jax.tree_util.tree_map(lambda a: a[i], xs)
            x, out = body(x, sl)
            outs.append(out)
        if outs and outs[0] is not None:
            stacked = jax.tree_util.tree_map(
                lambda *ys: jnp.stack(ys), *outs)
        else:
            stacked = None
        return x, stacked
    if rc.remat:
        body = jax.checkpoint(body)
    return jax.lax.scan(body, x, xs)


def embed_inputs(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
                 dtype) -> jax.Array:
    if cfg.frontend == "audio":
        return batch["frames"].astype(dtype)
    x = params["embed"][batch["tokens"]].astype(dtype)
    if cfg.frontend == "vision":
        n = cfg.n_frontend_tokens
        patches = batch["patches"].astype(dtype)
        x = jnp.concatenate([patches, x[:, n:]], axis=1)
    return x


def _heads_shard_on_model(cfg: ModelConfig) -> bool:
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or "model" not in mesh.axis_names:
            return True
        return cfg.n_heads % mesh.shape["model"] == 0
    except Exception:
        return True


def forward(params: Pytree, batch: Dict[str, jax.Array], cfg: ModelConfig,
            rc: RunConfig) -> jax.Array:
    """Full-sequence forward -> logits (B, S, vocab)."""
    dtype = jnp.dtype(rc.dtype)
    x = embed_inputs(params, batch, cfg, dtype)
    if rc.attn_batch_shard and not _heads_shard_on_model(cfg):
        # heads cannot shard over the model axis (e.g. 24H or 40H on TP=16):
        # switch the whole residual stream to 2-D batch sharding once, here,
        # instead of bouncing layouts around every attention layer
        from .attention import batch_shard_constraint
        x = batch_shard_constraint(x)
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype)
                                            if a.dtype == jnp.float32 else a, t)

    if cfg.family == "ssm":
        def body(h, bp):
            return _ssm_block_apply(cast(bp), h, cfg, rc), None
        x, _ = _stack_scan(body, x, params["blocks"], rc)
    elif cfg.family == "hybrid":
        window = cfg.rglru.window

        def macro_body(h, mp):
            mp = cast(mp)
            for j, kind in enumerate(cfg.rglru.pattern):
                bp = mp[f"{j}_{kind}"]
                h = (_rec_block_apply(bp, h, cfg, rc) if kind == "rec"
                     else _dense_block_apply(bp, h, cfg, rc, window=window))
            return h, None
        x, _ = _stack_scan(macro_body, x, params["macros"], rc)
        _, tail = _hybrid_layout(cfg)
        for j, kind in enumerate(tail):
            bp = cast(params[f"tail_{j}_{kind}"])
            x = (_rec_block_apply(bp, x, cfg, rc) if kind == "rec"
                 else _dense_block_apply(bp, x, cfg, rc, window=window))
    else:
        def body(h, bp):
            return _dense_block_apply(cast(bp), h, cfg, rc), None
        x, _ = _stack_scan(body, x, params["blocks"], rc)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,vd->bsv", x, head.astype(dtype))
    # logits stay in the compute dtype: upcasting here would drag the entire
    # backward pass (activation-gradient all-reduces included) into fp32 —
    # see EXPERIMENTS.md §Perf (phi3 hillclimb #1)
    return logits


# ---------------------------------------------------------------------------
# decode caches + step
# ---------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, batch: int, max_len: int,
               dtype) -> Dict[str, jax.ShapeDtypeStruct]:
    """Shape tree of the decode cache (also used to allocate zeros)."""
    L, hd = cfg.n_layers, cfg.resolved_head_dim
    sd = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt)
    # ``len`` is per-sequence: continuous batching admits a request into a
    # freed slot mid-run, so each batch row carries its own position (RoPE
    # angle, KV write cursor, and attention-mask extent all derive from it)
    out: Dict[str, Any] = {"len": sd((batch,), jnp.int32)}
    if cfg.family == "ssm":
        d_in, _, d_state = ssm_mod.ssm_dims(cfg)
        K = cfg.ssm.d_conv
        out["ssm"] = sd((L, batch, d_in, d_state), jnp.float32)
        out["conv"] = sd((L, batch, K - 1, d_in))
        return out
    if cfg.family == "hybrid":
        n_full, tail = _hybrid_layout(cfg)
        pat = cfg.rglru.pattern
        kinds = list(pat) * n_full + list(tail)
        n_rec = sum(1 for k in kinds if k == "rec")
        n_attn = len(kinds) - n_rec
        w = cfg.rglru.lru_width or cfg.d_model
        W = min(cfg.rglru.window, max_len)
        out["h"] = sd((n_rec, batch, w), jnp.float32)
        out["conv"] = sd((n_rec, batch, cfg.rglru.conv_width - 1, w))
        out["k"] = sd((n_attn, batch, cfg.n_kv_heads, W, hd))
        out["v"] = sd((n_attn, batch, cfg.n_kv_heads, W, hd))
        return out
    if cfg.mla:
        m = cfg.mla
        out["latent"] = sd((L, batch, max_len, m.kv_lora_rank))
        out["rope"] = sd((L, batch, max_len, m.qk_rope_head_dim))
        return out
    out["k"] = sd((L, batch, cfg.n_kv_heads, max_len, hd))
    out["v"] = sd((L, batch, cfg.n_kv_heads, max_len, hd))
    return out


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Pytree:
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  cache_spec(cfg, batch, max_len, dtype))


def decode_step(params: Pytree, cache: Pytree, batch: Dict[str, jax.Array],
                cfg: ModelConfig, rc: RunConfig
                ) -> Tuple[jax.Array, Pytree]:
    """One token for every sequence in the batch.
    batch = {"tokens": (B, 1)} -> (logits (B, vocab), new cache).
    ``cache["len"]`` is a per-sequence (B,) position vector, so slots of a
    continuously-batched engine may sit at different sequence lengths."""
    dtype = jnp.dtype(rc.dtype)
    x = params["embed"][batch["tokens"]].astype(dtype)
    length = cache["len"]
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype)
                                            if a.dtype == jnp.float32 else a, t)

    if cfg.family == "ssm":
        def body(h, sl):
            bp, ssm_s, conv_s = sl
            bp = cast(bp)
            hn = rms_norm(h, bp["ln1"], cfg.norm_eps)
            y, ssm_s, conv_s = ssm_mod.mamba_decode(bp["mamba"], hn, cfg,
                                                    ssm_s, conv_s)
            return h + y, (ssm_s, conv_s)
        x, (ssm_s, conv_s) = _stack_scan(
            body, x, (params["blocks"], cache["ssm"], cache["conv"]),
            rc)
        cache = {**cache, "ssm": ssm_s, "conv": conv_s, "len": length + 1}
    elif cfg.family == "hybrid":
        x, cache = _hybrid_decode(params, cache, x, cfg, rc, dtype)
    elif cfg.mla:
        def body(h, sl):
            bp, lat, rp = sl
            bp = cast(bp)
            hn = rms_norm(h, bp["ln1"], cfg.norm_eps)
            with jax.named_scope("attention"):
                y, lat, rp = attn.mla_decode(bp["attn"], hn, cfg, lat, rp,
                                             length)
            h = h + y
            hn = rms_norm(h, bp["ln2"], cfg.norm_eps)
            with jax.named_scope("ffn"):
                y = (moe_mod.moe_apply(bp["ffn"], hn, cfg) if cfg.moe
                     else ffn_apply(bp["ffn"], hn, cfg.ffn_act))
            return h + y, (lat, rp)
        x, (lat, rp) = _stack_scan(
            body, x, (params["blocks"], cache["latent"], cache["rope"]), rc)
        cache = {**cache, "latent": lat, "rope": rp, "len": length + 1}
    else:
        def body(h, sl):
            bp, kc, vc = sl
            bp = cast(bp)
            hn = rms_norm(h, bp["ln1"], cfg.norm_eps)
            with jax.named_scope("attention"):
                y, kc, vc = attn.gqa_decode(bp["attn"], hn, cfg, kc, vc,
                                            length)
            h = h + y
            hn = rms_norm(h, bp["ln2"], cfg.norm_eps)
            with jax.named_scope("ffn"):
                y = (moe_mod.moe_apply(bp["ffn"], hn, cfg) if cfg.moe
                     else ffn_apply(bp["ffn"], hn, cfg.ffn_act))
            return h + y, (kc, vc)
        x, (kc, vc) = _stack_scan(
            body, x, (params["blocks"], cache["k"], cache["v"]), rc)
        cache = {**cache, "k": kc, "v": vc, "len": length + 1}

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("bsd,vd->bsv", x, head.astype(dtype))[:, 0]
    return logits.astype(jnp.float32), cache


def _hybrid_decode(params, cache, x, cfg: ModelConfig, rc: RunConfig, dtype):
    length = cache["len"]
    window = cfg.rglru.window
    n_full, tail = _hybrid_layout(cfg)
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype)
                                            if a.dtype == jnp.float32 else a, t)
    pat = cfg.rglru.pattern
    rec_per_macro = sum(1 for k in pat if k == "rec")
    attn_per_macro = len(pat) - rec_per_macro
    n_rec_scan = n_full * rec_per_macro
    n_attn_scan = n_full * attn_per_macro

    h_sc = cache["h"][:n_rec_scan].reshape(n_full, rec_per_macro, *cache["h"].shape[1:])
    cv_sc = cache["conv"][:n_rec_scan].reshape(n_full, rec_per_macro, *cache["conv"].shape[1:])
    k_sc = cache["k"][:n_attn_scan].reshape(n_full, attn_per_macro, *cache["k"].shape[1:])
    v_sc = cache["v"][:n_attn_scan].reshape(n_full, attn_per_macro, *cache["v"].shape[1:])

    def macro(hx, sl):
        mp, hs, cs, ks, vs = sl
        mp = cast(mp)
        ri = ai = 0
        hs2, cs2, ks2, vs2 = list(hs), list(cs), list(ks), list(vs)
        for j, kind in enumerate(pat):
            bp = mp[f"{j}_{kind}"]
            hn = rms_norm(hx, bp["ln1"], cfg.norm_eps)
            if kind == "rec":
                y, h_new, c_new = rglru_mod.rglru_decode(bp["rglru"], hn, cfg,
                                                         hs[ri], cs[ri])
                hs2[ri], cs2[ri] = h_new, c_new
                ri += 1
            else:
                y, k_new, v_new = attn.gqa_decode(bp["attn"], hn, cfg,
                                                  ks[ai], vs[ai], length,
                                                  window=window)
                ks2[ai], vs2[ai] = k_new, v_new
                ai += 1
            hx = hx + y
            hn = rms_norm(hx, bp["ln2"], cfg.norm_eps)
            hx = hx + ffn_apply(bp["ffn"], hn, cfg.ffn_act)
        return hx, (jnp.stack(hs2), jnp.stack(cs2), jnp.stack(ks2), jnp.stack(vs2))

    x, (hs, cs, ks, vs) = _stack_scan(
        macro, x, (params["macros"], h_sc, cv_sc, k_sc, v_sc), rc)
    new_h = list(hs.reshape(n_rec_scan, *cache["h"].shape[1:]))
    new_cv = list(cs.reshape(n_rec_scan, *cache["conv"].shape[1:]))
    new_k = list(ks.reshape(n_attn_scan, *cache["k"].shape[1:]))
    new_v = list(vs.reshape(n_attn_scan, *cache["v"].shape[1:]))

    ri, ai = n_rec_scan, n_attn_scan
    for j, kind in enumerate(tail):
        bp = cast(params[f"tail_{j}_{kind}"])
        hn = rms_norm(x, bp["ln1"], cfg.norm_eps)
        if kind == "rec":
            y, h_new, c_new = rglru_mod.rglru_decode(
                bp["rglru"], hn, cfg, cache["h"][ri], cache["conv"][ri])
            new_h.append(h_new)
            new_cv.append(c_new)
            ri += 1
        else:
            y, k_new, v_new = attn.gqa_decode(bp["attn"], hn, cfg,
                                              cache["k"][ai], cache["v"][ai],
                                              length, window=window)
            new_k.append(k_new)
            new_v.append(v_new)
            ai += 1
        x = x + y
        hn = rms_norm(x, bp["ln2"], cfg.norm_eps)
        x = x + ffn_apply(bp["ffn"], hn, cfg.ffn_act)

    cache = {**cache, "h": jnp.stack(new_h), "conv": jnp.stack(new_cv),
             "k": jnp.stack(new_k), "v": jnp.stack(new_v), "len": length + 1}
    return x, cache


# ---------------------------------------------------------------------------
# chunked prefill: C prompt tokens per slot per jitted call
# ---------------------------------------------------------------------------

def _merge_masked(active: jax.Array, new: jax.Array, old: jax.Array
                  ) -> jax.Array:
    """Per-slot select between two cache leaves: batch is axis 0 of the
    per-sequence ``len`` vector and axis 1 of every stacked leaf (same
    convention as the engine's slot-reset)."""
    if new.ndim == 0:
        return new
    if new.ndim == 1:                          # cache["len"]: (B,)
        return jnp.where(active, new, old)
    shape = (1, active.shape[0]) + (1,) * (new.ndim - 2)
    return jnp.where(active.reshape(shape), new, old)


def prefill_step(params: Pytree, cache: Pytree, batch: Dict[str, jax.Array],
                 cfg: ModelConfig, rc: RunConfig
                 ) -> Tuple[jax.Array, Pytree]:
    """Ingest a chunk of up to C prompt tokens per slot in ONE jitted call.

    ``batch = {"tokens": (B, C) int32, "n_tokens": (B,) int32}`` — slot
    ``i`` consumes its first ``n_tokens[i]`` columns starting at its own
    cache position ``cache["len"][i]`` (``0 <= n_tokens[i] <= C``; ``0``
    leaves the slot completely untouched).  Mixed-phase batches are the
    point: a slot mid-prefill (``n_tokens = C``) coexists with a slot
    mid-decode (``n_tokens = 1``, its column 0 holding the last generated
    token) and with free slots (``n_tokens = 0``) in the same fixed-shape
    call.

    Returns ``(logits, cache)`` where ``logits[i]`` is the next-token
    distribution after slot ``i``'s **last valid column** — for a decoding
    slot that is the ordinary decode logits; for a slot whose prefill
    completes inside this chunk it is the first-generated-token logits —
    and zeros where ``n_tokens[i] == 0``.  ``cache["len"]`` advances by
    ``n_tokens``.

    Attention families (dense GQA, MLA, dense-FFN or MoE blocks) run the
    whole ``(B, C)`` chunk through the layer stack in one pass, so each
    weight and the cache are read once per chunk: column ``j`` of slot ``i``
    sits at position ``len[i] + j``, writes its K/V (or latent) row there
    if ``j < n_tokens[i]``, and attends to the rows up to its position.
    Against C :func:`decode_step` calls this gives the same greedy tokens
    and the same logits and written rows up to float rounding (matmuls over
    C rows may sum in another order); rows of columns that are not written,
    and every row of a slot with ``n_tokens == 0``, are left bit for bit.
    Recurrent families (``ssm``, ``hybrid``) carry state that a parallel
    chunk would have to scan, so they keep :func:`_prefill_scan`.
    """
    if cfg.family in ("ssm", "hybrid"):
        return _prefill_scan(params, cache, batch, cfg, rc)
    tokens, n_tokens = batch["tokens"], batch["n_tokens"]
    dtype = jnp.dtype(rc.dtype)
    x = params["embed"][tokens].astype(dtype)
    length = cache["len"]
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype)
                                            if a.dtype == jnp.float32 else a, t)
    names, attend = ((("latent", "rope"), attn.mla_chunk) if cfg.mla
                     else (("k", "v"), attn.gqa_chunk))

    def body(h, sl):
        bp, c0, c1 = sl
        bp = cast(bp)
        hn = rms_norm(h, bp["ln1"], cfg.norm_eps)
        with jax.named_scope("attention"):
            y, c0, c1 = attend(bp["attn"], hn, cfg, c0, c1, length, n_tokens)
        h = h + y
        hn = rms_norm(h, bp["ln2"], cfg.norm_eps)
        with jax.named_scope("ffn"):
            y = (moe_mod.moe_apply(bp["ffn"], hn, cfg) if cfg.moe
                 else ffn_apply(bp["ffn"], hn, cfg.ffn_act))
        return h + y, (c0, c1)
    x, (c0, c1) = _stack_scan(
        body, x, (params["blocks"], cache[names[0]], cache[names[1]]), rc)
    cache = {**cache, names[0]: c0, names[1]: c1, "len": length + n_tokens}

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.maximum(n_tokens - 1, 0)
    x = jnp.take_along_axis(x, last[:, None, None], axis=1)       # (B, 1, d)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("bsd,vd->bsv", x, head.astype(dtype))[:, 0]
    logits = jnp.where(n_tokens[:, None] > 0, logits.astype(jnp.float32), 0.0)
    return logits, cache


def _prefill_scan(params: Pytree, cache: Pytree, batch: Dict[str, jax.Array],
                  cfg: ModelConfig, rc: RunConfig
                  ) -> Tuple[jax.Array, Pytree]:
    """:func:`prefill_step` for recurrent families: ``lax.scan`` of the
    whole :func:`decode_step` over the chunk's columns, each column's cache
    update kept only for the slots it is active in.  Bit-exact with C
    token-by-token calls."""
    tokens, n_tokens = batch["tokens"], batch["n_tokens"]
    B, C = tokens.shape

    def column(carry, j):
        cache, logits = carry
        tok = jax.lax.dynamic_slice_in_dim(tokens, j, 1, axis=1)   # (B, 1)
        active = j < n_tokens                                      # (B,)
        step_logits, new_cache = decode_step(params, cache,
                                             {"tokens": tok}, cfg, rc)
        with jax.named_scope("cache_merge"):
            cache = {k: _merge_masked(active, new_cache[k], cache[k])
                     for k in cache}
        logits = jnp.where(active[:, None], step_logits, logits)
        return (cache, logits), None

    logits0 = jnp.zeros((B, cfg.vocab), jnp.float32)
    (cache, logits), _ = jax.lax.scan(column, (cache, logits0),
                                      jnp.arange(C))
    return logits, cache


# ---------------------------------------------------------------------------
# canonical input specs per (arch x shape) cell — ShapeDtypeStructs only
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig, rc: RunConfig,
                ) -> Dict[str, Any]:
    """Stand-ins for every model input of this cell (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    dtype = jnp.dtype(rc.dtype)
    i32 = jnp.int32
    sd = jax.ShapeDtypeStruct
    if shape.mode == "decode":
        return {"tokens": sd((B, 1), i32),
                "cache": cache_spec(cfg, B, S, dtype)}
    batch: Dict[str, Any] = {}
    if cfg.frontend == "audio":
        batch["frames"] = sd((B, S, cfg.d_model), dtype)
    else:
        batch["tokens"] = sd((B, S), i32)
        if cfg.frontend == "vision":
            batch["patches"] = sd((B, cfg.n_frontend_tokens, cfg.d_model), dtype)
    if shape.mode == "train":
        batch["labels"] = sd((B, S), i32)
    return batch
