"""``prefill_step`` against C token-by-token ``decode_step`` calls.

Attention families (GQA, MLA, MoE) run the chunk in one parallel pass and
agree with the token path up to float rounding; recurrent families keep the
column scan and agree bit for bit.  The batch mixes every kind of slot: a
full chunk, a decoding slot (one token), an untouched slot (no token), a
partial chunk, and a full chunk that ends exactly at ``max_len``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import RunConfig
from repro.configs import get_reduced
from repro.models import model as model_mod
from repro.models.model import (cache_spec, decode_step, init_model_params,
                                prefill_step)

RC = RunConfig(remat=False, dtype="float32")
C, MAX_LEN = 4, 16
#: per slot: cache position before the chunk, and tokens it takes
LEN0 = np.array([5, 7, 6, 2, MAX_LEN - C], np.int32)
N_TOKENS = np.array([C, 1, 0, 3, C], np.int32)
TOL = dict(rtol=1e-5, atol=1e-5)


def _inputs(cfg):
    """Weights, a cache of random rows at positions ``LEN0``, and tokens.
    The weights come from one jitted call on an ``rbg`` key (eager, or with
    threefry, the random draws take seconds to compile)."""
    params = jax.jit(lambda k: init_model_params(k, cfg))(
        jax.random.key(3, impl="rbg"))
    rng = np.random.default_rng(3)
    cache = {name: jnp.asarray(rng.standard_normal(s.shape, np.float32))
             for name, s in cache_spec(cfg, len(LEN0), MAX_LEN,
                                       jnp.float32).items() if name != "len"}
    cache["len"] = jnp.asarray(LEN0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (len(LEN0), C)), jnp.int32)
    return params, cache, {"tokens": tokens, "n_tokens": jnp.asarray(N_TOKENS)}


def _token_path(params, cache, batch, cfg):
    """C ``decode_step`` calls, each slot keeping a column's cache update
    and logits only while the column is one of its ``n_tokens``."""
    step = jax.jit(lambda p, c, b: decode_step(p, c, b, cfg, RC))

    @jax.jit
    def keep(active, new_logits, logits, new, cache):
        # batch is axis 0 of the logits and of ``len``, axis 1 of the rest
        return jnp.where(active[:, None], new_logits, logits), jax.tree.map(
            lambda n, o: jnp.where(active.reshape((1, -1) + (1,) * (n.ndim - 2))
                                   if n.ndim > 1 else active, n, o), new, cache)

    tokens, n_tokens = batch["tokens"], batch["n_tokens"]
    logits = jnp.zeros((tokens.shape[0], cfg.vocab), jnp.float32)
    for j in range(tokens.shape[1]):
        col_logits, new = step(params, cache, {"tokens": tokens[:, j:j + 1]})
        logits, cache = keep(j < n_tokens, col_logits, logits, new, cache)
    return logits, cache


def _chunk_path(params, cache, batch, cfg, monkeypatch):
    """One jitted ``prefill_step`` call, and whether it took the scan."""
    calls = []
    scan = model_mod._prefill_scan
    monkeypatch.setattr(model_mod, "_prefill_scan",
                        lambda *a: calls.append(1) or scan(*a))
    fn = jax.jit(lambda p, c, b: prefill_step(p, c, b, cfg, RC))
    logits, out = fn(params, cache, batch)
    return logits, out, bool(calls)


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "minicpm3-4b",
                                  "granite-moe-3b-a800m"])
def test_parallel_chunk_matches_token_path(arch, monkeypatch):
    cfg = get_reduced(arch)
    params, cache, batch = _inputs(cfg)
    ref_logits, ref = _token_path(params, cache, batch, cfg)
    logits, out, scanned = _chunk_path(params, cache, batch, cfg, monkeypatch)
    assert not scanned

    live = N_TOKENS > 0
    assert np.array_equal(np.argmax(logits[live], -1),
                          np.argmax(ref_logits[live], -1))
    np.testing.assert_allclose(logits, ref_logits, **TOL)
    assert not np.any(np.asarray(logits)[~live])
    assert np.array_equal(out["len"], LEN0 + N_TOKENS)

    pos = np.arange(MAX_LEN)
    written = ((pos[None] >= LEN0[:, None])
               & (pos[None] < (LEN0 + N_TOKENS)[:, None]))      # (B, T)
    for name in cache:
        if name == "len":
            continue
        new, old = np.asarray(out[name]), np.asarray(cache[name])
        np.testing.assert_allclose(new, np.asarray(ref[name]), **TOL,
                                   err_msg=name)
        # GQA leaves are (L, B, Hkv, T, hd), MLA leaves (L, B, T, r)
        mask = written[:, None] if new.ndim == 5 else written
        keep = ~np.broadcast_to(mask[None, ..., None], new.shape)
        assert np.array_equal(new[keep], old[keep]), name
        assert np.array_equal(new[:, 2], old[:, 2]), name   # n_tokens == 0


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "recurrentgemma-2b"])
def test_recurrent_chunk_keeps_the_exact_scan(arch, monkeypatch):
    cfg = get_reduced(arch)
    params, cache, batch = _inputs(cfg)
    ref_logits, ref = _token_path(params, cache, batch, cfg)
    logits, out, scanned = _chunk_path(params, cache, batch, cfg, monkeypatch)
    assert scanned
    assert np.array_equal(logits, ref_logits)
    for name in cache:
        assert np.array_equal(out[name], ref[name]), name
