"""The reference's walk over a model's layer plan: the same hidden states as
the one-kind loop it replaced, and a stack of two kinds (a leading layer in
a group of its own, then stacked latent-attention layers) checked end to
end by ``served_gaps``, the float8 control included."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import (_hidden, _static_cfg, layer_plan,
                             reference_module, served_gaps)
from bench.references import mla
from bench.references.common import mm_f32, mm_fp8, rms_norm
from bench.tests import _small, _two_kind
from bench.weights import make
from bench.workcount import WorkCounter

SEED = 2**31 + 29


@partial(jax.jit, static_argnames=("cfg_items", "ref", "quant"))
def _one_kind_layer(blocks, l, x, *, cfg_items, ref, quant):
    cfg = dict(cfg_items)
    p = jax.tree.map(lambda a: a[l], blocks)
    pos = jnp.arange(x.shape[1])
    return reference_module(cfg).block(p, x, pos, cfg,
                                       mm_fp8 if quant else mm_f32)


def _one_kind_hidden(weights, tokens, cfg_items, ref, quant, n_layers):
    """The walk as it was before layer plans: ``n_layers`` alike, stacked
    in ``"blocks"``."""
    x = weights["embed"][tokens].astype(jnp.float32)
    for l in range(n_layers):
        x = _one_kind_layer(weights["blocks"], l, x, cfg_items=cfg_items,
                            ref=ref, quant=quant)
    return x


def _tokens(vocab, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, shape))


@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("which", ["gqa", "mla"])
def test_one_kind_walk_is_bit_identical(which, quant):
    cfg, m = getattr(_small, which)()
    w = make(reference_module(cfg).layout(cfg), SEED, jnp.float32)
    tokens, items = _tokens(m.vocab, (2, 40)), _static_cfg(cfg)
    want = _one_kind_hidden(w, tokens, items, which, quant, m.n_layers)
    got = _hidden(w, tokens, items, which, quant)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_default_plan_is_the_stacked_blocks():
    cfg, m = _small.gqa(n_layers=3)
    assert layer_plan(cfg) == (("blocks", 0, None), ("blocks", 1, None),
                               ("blocks", 2, None))


@pytest.fixture(scope="module")
def two_kind_weights():
    cfg = _two_kind.config()
    return cfg, make(_two_kind.layout(cfg), SEED, jnp.float32)


@pytest.fixture
def two_kind(two_kind_weights, monkeypatch):
    _two_kind.install(monkeypatch)
    return two_kind_weights


def _unrolled_logits(w, tokens, c):
    """The two-kind model by hand: the lead layer, then each stacked one."""
    pos = jnp.arange(tokens.shape[1])
    x = w["embed"][tokens].astype(jnp.float32)
    x = mla.block(w["lead_block"], x, pos, c, mm_f32)
    for l in range(c["num_hidden_layers"] - 1):
        x = mla.block(jax.tree.map(lambda a: a[l], w["blocks"]), x, pos, c,
                      mm_f32)
    return mm_f32(rms_norm(x, w["final_norm"], c["rms_norm_eps"]),
                  w["head"].T)


def test_two_kind_walk_matches_unrolled_forward(two_kind):
    cfg, w = two_kind
    assert w["lead_block"]["ffn"]["wi"].shape == (64, 96)
    assert w["blocks"]["ffn"]["wi"].shape == (2, 64, 128)
    tokens = _tokens(cfg["vocab_size"], (2, 40))
    want = _unrolled_logits(w, tokens, cfg)
    h = _hidden(w, tokens, _static_cfg(cfg), cfg["reference"], False)
    got = mm_f32(rms_norm(h, w["final_norm"], cfg["rms_norm_eps"]),
                 w["head"].T)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert float(jnp.std(want)) > 0.05       # the logits are not flat


def test_two_kinds_compile_two_layer_programs(monkeypatch):
    """Five layers of two kinds trace the block once per kind, each given
    its kind."""
    _two_kind.install(monkeypatch)
    # an eps no other test uses, so no layer program is in jit's cache yet
    cfg = {**_two_kind.config(n_layers=5), "rms_norm_eps": 1.25e-6}
    w = make(reference_module(cfg).layout(cfg), SEED, jnp.float32)
    assert [k for _, _, k in layer_plan(cfg)] == ["lead"] + ["mla"] * 4
    traced = []

    def block(p, x, pos, c, mm, kind):
        traced.append(kind)
        return mla.block(p, x, pos, c, mm)

    monkeypatch.setattr(_two_kind, "block", block)
    _hidden(w, _tokens(cfg["vocab_size"], (1, 8)), _static_cfg(cfg),
            cfg["reference"], False)
    assert traced == ["lead", "mla"]


def _greedy(w, prompt, n, c, width=24):
    """``n`` tokens decoded greedily by the unrolled model, each step over a
    zero-padded row of ``width`` (causal: the padding reads nothing back)."""
    logits = jax.jit(lambda w, t: _unrolled_logits(w, t, c))
    seq = list(prompt)
    for _ in range(n):
        row = np.zeros((1, width), np.int32)
        row[0, :len(seq)] = seq
        at = logits(w, jnp.asarray(row))[0, len(seq) - 1]
        seq.append(int(jnp.argmax(at)))
    return seq[len(prompt):]


def test_two_kind_served_gaps_and_control(two_kind):
    """The check reads one gap per served token, for the program's tokens
    and the float8 control's; tokens that the two-kind model itself puts
    first read a gap of 0, to rounding."""
    cfg, w = two_kind
    rng = np.random.default_rng(1)
    seqs = []
    for n_prompt, n_served in ((7, 5), (12, 3)):
        prompt = rng.integers(0, cfg["vocab_size"], n_prompt).tolist()
        seqs.append({"prompt": prompt,
                     "served": _greedy(w, prompt, n_served, cfg)})
    out = served_gaps(w, cfg, seqs, (2, 24), control=True)
    assert [len(g) for g in out["served"]] == [5, 3]
    assert [len(g) for g in out["control"]] == [5, 3]
    assert max(float(g.max()) for g in out["served"]) < 1e-3
    ctl = np.concatenate(out["control"])
    assert np.isfinite(ctl).all() and (ctl >= 0).all()


def test_two_kind_work_counts_both_groups(two_kind):
    cfg, _ = two_kind
    d, V, f, f0 = 64, 128, 128, 96
    # an MLA layer's attention and norms at _small.mla's sizes: W_dq 64*32,
    # q_norm 32, W_uq 32*4*12, W_dkv 64*20, kv_norm 16, W_uk and W_uv
    # 16*4*8 each, W_o 4*8*64, ln1 and ln2 64 each = 8112; then 3*d*width
    layer = lambda width: 8112 + 3 * d * width
    values = layer(f0) + 2 * layer(f) + V * d + d    # + head, final norm
    assert WorkCounter(cfg).weight_bytes == 2 * values       # bf16
