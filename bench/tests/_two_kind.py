"""A test-only reference whose layer stack has two kinds, as DeepSeek-V3
and Moonlight have: one leading layer (kind ``"lead"``) with a SwiGLU of
its own width (``lead_intermediate_size``) in an unstacked group of its
own, ``"lead_block"``, then ``num_hidden_layers - 1`` latent-attention
layers (kind ``"mla"``) stacked in ``"blocks"``.  Both kinds are the
``mla`` block's equations; they differ in the feed-forward's width.

The tests install it as ``bench.references.two_kind`` (see
:func:`install`), so the harness finds it by the name a configuration
file gives."""
import sys

import jax

from bench.references import mla
from bench.tests import _small
from bench.weights import is_leaf

NAME = "two_kind"


def install(monkeypatch):
    monkeypatch.setitem(sys.modules, f"bench.references.{NAME}",
                        sys.modules[__name__])


def config(n_layers=3, lead_ff=96, **kw):
    """A bench configuration dict at a small size: ``n_layers`` layers in
    all, the first with a SwiGLU of width ``lead_ff``."""
    cfg, _ = _small.mla(n_layers=n_layers, **kw)
    return {**cfg, "reference": NAME, "lead_intermediate_size": lead_ff}


def _parts(c):
    """The configurations of the two kinds' own stacks, as ``mla`` reads
    them: the lead layer alone, and the rest."""
    lead = {**c, "num_hidden_layers": 1,
            "intermediate_size": c["lead_intermediate_size"]}
    rest = {**c, "num_hidden_layers": c["num_hidden_layers"] - 1}
    return lead, rest


def layer_plan(c):
    return [("lead_block", None, "lead")] + [
        ("blocks", l, "mla") for l in range(c["num_hidden_layers"] - 1)]


def layout(c):
    lead, rest = _parts(c)
    unstack = lambda t: jax.tree.map(lambda s: (s[0][1:], *s[1:]), t,
                                     is_leaf=is_leaf)
    return {**mla.layout(rest),
            "lead_block": unstack(mla.layout(lead)["blocks"])}


def block(p, x, pos, c, mm, kind):
    return mla.block(p, x, pos, c, mm)


def cache_values_per_token(c):
    return mla.cache_values_per_token(c)
