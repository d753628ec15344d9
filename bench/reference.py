"""The check of what the timed path served: the configuration's plain
reference, teacher-forced over each sampled request's prompt and served
tokens, in float32, layer by layer.

For each served token it reads the gap by which the token's reference
logit lies below the reference's best logit at that position, in standard
deviations of the reference's logits there.  Greedy serving of a sound
program gives a gap of 0, or a small one where bf16 rounding broke a near
tie.  The widest gap over the sample is the number compared.

The control (``control=True``) runs the same reference a second time with
every matmul of the blocks and the head in float8 e4m3 (W8A8, the precision
below the configuration's bf16), takes the token that puts first at each of
the same positions, and reads that token's gap in the float32 reference.

A configuration file names its reference module,
``bench/references/<reference>.py``: the weights' tree (``layout``) and one
layer's equations (``block``).  The program is handed that same tree as its
parameters (``harness.build``), so the program's parameter tree is the
reference's ``layout``.  The reference walks the model's layers in the order
of its layer plan, one ``(group, index, kind)`` per layer: ``group`` names
the subtree of the weights that holds the layer (``"blocks"``,
``"dense_blocks"``, ...); ``index`` is its place on that group's stacked
leading axis, or None where the group is one unstacked layer; ``kind`` is a
hashable tag of the layer's kind.

* A module whose layers are all alike declares no plan.  Its plan is
  ``("blocks", l, None)`` for ``l < num_hidden_layers``, and its block is
  called ``block(p, x, pos, c, mm)``.
* A module whose stack has more than one kind declares ``layer_plan(c)``,
  the list of those triples, and its block takes the kind as a sixth
  argument: ``block(p, x, pos, c, mm, kind)``.

``c`` holds the configuration file's top-level numbers and strings, for the
plan and the block alike.  Each layer is one jitted call with the kind
static, so a model of two kinds compiles two layer programs, not one per
layer."""
from __future__ import annotations

import importlib
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .references.common import mm_f32, mm_fp8, rms_norm


def reference_module(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def layer_plan(cfg: dict):
    """The model's layers in order, as ``(group, index, kind)``: the
    reference module's ``layer_plan``, or else ``num_hidden_layers`` alike
    stacked in ``"blocks"``."""
    mod = reference_module(cfg)
    if hasattr(mod, "layer_plan"):
        return tuple(mod.layer_plan(cfg))
    return tuple(("blocks", l, None) for l in range(cfg["num_hidden_layers"]))


@partial(jax.jit, static_argnames=("cfg_items", "ref", "quant", "kind"))
def _layer(group, l, x, *, cfg_items, ref, quant, kind):
    cfg = dict(cfg_items)
    mod = reference_module(cfg)
    p = group if l is None else jax.tree.map(lambda a: a[l], group)
    pos = jnp.arange(x.shape[1])
    mm = mm_fp8 if quant else mm_f32
    if hasattr(mod, "layer_plan"):
        return mod.block(p, x, pos, cfg, mm, kind)
    return mod.block(p, x, pos, cfg, mm)


@partial(jax.jit, static_argnames=("eps",))
def _gaps(h, h_ctl, final_norm, head, nxt, *, eps):
    """Gaps, in standard deviations of the float32 logits at each position
    of ``h`` (P, d): of the token ``nxt`` and, where ``h_ctl`` is given, of
    the token that the float8 control's logits put first."""
    ref = mm_f32(rms_norm(h, final_norm, eps), head.T)
    best, sd = ref.max(axis=1), ref.std(axis=1)
    at = lambda t: jnp.take_along_axis(ref, t[:, None], axis=1)[:, 0]
    served = (best - at(nxt)) / sd
    if h_ctl is None:
        return served, None
    first = jnp.argmax(mm_fp8(rms_norm(h_ctl, final_norm, eps), head.T),
                       axis=1)
    return served, (best - at(first)) / sd


def _hidden(weights, tokens, cfg_items, ref, quant):
    x = weights["embed"][tokens].astype(jnp.float32)
    for group, l, kind in layer_plan(dict(cfg_items)):
        x = _layer(weights[group], l, x, cfg_items=cfg_items, ref=ref,
                   quant=quant, kind=kind)
    return x


def _static_cfg(cfg: dict):
    """The configuration's numbers, hashable for ``jit``."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def served_gaps(weights, cfg: dict, seqs: Sequence[dict], shape,
                control: bool = False) -> Dict[str, List[np.ndarray]]:
    """Per sampled request, the gap of every served token (``"served"``)
    and, with ``control``, that of the token the float8 control puts first
    at the same position (``"control"``).

    ``seqs``: dicts with ``prompt`` and ``served`` token lists.  They are
    laid out in a ``shape`` = (rows, positions) block padded with zeros, so
    every run compiles the same shapes.
    """
    cfg_items = _static_cfg(cfg)
    rows, nxt = np.zeros(shape, np.int32), np.zeros(shape, np.int32)
    for i, s in enumerate(seqs):
        full = list(s["prompt"]) + list(s["served"])
        rows[i, :len(full) - 1] = full[:-1]
        nxt[i, :len(full) - 1] = full[1:]
    tokens = jnp.asarray(rows)
    hid = _hidden(weights, tokens, cfg_items, cfg["reference"], False)
    ctl = (_hidden(weights, tokens, cfg_items, cfg["reference"], True)
           if control else None)
    out = {"served": [], "control": []}
    for i, s in enumerate(seqs):
        served, first = _gaps(hid[i], None if ctl is None else ctl[i],
                              weights["final_norm"], weights["head"],
                              jnp.asarray(nxt[i]), eps=cfg["rms_norm_eps"])
        n0, n = len(s["prompt"]) - 1, len(s["served"])
        out["served"].append(np.asarray(served)[n0:n0 + n])
        if control:
            out["control"].append(np.asarray(first)[n0:n0 + n])
    return out
