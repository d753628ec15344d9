"""Shared layers: param-spec trees, norms, embeddings, RoPE, FFN variants.

Parameters are declared as :class:`ParamSpec` trees (shape + logical axis
names + initializer).  The same tree serves three consumers:
 - ``init_params``      — materialize real weights (smoke tests, training)
 - ``shape_tree``       — ShapeDtypeStructs for AOT lowering (dry-run)
 - ``distributed.sharding`` — logical-axis -> mesh-axis resolution
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical axis names (for sharding)
    init: str = "normal"                 # normal | zeros | ones | embed
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


#: leading axes that stack independent weights (a scanned layer stack, an
#: expert bank): no part of any one weight's fan-in
_STACK_AXES = ("layers", "experts")


def _fan_in(spec: ParamSpec) -> int:
    """Inputs summed into each output of the weight: its first axis after
    any stacking axes, times ``head_dim`` where that axis is ``heads`` (an
    attention output projection contracts both).  Too large a std makes
    attention scores saturate the softmax, and the model then turns every
    rounding into a different attended position."""
    dims = list(zip(spec.shape, spec.axes))
    while dims and dims[0][1] in _STACK_AXES:
        dims.pop(0)
    if len(dims) < 2:
        return 1
    fan_in = dims[0][0]
    if dims[0][1] == "heads":
        fan_in *= dims[1][0]
    return fan_in


def _init_leaf(key, spec: ParamSpec, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, spec.shape, dtype) * spec.scale
                ).astype(dtype)
    std = spec.scale / math.sqrt(_fan_in(spec))
    return (jax.random.normal(key, spec.shape, dtype) * std).astype(dtype)


def init_params(key, tree: Pytree, dtype=jnp.float32) -> Pytree:
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(k, s, dtype) for k, s in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def shape_tree(tree: Pytree, dtype) -> Pytree:
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), tree,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def logical_axes_tree(tree: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda s: s.axes, tree, is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def rope_angles(positions: jax.Array, dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions: (...,) int32 -> cos/sin of shape (..., dim//2), fp32."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., dim); cos/sin broadcastable to (..., dim//2)."""
    dt = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(dt)


def ffn_specs(d_model: int, d_ff: int, act: str) -> Dict[str, ParamSpec]:
    if act == "swiglu":
        return {
            "wi": ParamSpec((d_model, d_ff), ("embed", "ff")),
            "wg": ParamSpec((d_model, d_ff), ("embed", "ff")),
            "wo": ParamSpec((d_ff, d_model), ("ff", "embed")),
        }
    return {
        "wi": ParamSpec((d_model, d_ff), ("embed", "ff")),
        "wo": ParamSpec((d_ff, d_model), ("ff", "embed")),
    }


def ffn_apply(p: Dict[str, jax.Array], x: jax.Array, act: str) -> jax.Array:
    if act == "swiglu":
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["wi"]))
    else:
        h = jax.nn.gelu(x @ p["wi"])
    return h @ p["wo"]
