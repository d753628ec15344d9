"""Seeded random weights of a configuration, made on the device in one
jitted call, in the type they are served in.

The tree and each leaf's scale come from the configuration's reference
(``layout``): matrices have std 1/sqrt(fan_in), so attention scores stay
in the softmax's working range at every width; embedding and head rows
have std 0.02; norm gains are offsets from 1 with std 0.1.  Leaf ``i`` is
drawn from the seed's key folded with ``i``."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_STD = {"embed": lambda fan: 0.02, "gain": lambda fan: 0.1,
        "matrix": lambda fan: 1.0 / math.sqrt(fan)}


def is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def seed_key_data(seed: int) -> np.ndarray:
    """Two 32-bit words from any non-negative whole number."""
    return np.random.SeedSequence(seed).generate_state(2, np.uint32)


def n_values(layout) -> int:
    return sum(math.prod(s[0]) for s in
               jax.tree.leaves(layout, is_leaf=is_leaf))


def make(layout, seed: int, dtype):
    """The weights of ``layout`` for ``seed``, as device arrays of
    ``dtype``."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=is_leaf)

    def build(data):
        key = jax.random.wrap_key_data(data)
        out = []
        for i, (shape, kind, fan) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            out.append(jax.random.normal(k, shape, dtype)
                       * jnp.asarray(_STD[kind](fan), dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)(jnp.asarray(seed_key_data(seed)))
