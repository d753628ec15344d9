"""Collective matmul policies — COPIFTv2's queue idea at the mesh level.

Tensor-parallel ``y = x @ W`` with ``x`` gathered across the 'model' axis:

* COPIFT-analogue (``bulk``): ``all_gather(x)`` then one big local matmul —
  batch-granular synchronization: all communication completes before any
  compute starts (one bulk collective, zero overlap).
* COPIFTv2-analogue (``ring``): shards flow around the mesh ring via
  ``collective_permute`` while each in-flight shard is multiplied locally —
  a depth-1 queue of shards, fine-grained producer/consumer overlap.  On a
  real TPU the permute of chunk i+1 overlaps the MXU work on chunk i; the
  collective-bytes term is identical, but it is spread across the step
  instead of serializing at the front (see EXPERIMENTS.md §Perf).

Numerics are identical (same partial sums, same order up to an exact
permutation of chunk concatenation); tests assert exact equality against the
single-device reference.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.policy import ExecutionPolicy

def _bulk_kernel(x, w, axis: str):
    xg = jax.lax.all_gather(x, axis, axis=0, tiled=True)
    return xg @ w


def _ring_kernel(x, w, axis: str):
    """x: (m/n, k) local shard; w: (k, p/n) local shard.  Computes the same
    (m, p/n) result as bulk, one shard-chunk per step, overlapping the
    permute of the next chunk with the matmul of the current one."""
    n = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        buf, out, src = carry
        # issue the permute for the *next* chunk, then compute on the
        # current one: XLA schedules these concurrently (async collective)
        nxt = jax.lax.ppermute(buf, axis, perm)
        part = buf @ w
        out = out.at[src].set(part)
        src = (src - 1) % n
        return (nxt, out, src), None

    m, p = x.shape[0], w.shape[1]
    out0 = jnp.zeros((n, m, p), x.dtype)
    (_, out, _), _ = jax.lax.scan(step, (x, out0, idx), None, length=n)
    return out.reshape(n * m, p)


def tp_matmul(x: jax.Array, w: jax.Array, mesh: Mesh, *,
              policy: ExecutionPolicy = ExecutionPolicy.COPIFTV2,
              axis: str = "model",
              x_spec: Optional[P] = None, w_spec: Optional[P] = None,
              out_spec: Optional[P] = None) -> jax.Array:
    """Sequence-parallel x (sharded on dim 0) times column-parallel W
    (sharded on dim 1) -> y sharded on dim 1.  Policy picks the schedule."""
    x_spec = x_spec or P(axis, None)
    w_spec = w_spec or P(None, axis)
    out_spec = out_spec or P(None, axis)
    kern = _bulk_kernel if policy is not ExecutionPolicy.COPIFTV2 else _ring_kernel
    fn = jax.shard_map(partial(kern, axis=axis), mesh=mesh,
                       in_specs=(x_spec, w_spec), out_specs=out_spec,
                       check_vma=False)
    return fn(x, w)


def collective_bytes_estimate(m: int, k: int, n_shards: int,
                              dtype_bytes: int = 2) -> dict:
    """Napkin model for §Perf: both policies move the same payload; the ring
    splits it into n chunks that overlap compute."""
    payload = m * k * dtype_bytes * (n_shards - 1) / n_shards
    return {"bulk_front_loaded_bytes": payload,
            "ring_per_step_bytes": payload / max(n_shards - 1, 1),
            "ring_steps": n_shards}
