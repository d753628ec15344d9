"""Jitted public wrapper: padding to MXU-aligned tiles + policy plumbing.

Queue geometry is no longer hard-coded: when the depth / ``policy`` /
``unroll`` knobs are left unset, they resolve once (outside the jit) from
the calibration-backed :class:`~repro.core.policy.PolicyTable` — the
``queue_matmul`` workload proxies to the ``dequant_dot`` machine-model
kernel whose DSE Pareto front picked the operating point
(``examples/explore.py calibrate``; override the artifact directory with
``REPRO_CALIBRATION_DIR``).  Explicit arguments always win, and with no
artifact present the paper's headline point (COPIFTv2, depth 4, unroll 8)
is the fallback.

The two operand rings are sized independently (asymmetric FIFO geometry):
the activation (x) ring takes the calibrated ``queue_depth_i2f`` and the
weight (w) ring ``queue_depth_f2i``, each falling back to the symmetric
``queue_depth`` — so a DSE selection that found one direction needs less
buffering shows up directly as saved VMEM.  The symmetric ``depth``
argument (and per-ring ``depth_x``/``depth_w``) remain explicit overrides.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.policy import ExecutionPolicy, OperatingPoint, default_table
from .kernel import queue_matmul_kernel
from .ref import matmul_ref


def _pad_to(a: jax.Array, mults: Tuple[int, int]) -> jax.Array:
    pads = [(-a.shape[i]) % mults[i] for i in range(2)]
    if any(pads):
        a = jnp.pad(a, ((0, pads[0]), (0, pads[1])))
    return a


def operating_point() -> OperatingPoint:
    """The operating point ``queue_matmul`` runs at when called without
    explicit ``depth``/``policy``/``unroll`` (resolution is a startup-time
    table lookup, never a per-call sweep)."""
    return default_table().resolve("queue_matmul")


@partial(jax.jit,
         static_argnames=("block", "depth_x", "depth_w", "unroll",
                          "interpret", "policy"))
def _queue_matmul(x: jax.Array, w: jax.Array, *,
                  block: Tuple[int, int, int], depth_x: int, depth_w: int,
                  unroll: int, policy: ExecutionPolicy,
                  interpret: bool) -> jax.Array:
    if policy is ExecutionPolicy.BASELINE:
        return matmul_ref(x, w).astype(x.dtype)
    if policy is ExecutionPolicy.COPIFT:
        depth_x = depth_w = 1
    m0, n0 = x.shape[0], w.shape[1]
    bm, bn, bk = block
    xp = _pad_to(x, (bm, bk))
    wp = _pad_to(w, (bk, bn))
    out = queue_matmul_kernel(xp, wp, bm=bm, bn=bn, bk=bk, depth_x=depth_x,
                              depth_w=depth_w, unroll=unroll,
                              interpret=interpret, out_dtype=x.dtype)
    return out[:m0, :n0]


def queue_matmul(x: jax.Array, w: jax.Array, *,
                 block: Tuple[int, int, int] = (128, 128, 128),
                 depth: Optional[int] = None,
                 depth_x: Optional[int] = None,
                 depth_w: Optional[int] = None,
                 unroll: Optional[int] = None,
                 policy: Optional[ExecutionPolicy] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """y = x @ w through the queue-pipelined kernel.

    ``policy`` overrides the depths: BASELINE falls back to the XLA matmul,
    COPIFT forces both rings to depth 1 (batch-synchronized staging),
    COPIFTV2 keeps the requested multi-buffer depths.  Unset knobs come
    from the calibration table (see module docstring): the x ring maps to
    the calibrated I2F depth and the w ring to the F2I depth (each
    defaulting to the symmetric ``queue_depth``).  Explicit arguments
    always win — ``depth`` pins both rings, ``depth_x``/``depth_w`` pin one
    each; in particular any explicit depth with ``policy`` unset runs the
    depth-honouring COPIFTv2 path (the pre-calibration behavior), never a
    table policy that would discard it.  ``interpret=None`` interprets the
    kernel unless the backend is a TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if depth is not None:
        depth_x = depth if depth_x is None else depth_x
        depth_w = depth if depth_w is None else depth_w
    if depth_x is None or depth_w is None or unroll is None or policy is None:
        if policy is None and (depth_x is not None or depth_w is not None):
            policy = ExecutionPolicy.COPIFTV2
        pt = operating_point()
        if policy is None:
            policy = pt.policy
        cal_x, cal_w = pt.effective_depths()
        if depth_x is None:
            depth_x = cal_x
        if depth_w is None:
            depth_w = cal_w
        if unroll is None:
            unroll = pt.unroll
    return _queue_matmul(x, w, block=block, depth_x=depth_x, depth_w=depth_w,
                         unroll=unroll, policy=policy, interpret=interpret)
