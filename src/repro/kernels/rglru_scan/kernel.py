"""RG-LRU linear-recurrence kernel: diagonal gated scan with the hidden
state resident in VMEM across time blocks (same scheme as ssm_scan, without
the state dimension)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h_out, h_scr, *, bt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    # each time step is read and written as a (1, bw) row slice of the refs:
    # the TPU lowering has no dynamic index into a loaded value
    def step(t, _):
        row = pl.ds(t, 1)
        a = a_ref[0, row, :].astype(jnp.float32)
        bx = b_ref[0, row, :].astype(jnp.float32)
        h = a * h_scr[...] + bx
        h_scr[...] = h
        h_out[0, row, :] = h.astype(h_out.dtype)
        return ()

    jax.lax.fori_loop(0, bt, step, ())


def rglru_scan_kernel(a, bx, *, bt: int, bw: int, interpret: bool) -> jax.Array:
    B, T, w = a.shape
    grid = (B, w // bw, T // bt)
    kern = functools.partial(_kernel, bt=bt)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda b, wi, ti: (b, ti, wi)),
            pl.BlockSpec((1, bt, bw), lambda b, wi, ti: (b, ti, wi)),
        ],
        out_specs=pl.BlockSpec((1, bt, bw), lambda b, wi, ti: (b, ti, wi)),
        out_shape=jax.ShapeDtypeStruct((B, T, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        interpret=interpret,
    )(a, bx)
