"""Fused selective-scan kernel (Mamba-1 inner loop).

TPU-native adaptation of the CUDA selective-scan: instead of one thread
block per (batch, channel-tile) with shared-memory staging, the grid walks
(batch, channel-tile, time-block) with the recurrent state (N, bd) resident
in VMEM scratch across time blocks — the state never round-trips to HBM,
which is the entire point of the fusion.  dA/dBx are computed on the fly
from (x, dt, A, B) per time step, so HBM traffic is the *inputs* only, never
the (B,T,d,N) state tensor."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, At_ref, B_ref, C_ref, y_ref, h_scr, *,
            bt: int, n: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    # the state is held transposed, (N, bd), so channels sit on lanes and a
    # time step's (1, bd) rows of x and dt broadcast over it directly
    At = At_ref[...].astype(jnp.float32)                 # (N, bd)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
           ).astype(jnp.float32)

    def column(ref, row):
        """Row ``row`` of an (bt, N) block as an (N, 1) column: the diagonal
        of the row broadcast over N sublanes, which needs no transpose."""
        return jnp.sum(ref[0, row, :].astype(jnp.float32) * eye, axis=1,
                       keepdims=True)

    # each time step is read and written as a row slice of the refs: the
    # TPU lowering has no dynamic index into a loaded value
    def step(t, _):
        row = pl.ds(t, 1)
        x = x_ref[0, row, :].astype(jnp.float32)         # (1, bd)
        dt = dt_ref[0, row, :].astype(jnp.float32)       # (1, bd)
        h = jnp.exp(dt * At) * h_scr[...] + column(B_ref, row) * (dt * x)
        h_scr[...] = h
        y_ref[0, row, :] = jnp.sum(h * column(C_ref, row), axis=0,
                                   keepdims=True).astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, bt, step, ())


def ssm_scan_kernel(x, dt, A, Bm, C, *, bt: int, bd: int,
                    interpret: bool) -> jax.Array:
    B, T, d = x.shape
    N = A.shape[1]
    grid = (B, d // bd, T // bt)
    kern = functools.partial(_kernel, bt=bt, n=N)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda b, di, ti: (b, ti, di)),
            pl.BlockSpec((1, bt, bd), lambda b, di, ti: (b, ti, di)),
            pl.BlockSpec((N, bd), lambda b, di, ti: (0, di)),
            pl.BlockSpec((1, bt, N), lambda b, di, ti: (b, ti, 0)),
            pl.BlockSpec((1, bt, N), lambda b, di, ti: (b, ti, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, bd), lambda b, di, ti: (b, ti, di)),
        out_shape=jax.ShapeDtypeStruct((B, T, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        interpret=interpret,
    )(x, dt, A.T, Bm, C)
