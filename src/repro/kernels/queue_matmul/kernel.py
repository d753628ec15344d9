"""queue_matmul — COPIFTv2's queue mechanism as a TPU matmul kernel.

Mapping (DESIGN.md §4): the scalar core issuing async HBM→VMEM copies is the
paper's *integer thread* (pure address generation); the MXU loop consuming
arrived tiles is the *FP thread*.  The two are coupled by per-operand VMEM
rings with DMA-semaphore handshakes — exactly the blocking FIFO semantics of
the hardware queues, with the queue *depth* as the ring's slot count:

 * ``depth=1``  — COPIFT analogue: stage a tile, barrier (sem wait), compute,
   repeat: communication and compute fully serialized.
 * ``depth>=2`` — COPIFTv2 analogue: copies for tile j+1..j+depth-1 are in
   flight while tile j multiplies; the semaphore wait *is* the queue pop.

The two operand streams have their own rings (``depth_x`` for activations,
``depth_w`` for weights), mirroring the paper's asymmetric I2F vs F2I FIFO
geometry: a DSE sweep that finds one direction needs less buffering maps its
``queue_depth_i2f``/``queue_depth_f2i`` selection onto the x-/w-ring depths
and saves the VMEM the symmetric ring wasted.

Operands live in ANY (HBM) memory space; the kernel owns its VMEM explicitly
(slots + fp32 accumulator), with MXU-aligned (128-multiple) tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_hbm, w_hbm, o_ref, xs, ws, acc, sx, sw, *,
            bm: int, bn: int, bk: int, nk: int, depth_x: int, depth_w: int,
            unroll: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    # integer-thread work: compute tile addresses, push the copies — one
    # ring per operand stream, each with its own depth
    def start_x(t, slot):
        pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * bm, bm), pl.ds(t * bk, bk)],
            xs.at[slot], sx.at[slot]).start()

    def start_w(t, slot):
        pltpu.make_async_copy(
            w_hbm.at[pl.ds(t * bk, bk), pl.ds(j * bn, bn)],
            ws.at[slot], sw.at[slot]).start()

    # prologue: fill each ring to its own depth
    for d in range(min(depth_x, nk)):
        start_x(d, d)
    for d in range(min(depth_w, nk)):
        start_w(d, d)

    acc[...] = jnp.zeros_like(acc)

    def body(t, _):
        slot_x = t % depth_x
        slot_w = t % depth_w
        # FP-thread pop: blocking wait on each ring's slot semaphore
        pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * bm, bm), pl.ds(t * bk, bk)],
            xs.at[slot_x], sx.at[slot_x]).wait()
        pltpu.make_async_copy(
            w_hbm.at[pl.ds(t * bk, bk), pl.ds(j * bn, bn)],
            ws.at[slot_w], sw.at[slot_w]).wait()
        acc[...] += jax.lax.dot_general(
            xs[slot_x], ws[slot_w], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        # integer thread refills each ring independently
        @pl.when(t + depth_x < nk)
        def _():
            start_x(t + depth_x, slot_x)

        @pl.when(t + depth_w < nk)
        def _():
            start_w(t + depth_w, slot_w)
        return ()

    # the calibrated schedule-interleave factor maps to K-loop unrolling (the
    # FP thread retiring several queue pops per trip).  The TPU lowering
    # takes a loop either rolled or fully unrolled, so a factor that covers
    # the trip count unrolls it fully and any smaller one keeps it rolled
    jax.lax.fori_loop(0, nk, body, (), unroll=nk if unroll >= nk else 1)
    o_ref[...] = acc[...].astype(o_ref.dtype)


def queue_matmul_kernel(x: jax.Array, w: jax.Array, *, bm: int, bn: int,
                        bk: int, depth_x: int, depth_w: int, interpret: bool,
                        out_dtype, unroll: int = 1) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    nk = k // bk
    grid = (m // bm, n // bn)
    kern = functools.partial(_kernel, bm=bm, bn=bn, bk=bk, nk=nk,
                             depth_x=depth_x, depth_w=depth_w, unroll=unroll)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((depth_x, bm, bk), x.dtype),
            pltpu.VMEM((depth_w, bk, bn), w.dtype),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.SemaphoreType.DMA((depth_x,)),
            pltpu.SemaphoreType.DMA((depth_w,)),
        ],
        interpret=interpret,
    )(x, w)
