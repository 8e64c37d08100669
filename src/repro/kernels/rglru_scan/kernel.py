"""RG-LRU recurrence Pallas TPU kernel.

Computes h_t = exp(log_a_t) * h_{t-1} + b_t over blocked (time, width) VMEM
tiles.  Grid: (B, nw, nt) with the time dim innermost and sequential; the
running state for each (batch, width-tile) lives in VMEM scratch across the
nt iterations, so HBM traffic is exactly one read of (log_a, b) and one
write of h — the recurrence is bandwidth-bound, and this tiling keeps it at
the streaming minimum (the roofline memory term).

The diagonal recurrence is elementwise over width, so the width tile (lanes)
can be large (512) while the time tile bounds the sequential inner loop.
The tile's decay exp(log_a) and input are first widened to f32 VMEM
scratch in whole-tile passes; the loop then steps one f32 row at a time
through ``pl.ds`` ref windows (a single packed bf16 row is not addressable
at an arbitrary offset) and the finished tile is narrowed back in one store.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_W = 512


def _rglru_kernel(log_a_ref, b_ref, h0_ref, o_ref, carry_ref, a_ref, x_ref,
                  h_ref, *, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        carry_ref[...] = h0_ref[...].astype(jnp.float32)

    a_ref[...] = jnp.exp(log_a_ref[0].astype(jnp.float32))   # (bt, bw)
    x_ref[...] = b_ref[0].astype(jnp.float32)

    def body(t, h):                              # h: (1, bw)
        row = pl.ds(t, 1)
        h = a_ref[row, :] * h + x_ref[row, :]
        h_ref[row, :] = h
        return h

    carry_ref[...] = jax.lax.fori_loop(0, block_t, body, carry_ref[...])
    o_ref[0] = h_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_w", "interpret"))
def rglru_scan_pallas(log_a, b, h0, *, block_t: int = DEFAULT_BLOCK_T,
                      block_w: int = DEFAULT_BLOCK_W,
                      interpret: bool | None = None):
    """log_a, b: (B,S,W); h0: (B,W).  Returns h: (B,S,W)."""
    bsz, s, w = log_a.shape
    block_t = min(block_t, s)
    block_w = min(block_w, w)
    assert s % block_t == 0 and w % block_w == 0, (s, w, block_t, block_w)
    nt, nw = s // block_t, w // block_w

    kernel = functools.partial(_rglru_kernel, block_t=block_t)
    return pl.pallas_call(
        kernel,
        grid=(bsz, nw, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda b_, wi, ti: (b_, ti, wi)),
            pl.BlockSpec((1, block_t, block_w), lambda b_, wi, ti: (b_, ti, wi)),
            pl.BlockSpec((1, block_w), lambda b_, wi, ti: (b_, wi)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_w),
                               lambda b_, wi, ti: (b_, ti, wi)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), log_a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)] +
        [pltpu.VMEM((block_t, block_w), jnp.float32)] * 3,
        interpret=interpret_mode(interpret),
    )(log_a, b, h0)
