"""Fused quantize-dequantize Pallas TPU kernel (fake quantization).

The per-minibatch hot path of the compression subsystem: every cut-layer
activation tensor (and gradient) is pushed through ``dq(q(x))`` once per
client per minibatch, so the round trip must stay a single streaming pass —
one read of (x, u), one write of x_hat, no intermediate int buffer in HBM.

The per-tensor scale is a global reduction, so it is computed OUTSIDE the
kernel (a cheap ``max(|x|)``) and fed in as a (1, 1) scalar operand; the
kernel body is purely elementwise (VPU work) over (block_m, 128) VMEM
tiles: ``clip(floor(x/scale + u), -qmax, qmax) * scale``.  ``u`` carries
the stochastic-rounding randomness (uniform [0,1) drawn by the caller from
a jax PRNG key), which keeps the kernel deterministic given its inputs and
bit-comparable with ref.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

LANES = 128
DEFAULT_BLOCK_M = 256


def _qdq_kernel(x_ref, u_ref, scale_ref, o_ref, *, qmax: int):
    s = scale_ref[0, 0]
    inv = jnp.where(s > 0, 1.0 / s, 0.0)
    q = jnp.floor(x_ref[...].astype(jnp.float32) * inv
                  + u_ref[...].astype(jnp.float32))
    q = jnp.clip(q, -float(qmax), float(qmax))
    o_ref[...] = (q * s).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("qmax", "block_m", "interpret"))
def quantize_dequantize_pallas(x, u, scale, *, qmax: int,
                               block_m: int = DEFAULT_BLOCK_M,
                               interpret: bool | None = None):
    """x, u: (M, 128) with M % block_m == 0; scale: (1, 1) float32."""
    m, lanes = x.shape
    assert lanes == LANES and u.shape == x.shape, (x.shape, u.shape)
    block_m = min(block_m, m)
    assert m % block_m == 0, (m, block_m)

    kernel = functools.partial(_qdq_kernel, qmax=qmax)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, LANES), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, u, scale)
