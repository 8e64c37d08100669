"""Shape-generic wrapper for the quantize-dequantize kernel with STE VJP.

Handles what the tiled kernel cannot: arbitrary input shapes (flatten + pad
to (M, 128) tiles), the per-tensor absmax scale, drawing the
stochastic-rounding uniforms from a PRNG key, and a straight-through
estimator so the fake-quantizer is transparent to autodiff (the quantizer
is piecewise constant, so its true derivative is 0 a.e.; STE passes the
cotangent through unchanged, the standard choice for quantization-aware
training).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.quantize.kernel import LANES, quantize_dequantize_pallas
from repro.kernels.quantize.ref import quantize_dequantize_ref


def tensor_scale(x, qmax: int):
    """Per-tensor symmetric step size: absmax / qmax (0 for a zero tensor)."""
    return (jnp.max(jnp.abs(x.astype(jnp.float32))) / qmax).reshape(1, 1)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qdq_ste(x, u, scale, qmax):
    """Padded (M, 128) quantize-dequantize with straight-through gradient."""
    return quantize_dequantize_pallas(x, u, scale, qmax=qmax)


def _qdq_fwd(x, u, scale, qmax):
    return _qdq_ste(x, u, scale, qmax), (u.shape,)


def _qdq_bwd(qmax, res, g):
    (u_shape,) = res
    return g, jnp.zeros(u_shape, g.dtype), jnp.zeros((1, 1), jnp.float32)


_qdq_ste.defvjp(_qdq_fwd, _qdq_bwd)


def quantize_dequantize(x, key, *, bits: int = 8, stochastic: bool = True,
                        use_ref: bool = False):
    """Fake-quantize ``x`` to ``bits``-bit symmetric integers, any shape.

    ``key`` drives the stochastic rounding (ignored when
    ``stochastic=False``, which rounds half-up).  ``use_ref`` bypasses the
    Pallas kernel for the pure-jnp oracle (same math, same bits).
    """
    qmax = 2 ** (bits - 1) - 1
    scale = tensor_scale(x, qmax)
    flat = x.reshape(-1)
    if stochastic:
        u_flat = jax.random.uniform(key, flat.shape, jnp.float32)
    else:
        u_flat = jnp.full(flat.shape, 0.5, jnp.float32)
    if use_ref:
        out = quantize_dequantize_ref(flat, u_flat, scale[0, 0],
                                      qmax).reshape(x.shape)
    else:
        n = flat.shape[0]
        # big tensors amortize the grid over 256-row tiles; small ones keep
        # the padding waste at one minimal (8, 128) tile
        block_m = 256 if n >= 256 * LANES else 8
        tile = block_m * LANES
        pad = (-n) % tile
        xp = jnp.pad(flat, (0, pad)).reshape(-1, LANES)
        up = jnp.pad(u_flat, (0, pad)).reshape(-1, LANES)
        out = _qdq_ste(xp, up, scale, qmax)
        out = out.reshape(-1)[:n].reshape(x.shape)
    return out
