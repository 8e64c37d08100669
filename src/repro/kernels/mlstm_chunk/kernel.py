"""Chunkwise-parallel mLSTM Pallas TPU kernel (xLSTM, arXiv:2405.04517).

One program instance processes one (batch, head) pair; the chunk dim is the
innermost grid axis and the inter-chunk carry (C: dk x dv matrix memory,
n: dk normalizer, m: stabilizer) lives in VMEM scratch.  Within a chunk the
math is the quadratic stabilized form on a (bt x bt) tile — MXU-friendly —
while cross-chunk state keeps total work linear in sequence length.

Matches repro.models.xlstm.mlstm_chunkwise (the jnp implementation used by
the model) and the recurrent decode step (tested).

Every value in the body is 2-D so it maps onto (8, 128) vreg tiles: the
gates arrive as (chunk, 1) columns (index t on sublanes), and the
within-chunk cumulative sum and running max that Mosaic has no primitive
for are masked lane reductions over the (t, s) plane, whose row-broadcast
form [t, s] = x_s is the transpose of the column's lane broadcast.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

DEFAULT_CHUNK = 128
NEG_BIG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, o_ref,
                  c_ref, n_ref, m_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_BIG)

    q = q_ref[0, 0].astype(jnp.float32)          # (bt, dh)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    li = li_ref[0, 0].astype(jnp.float32)        # (bt, 1)
    lf = lf_ref[0, 0].astype(jnp.float32)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = t_idx >= s_idx

    def by_s(col):                               # [t, s] = col_s
        return jnp.broadcast_to(col, (chunk, chunk)).T

    m_prev = m_ref[...]                          # (1, 1)
    a = jnp.sum(jnp.where(causal, by_s(lf), 0.0), axis=1,
                keepdims=True)                   # cumsum(lf), (bt, 1)
    g = li - a
    g_s = by_s(g)
    run_max = jnp.max(jnp.where(causal, g_s, NEG_BIG), axis=1,
                      keepdims=True)             # cummax(g), (bt, 1)
    M = jnp.maximum(m_prev, run_max)
    m_t = a + M

    # intra-chunk decay matrix D[t,s] = exp(g_s - M_t), s <= t
    D = jnp.where(causal, jnp.exp(g_s - M), 0.0)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * D  # (bt,bt)
    h_intra = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())))
    n_intra = jax.lax.dot_general(D, k, (((1,), (0,)), ((), ())))     # (bt,dh)

    decay = jnp.exp(m_prev - M)                  # (bt, 1)
    h_inter = jax.lax.dot_general(q, c_ref[...], (((1,), (0,)), ((), ()))) \
        * decay
    n_tot = n_intra + n_ref[...] * decay
    denom = jnp.maximum(jnp.abs(jnp.sum(q * n_tot, axis=1, keepdims=True)),
                        jnp.exp(-m_t))
    o_ref[0, 0] = ((h_intra + h_inter) / denom).astype(o_ref.dtype)

    # ---- carry update ----
    M_L = M[chunk - 1:, :]                       # (1, 1)
    w_s = jnp.exp(g - M_L)                       # (bt, 1)
    c_ref[...] = c_ref[...] * jnp.exp(m_prev - M_L) + \
        jax.lax.dot_general(k * w_s, v, (((0,), (0,)), ((), ())))
    n_ref[...] = n_ref[...] * jnp.exp(m_prev - M_L) + \
        jnp.sum(k * w_s, axis=0, keepdims=True)
    m_ref[...] = m_t[chunk - 1:, :]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk_pallas(q, k, v, li, lf, *, chunk: int = DEFAULT_CHUNK,
                       interpret: bool | None = None):
    """q,k,v: (B,H,S,dh); li,lf: (B,H,S) log input/forget gates.

    Returns h: (B,H,S,dh).
    """
    b, h, s, dh = q.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    kernel = functools.partial(_mlstm_kernel, chunk=chunk)
    blk4 = lambda b_, h_, ci: (b_, h_, ci, 0)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dh), blk4),
            pl.BlockSpec((1, 1, chunk, dh), blk4),
            pl.BlockSpec((1, 1, chunk, dh), blk4),
            pl.BlockSpec((1, 1, chunk, 1), blk4),
            pl.BlockSpec((1, 1, chunk, 1), blk4),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, dh), blk4),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v, li[..., None], lf[..., None])
