"""Flash attention Pallas TPU kernel with causal + sliding-window block skip.

Layout: q (B,H,S,d), k/v (B,KVH,S,d) — head-major so BlockSpecs tile the
(seq, head_dim) plane in VMEM and GQA is folded into the k/v index_map
(kv head = q head // group) with no materialized expansion.

Grid: (B, H, nq, nk) — the kv-block dim is innermost; per-(b,h,i) online
softmax state (m, l, acc) lives in VMEM scratch across the nk iterations.
Block skipping is structural: for causal masks, kv blocks strictly above the
diagonal contribute nothing and are skipped with pl.when; for sliding-window
masks, kv blocks entirely left of the window are skipped too — this is what
the pure-JAX chunked path cannot do (it must compute the full rectangle and
mask), and is the measured compute-term win in EXPERIMENTS.md §Perf.

VMEM budget per program instance (f32 compute):
    q block  bq*d*4      k/v blocks 2*bk*d*4
    scores   bq*bk*4     scratch (2*bq + bq*d)*4
with the default bq=bk=512, d=128: ~1.8 MiB — comfortably inside the
~16 MiB/core VMEM, leaving room for double buffering.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -2.0 ** 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 causal: bool, window: int, softcap: float, scale: float,
                 block_q: int, block_k: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * block_q
    k_start = j * block_k

    # ---- structural block skip (the FLOP saving vs the masked rectangle) --
    diag_ok = True
    if causal:
        diag_ok = k_start <= q_start + block_q - 1          # not fully above diag
    win_ok = True
    if window:
        # kv block entirely out of every query's window?
        win_ok = k_start + block_k - 1 > q_start - window

    @pl.when(jnp.logical_and(diag_ok, win_ok))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        keep = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            keep &= kpos <= qpos
        if window:
            keep &= kpos > qpos - window
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-37)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"))
def flash_attention_hmajor(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool | None = None):
    """q: (B,H,S,d); k,v: (B,KVH,S,d).  Returns (B,H,S,d).

    ``interpret=None`` compiles the kernel on a TPU and interprets it
    elsewhere (:func:`repro.kernels.interpret_mode`).
    """
    b, h, s, d = q.shape
    kvh = k.shape[1]
    assert h % kvh == 0
    g = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _attn_kernel, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
