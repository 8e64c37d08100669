"""Flash attention Pallas TPU kernel with causal + sliding-window block skip.

Layout: q (B,H,Sq,d), k (B,KVH,Skv,d), v (B,KVH,Skv,dv) — head-major so
BlockSpecs tile the (seq, head_dim) plane in VMEM and GQA is folded into the
k/v index_map (kv head = q head // group) with no materialized expansion.
Sq and Skv may differ (cross-attention); positions are absolute from 0 on
both sides, so a causal mask keeps key j for query i iff j <= i.

Grid: (B, H, nq, nk) — the kv-block dim is innermost; per-(b,h,i) online
softmax state (m, l, acc) lives in VMEM scratch across the nk iterations.
Block skipping is structural: for causal masks, kv blocks strictly above the
diagonal contribute nothing and are skipped with pl.when; for sliding-window
masks, kv blocks entirely left of the window are skipped too — the pure-JAX
chunked path cannot do this (it computes the full rectangle and masks).

Besides the output the kernel writes each query row's log-sum-exp of its
(scaled, capped, masked) scores, m + log(l): the one number per row that a
backward pass needs to rebuild the probabilities block by block.  It is
written broadcast over ``LSE_LANES`` lanes, the TPU's tile width, and
callers keep lane 0.

The call is named ``flash_attention_fwd``, with ``_causal`` and
``_window<w>`` appended for those masks: the compiled program's operation
and a profile's events carry the name, so the kernel's time and its work
(``bench/rooflines/flash_attention.py``) can be read from a trace.

Matrix products take the inputs' dtype (bf16 operands on the MXU for a
bf16 model) and accumulate in f32; the softmax runs in f32.

VMEM budget per program instance (f32 compute):
    q block  bq*d*4      k/v blocks 2*bk*d*4
    scores   bq*bk*4     scratch (2*bq*128 + bq*dv)*4  (m, l: one lane tile)
with the default bq=bk=512, d=128: ~2.3 MiB — comfortably inside the
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
LSE_LANES = 128
NEG_INF = -2.0 ** 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                 *, causal: bool, window: int, softcap: float, scale: float,
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
        q = q_ref[0, 0]                                     # (bq, d)
        k = k_ref[0, 0]                                     # (bk, d)
        v = v_ref[0, 0]                                     # (bk, dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        if causal or window:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                keep &= kpos <= qpos
            if window:
                keep &= kpos > qpos - window
            s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[...]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)                  # (bq, 1)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_ref[...] + jnp.log(l),
                                         (block_q, LSE_LANES))


def kernel_name(causal: bool, window: int) -> str:
    return ("flash_attention_fwd" + ("_causal" if causal else "")
            + (f"_window{window}" if window else ""))


def fit_block(s: int, pref: int) -> int:
    """The largest of pref, pref/2, ... that divides s (s itself if none
    down to 8 does)."""
    b = min(pref, s)
    while b >= 8:
        if s % b == 0:
            return b
        b //= 2
    return s


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"))
def flash_attention_hmajor(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool | None = None):
    """q: (B,H,Sq,d); k: (B,KVH,Skv,d); v: (B,KVH,Skv,dv).

    Returns (out (B,H,Sq,dv) in q's dtype, lse (B,H,Sq) f32).
    ``interpret=None`` compiles the kernel on a TPU and interprets it
    elsewhere (:func:`repro.kernels.interpret_mode`).
    """
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    assert h % kvh == 0
    g = h // kvh
    block_q = fit_block(sq, block_q)
    block_k = fit_block(sk, block_k)
    assert sq % block_q == 0 and sk % block_k == 0
    nq, nk = sq // block_q, sk // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _attn_kernel, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, LSE_LANES),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, sq, LSE_LANES), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
        name=kernel_name(causal, window),
    )(q, k, v)
    return out, lse[..., 0]

