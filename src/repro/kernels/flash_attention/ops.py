"""The flash kernel in the model's (B,S,H,d) layout, with a memory-bounded
backward pass.

The forward pass is the Pallas kernel; it keeps, for the backward pass,
only O(Sq) per head: q, k, v, the output and each query row's log-sum-exp
(``lse``).  The backward pass rebuilds the probabilities from them one kv
block at a time (``lax.scan`` over kv blocks, blocked ``jax.numpy``):

    P_j  = exp(S_j - lse)          S_j = scale * q k_j^T (capped, masked)
    dV_j = P_j^T dO
    dS_j = P_j * (dO V_j^T - D)    D = rowsum(dO * O)
    dQ  += scale * dS_j K_j        dK_j = scale * dS_j^T Q

so nothing of size Sq x Skv outlives one block.  Matrix products take the
inputs' dtype and accumulate in f32, as the kernel's do.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (DEFAULT_BLOCK_K, NEG_INF,
                                                  fit_block,
                                                  flash_attention_hmajor)

F32 = jnp.float32


def _forward(q, k, v, causal, window, softcap):
    out, lse = flash_attention_hmajor(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=causal, window=window, softcap=softcap)
    return jnp.swapaxes(out, 1, 2), lse                  # (B,Sq,H,dv), (B,H,Sq)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention_core(q, k, v, causal: bool = True, window: int = 0,
                          softcap: float = 0.0):
    return _forward(q, k, v, causal, window, softcap)[0]


def _fwd(q, k, v, causal, window, softcap):
    out, lse = _forward(q, k, v, causal, window, softcap)
    return out, (q, k, v, out, lse)


def attention_bwd(q, k, v, out, lse, do, *, causal: bool, window: int,
                  softcap: float, block_k: int = DEFAULT_BLOCK_K):
    """(dq, dk, dv) of attention from its inputs, output and per-row
    log-sum-exp, one kv block of ``block_k`` keys at a time.

    q, do, out: (B,Sq,H,d|dv); k, v: (B,Skv,KVH,d|dv); lse: (B,H,Sq) f32.
    """
    b, sq, h, d = q.shape
    sk, kvh, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = h // kvh
    bk = fit_block(sk, block_k)
    nk = sk // bk
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, kvh, g, d)
    dog = do.astype(q.dtype).reshape(b, sq, kvh, g, dv)
    di = jnp.einsum("bqngd,bqngd->bngq", dog.astype(F32),
                    out.astype(F32).reshape(b, sq, kvh, g, dv))
    lse = lse.reshape(b, kvh, g, sq)
    kb = jnp.moveaxis(k.reshape(b, nk, bk, kvh, d), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nk, bk, kvh, dv), 1, 0)
    qpos = jnp.arange(sq)[:, None]

    def block(dq, xs):
        j, kj, vj = xs
        s = jnp.einsum("bqngd,bknd->bngqk", qg, kj,
                       preferred_element_type=F32) * scale
        if softcap:
            t = jnp.tanh(s / softcap)
            s = t * softcap
        kpos = j * bk + jnp.arange(bk)[None, :]
        keep = jnp.ones((sq, bk), bool)
        if causal:
            keep &= kpos <= qpos
        if window:
            keep &= kpos > qpos - window
        p = jnp.exp(jnp.where(keep, s, NEG_INF) - lse[..., None])
        dvj = jnp.einsum("bngqk,bqngd->bknd", p.astype(q.dtype), dog,
                         preferred_element_type=F32)
        dp = jnp.einsum("bqngd,bknd->bngqk", dog, vj,
                        preferred_element_type=F32)
        ds = p * (dp - di[..., None])
        if softcap:
            ds = ds * (1.0 - t * t)
        ds = (ds * scale).astype(q.dtype)
        dq = dq + jnp.einsum("bngqk,bknd->bqngd", ds, kj,
                             preferred_element_type=F32)
        dkj = jnp.einsum("bngqk,bqngd->bknd", ds, qg,
                         preferred_element_type=F32)
        return dq, (dkj, dvj)

    dq0 = jnp.zeros((b, sq, kvh, g, d), F32)
    dq, (dk, dv_) = jax.lax.scan(block, dq0, (jnp.arange(nk), kb, vb))
    dk = jnp.moveaxis(dk, 0, 1).reshape(b, sk, kvh, d)
    dv_ = jnp.moveaxis(dv_, 0, 1).reshape(b, sk, kvh, dv)
    return (dq.reshape(b, sq, h, d).astype(q.dtype), dk.astype(k.dtype),
            dv_.astype(v.dtype))


def _bwd(causal, window, softcap, res, do):
    q, k, v, out, lse = res
    return attention_bwd(q, k, v, out, lse, do, causal=causal, window=window,
                         softcap=softcap)


_flash_attention_core.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    softcap: float = 0.0):
    """q: (B,Sq,H,d); k: (B,Skv,KVH,d); v: (B,Skv,KVH,dv) — the model-zoo
    layout.  Returns (B,Sq,H,dv)."""
    return _flash_attention_core(q, k, v, causal, window, softcap)
