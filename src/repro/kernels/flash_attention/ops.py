"""Jitted wrapper exposing the kernel in the model's (B,S,H,d) layout, with
a custom VJP whose backward pass recomputes attention via the memory-safe
chunked reference (forward speed from the kernel, correctness from the ref;
a dedicated backward kernel is the standard next step on real hardware)."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_hmajor


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention_core(q, k, v, causal: bool = True, window: int = 0,
                          softcap: float = 0.0):
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = flash_attention_hmajor(qh, kh, vh, causal=causal, window=window,
                                 softcap=softcap)
    return jnp.swapaxes(out, 1, 2)


def _ref(q, k, v, causal, window, softcap):
    from repro.models.attention import self_attention
    return self_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, impl="dense"
                          if q.shape[1] <= 4096 else "auto")


def _fwd(q, k, v, causal, window, softcap):
    return _flash_attention_core(q, k, v, causal, window, softcap), (q, k, v)


def _bwd(causal, window, softcap, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: _ref(q_, k_, v_, causal, window,
                                             softcap), q, k, v)
    return vjp(g)


_flash_attention_core.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    softcap: float = 0.0):
    """q: (B,S,H,d); k,v: (B,S,KVH,d) — the model-zoo layout."""
    return _flash_attention_core(q, k, v, causal, window, softcap)
