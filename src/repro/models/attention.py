"""GQA attention: the Pallas flash kernel on a TPU, and dense, chunked
(online-softmax) and banded (sliding-window) pure-JAX paths, plus KV-cache
prefill/decode.

Path selection for full-sequence attention (``impl="auto"``,
:func:`choose_impl`, from the shapes and the platform alone):
  - on a TPU, query and key lengths both multiples of ``FLASH_TILE`` and
    at least ``FLASH_MIN_SEQ``: the flash kernel
    (``repro.kernels.flash_attention``).  Bidirectional, causal and
    cross-attention (Sq != Skv) alike; its backward pass keeps O(Sq) per
    head (the output and each row's log-sum-exp), never the Sq x Skv
    probabilities, so training memory does not grow with S^2.  Below
    ``FLASH_MIN_SEQ`` both paths take the same time per call on a v5e and
    the probabilities are small, so the dense path stays.
  - otherwise (decode with q_len 1, other platforms, short or odd
    lengths): short sequences take the dense masked softmax; long ones,
    full attention: chunked online softmax (O(chunk) memory); long ones,
    sliding window: banded — each query chunk only touches its (chunk +
    window) key band, so FLOPs are O(S*w) not O(S^2).
``impl="flash"``, ``"dense"`` or ``"chunked"`` (chunked, or banded with a
window) force a path.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.init_utils import dense, dense_axes, norm, norm_axes
from repro.telemetry.spans import scope

DENSE_MAX_SEQ = 4096          # longest seq for the dense path under "auto"
FLASH_TILE = 128              # the flash path's length unit: one lane tile
FLASH_MIN_SEQ = 512           # shortest length the flash path takes on a TPU
Q_CHUNK = 1024
KV_CHUNK = 1024

NEG_INF = -2.0 ** 30          # large-negative instead of -inf (NaN-safe masks)


# ------------------------------------------------------------- params ------
def attn_init(key, cfg: ModelConfig, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "q": dense(kq, cfg.d_model, (cfg.num_heads, cfg.head_dim),
                   bias=cfg.attn_bias, dtype=dtype),
        "k": dense(kk, cfg.d_model, (cfg.num_kv_heads, cfg.head_dim),
                   bias=cfg.attn_bias, dtype=dtype),
        "v": dense(kv, cfg.d_model, (cfg.num_kv_heads, cfg.head_dim),
                   bias=cfg.attn_bias, dtype=dtype),
        "o": dense(ko, cfg.num_heads * cfg.head_dim, cfg.d_model, dtype=dtype,
                   scale=1.0 / math.sqrt(cfg.num_heads * cfg.head_dim)),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm(cfg.head_dim, "rmsnorm", dtype)
        p["k_norm"] = norm(cfg.head_dim, "rmsnorm", dtype)
    return p


def attn_axes(cfg: ModelConfig):
    a = {
        "q": dense_axes(("embed", "heads", "head_dim"), bias=cfg.attn_bias),
        "k": dense_axes(("embed", "kv_heads", "head_dim"), bias=cfg.attn_bias),
        "v": dense_axes(("embed", "kv_heads", "head_dim"), bias=cfg.attn_bias),
        "o": dense_axes(("heads", "embed")),
    }
    if cfg.qk_norm:
        a["q_norm"] = norm_axes("rmsnorm")
        a["k_norm"] = norm_axes("rmsnorm")
    return a


def _project_qkv(p, cfg: ModelConfig, x):
    """x: (B,S,D) -> q (B,S,H,hd), k/v (B,S,KV,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["q"]["w"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["k"]["w"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["v"]["w"])
    if cfg.attn_bias:
        q = q + p["q"]["b"]
        k = k + p["k"]["b"]
        v = v + p["v"]["b"]
    if cfg.qk_norm:
        from repro.models.layers import apply_norm
        q = apply_norm(p["q_norm"], q, "rmsnorm")
        k = apply_norm(p["k_norm"], k, "rmsnorm")
    return q, k, v


def _out_proj(p, cfg: ModelConfig, o):
    """o: (B,S,H,hd) -> (B,S,D)."""
    b, s = o.shape[:2]
    return o.reshape(b, s, cfg.num_heads * cfg.head_dim) @ p["o"]["w"]


# ---------------------------------------------------------- core maths -----
def _expand_gqa(q, num_kv: int):
    """(B,S,H,hd) -> (B,S,KV,G,hd)."""
    b, s, h, d = q.shape
    g = h // num_kv
    return q.reshape(b, s, num_kv, g, d)


def _mask_bias(qpos, kpos, *, causal: bool, window: int, kv_valid=None):
    """Additive mask bias (..., q, k) from absolute positions."""
    qp = qpos[..., :, None]
    kp = kpos[..., None, :]
    keep = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        keep &= kp <= qp
    if window:
        keep &= kp > qp - window
    if kv_valid is not None:
        keep &= kv_valid[..., None, :]
    return jnp.where(keep, 0.0, NEG_INF)


def dense_attention(q, k, v, *, causal: bool, window: int, softcap: float,
                    q_offset=0, kv_valid=None):
    """Reference masked-softmax attention.

    q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd).  q_offset: absolute position of q[0]
    (int or (B,) array).  kv_valid: optional (B,Sk) bool.
    """
    b, sq, h, d = q.shape
    sk, kv_heads = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    qg = _expand_gqa(q, kv_heads)                        # (B,Sq,KV,G,hd)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqngd,bknd->bngqk",
                        qg.astype(jnp.float32) * scale, k.astype(jnp.float32))
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    qpos = (jnp.arange(sq)[None, :] + jnp.asarray(q_offset).reshape(-1, 1))
    kpos = jnp.broadcast_to(jnp.arange(sk)[None, :], (b, sk))
    bias = _mask_bias(qpos, kpos, causal=causal, window=window,
                      kv_valid=kv_valid)                 # (B,q,k)
    logits = logits + bias[:, None, None, :, :]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngqk,bknd->bqngd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, dv).astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool, window: int, softcap: float,
                      q_chunk: int = Q_CHUNK, kv_chunk: int = KV_CHUNK):
    """Flash-style online-softmax attention, O(chunk^2) live memory.

    Full-rectangle compute with masking (no block skipping — the Pallas
    kernel does skipping on TPU).
    """
    b, sq, h, d = q.shape
    sk, kv_heads = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    assert sq % q_chunk == 0 and sk % kv_chunk == 0, (sq, sk)
    nq, nk = sq // q_chunk, sk // kv_chunk
    scale = 1.0 / math.sqrt(d)
    qg = _expand_gqa(q, kv_heads).reshape(b, nq, q_chunk, kv_heads, h // kv_heads, d)
    kc = k.reshape(b, nk, kv_chunk, kv_heads, d)
    vc = v.reshape(b, nk, kv_chunk, kv_heads, dv)

    def per_q_chunk(qi, q_blk):
        # q_blk: (b, q_chunk, KV, G, d)
        qpos = qi * q_chunk + jnp.arange(q_chunk)

        def body(carry, inputs):
            m, l, acc = carry
            ki, k_blk, v_blk = inputs
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            logits = jnp.einsum("bqngd,bknd->bngqk",
                                q_blk.astype(jnp.float32) * scale,
                                k_blk.astype(jnp.float32))
            if softcap:
                logits = jnp.tanh(logits / softcap) * softcap
            keep = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                keep &= kpos[None, :] <= qpos[:, None]
            if window:
                keep &= kpos[None, :] > qpos[:, None] - window
            logits = logits + jnp.where(keep, 0.0, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bngqk,bknd->bngqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        g = h // kv_heads
        m0 = jnp.full((b, kv_heads, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kv_heads, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kv_heads, g, q_chunk, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-37)
        # (b, KV, G, q_chunk, dv) -> (b, q_chunk, h, dv)
        return jnp.moveaxis(out, 3, 1).reshape(b, q_chunk, h, dv)

    out = jax.lax.map(lambda args: per_q_chunk(*args),
                      (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)))
    # out: (nq, b, q_chunk, h, dv)
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, dv).astype(q.dtype)


def banded_attention(q, k, v, *, window: int, softcap: float,
                     q_chunk: int = Q_CHUNK):
    """Sliding-window attention with true O(S*(w+c)) FLOPs.

    Each query chunk attends only to its key band [start - w, start + c).
    """
    b, sq, h, d = q.shape
    sk, kv_heads = k.shape[1], k.shape[2]
    assert sq == sk, "banded path assumes self-attention"
    assert sq % q_chunk == 0
    nq = sq // q_chunk
    band = q_chunk + window
    scale = 1.0 / math.sqrt(d)
    qg = _expand_gqa(q, kv_heads).reshape(b, nq, q_chunk, kv_heads, h // kv_heads, d)

    def per_q_chunk(qi, q_blk):
        start = qi * q_chunk - window
        start_c = jnp.clip(start, 0, sk - band)
        k_band = jax.lax.dynamic_slice_in_dim(k, start_c, band, axis=1)
        v_band = jax.lax.dynamic_slice_in_dim(v, start_c, band, axis=1)
        qpos = qi * q_chunk + jnp.arange(q_chunk)
        kpos = start_c + jnp.arange(band)
        logits = jnp.einsum("bqngd,bknd->bngqk",
                            q_blk.astype(jnp.float32) * scale,
                            k_band.astype(jnp.float32))
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        keep = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] > qpos[:, None] - window)
        logits = logits + jnp.where(keep, 0.0, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bngqk,bknd->bngqd", probs, v_band.astype(jnp.float32))
        return jnp.moveaxis(out, 3, 1).reshape(b, q_chunk, h, d)

    out = jax.lax.map(lambda args: per_q_chunk(*args),
                      (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, d).astype(q.dtype)


def choose_impl(sq: int, sk: int, platform: str) -> str:
    """The path ``impl="auto"`` takes for ``sq`` queries onto ``sk`` keys on
    ``platform`` (``jax.default_backend()``)."""
    if (platform == "tpu" and min(sq, sk) >= FLASH_MIN_SEQ
            and sq % FLASH_TILE == 0 and sk % FLASH_TILE == 0):
        return "flash"
    if sq <= DENSE_MAX_SEQ or sq % Q_CHUNK or sk % KV_CHUNK:
        return "dense"
    return "chunked"


def self_attention(q, k, v, *, causal: bool = True, window: int = 0,
                   softcap: float = 0.0, impl: str = "auto"):
    """Full-sequence attention of q (B,Sq,H,d) onto k, v (B,Skv,KV,d),
    with automatic path choice (:func:`choose_impl`)."""
    sq = q.shape[1]
    if impl == "auto":
        impl = choose_impl(sq, k.shape[1], jax.default_backend())
    if impl == "flash":
        from repro.kernels.flash_attention.ops import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap)
    if impl == "chunked" and window and sq == k.shape[1]:
        return banded_attention(q, k, v, window=window, softcap=softcap)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
    return dense_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap)


@scope("attention")
def attn_apply(p, cfg: ModelConfig, x, *, window: int = 0,
               rope_theta: float = 10000.0, softcap: float = 0.0,
               positions=None, positions3=None, causal: bool = True,
               kv_override=None, impl: str = "auto"):
    """Full-sequence attention sublayer: proj -> rope -> attn -> out proj.

    kv_override: (k, v) from another sequence (cross-attention).
    """
    from repro.models.layers import apply_mrope, apply_rope

    b, s, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    if kv_override is not None:
        k, v = kv_override
    if positions3 is not None:
        q = apply_mrope(q, positions3, rope_theta, cfg.vlm.mrope_sections)
        if kv_override is None:
            k = apply_mrope(k, positions3, rope_theta, cfg.vlm.mrope_sections)
    elif rope_theta:
        pos = positions if positions is not None \
            else jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        q = apply_rope(q, pos, rope_theta)
        if kv_override is None:
            k = apply_rope(k, pos, rope_theta)
    out = self_attention(q, k, v, causal=causal, window=window,
                         softcap=softcap, impl=impl)
    return _out_proj(p, cfg, out)


def cross_kv(p, cfg: ModelConfig, memory):
    """Precompute cross-attention K/V from encoder memory (B,S_src,D)."""
    k = jnp.einsum("bsd,dhk->bshk", memory, p["k"]["w"])
    v = jnp.einsum("bsd,dhk->bshk", memory, p["v"]["w"])
    if cfg.attn_bias:
        k = k + p["k"]["b"]
        v = v + p["v"]["b"]
    return k, v


# ------------------------------------------------------------ KV cache -----
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                  window: int = 0, dtype=jnp.bfloat16):
    """Cache for one attention layer.  Sliding-window layers keep only a
    rolling ``window``-sized buffer (this is what makes long_500k decode
    memory bounded for gemma3/recurrentgemma local layers)."""
    length = min(window, max_len) if window else max_len
    shape = (batch, length, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def cache_axes():
    return {"k": (None, "length", "kv_heads", "head_dim"),
            "v": (None, "length", "kv_heads", "head_dim")}


def decode_attend(p, cfg: ModelConfig, x, cache, index, *, window: int,
                  rope_theta: float, softcap: float = 0.0, positions3=None):
    """One-token decode: append to cache, attend over valid prefix.

    x: (B,1,D); index: scalar int32 — number of tokens already in the cache.
    Returns (out (B,1,D), new_cache).
    """
    from repro.models.layers import apply_mrope, apply_rope

    b = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x)
    pos = jnp.full((b, 1), index, jnp.int32)
    if positions3 is not None:
        q = apply_mrope(q, positions3, rope_theta, cfg.vlm.mrope_sections)
        k = apply_mrope(k, positions3, rope_theta, cfg.vlm.mrope_sections)
    elif rope_theta:
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)

    length = cache["k"].shape[1]
    slot = jnp.mod(index, length) if window else jnp.minimum(index, length - 1)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype),
                                             slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype),
                                             slot, axis=1)

    # absolute positions of cache slots
    slots = jnp.arange(length)
    if window:
        # ring buffer: slot s holds position index - ((slot - s) mod length)
        offset = jnp.mod(slot - slots, length)
        kpos = index - offset
        valid = (kpos >= 0) & (kpos >= index - window + 1) | (slots == slot)
        kpos = jnp.broadcast_to(kpos[None], (b, length))
        kv_valid = jnp.broadcast_to(valid[None], (b, length))
    else:
        kpos = jnp.broadcast_to(slots[None], (b, length))
        kv_valid = jnp.broadcast_to((slots <= index)[None], (b, length))

    qg = _expand_gqa(q, cfg.num_kv_heads)                 # (B,1,KV,G,hd)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    logits = jnp.einsum("bqngd,bknd->bngqk", qg.astype(jnp.float32) * scale,
                        ck.astype(jnp.float32))
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = logits + jnp.where(kv_valid, 0.0, NEG_INF)[:, None, None, None, :]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngqk,bknd->bqngd", probs, cv.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.num_heads, cfg.head_dim).astype(x.dtype)
    return _out_proj(p, cfg, out), {"k": ck, "v": cv}
