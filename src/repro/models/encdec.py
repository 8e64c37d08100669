"""Encoder-decoder backbone (seamless-m4t-medium, arXiv:2308.11596).

The audio frontend (mel-spectrogram + conv feature extractor) is the allowed
stub: inputs carry precomputed ``source_embeds`` (B, S_src, d_model).  This
module implements the transformer encoder + autoregressive text/unit decoder
that consumes them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models.init_utils import (dense, dense_axes, embedding,
                                     embedding_axes, norm, norm_axes,
                                     stack_axes)
from repro.models.layers import apply_norm, mlp_apply, mlp_axes, mlp_init
from repro.models.transformer import LOSS_CHUNK, logits_from_hidden  # reuse head
from repro.telemetry.spans import scope


# ------------------------------------------------------------- layers ------
def _enc_layer_init(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {"ln1": norm(cfg.d_model, cfg.norm, dtype),
            "attn": attn_mod.attn_init(k1, cfg, dtype),
            "ln2": norm(cfg.d_model, cfg.norm, dtype),
            "mlp": mlp_init(k2, cfg, dtype=dtype)}


def _enc_layer_axes(cfg: ModelConfig):
    return {"ln1": norm_axes(cfg.norm), "attn": attn_mod.attn_axes(cfg),
            "ln2": norm_axes(cfg.norm), "mlp": mlp_axes()}


def _dec_layer_init(key, cfg: ModelConfig, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": norm(cfg.d_model, cfg.norm, dtype),
            "self": attn_mod.attn_init(k1, cfg, dtype),
            "lnx": norm(cfg.d_model, cfg.norm, dtype),
            "cross": attn_mod.attn_init(k2, cfg, dtype),
            "ln2": norm(cfg.d_model, cfg.norm, dtype),
            "mlp": mlp_init(k3, cfg, dtype=dtype)}


def _dec_layer_axes(cfg: ModelConfig):
    return {"ln1": norm_axes(cfg.norm), "self": attn_mod.attn_axes(cfg),
            "lnx": norm_axes(cfg.norm), "cross": attn_mod.attn_axes(cfg),
            "ln2": norm_axes(cfg.norm), "mlp": mlp_axes()}


# ------------------------------------------------------------- init --------
def init(key, cfg: ModelConfig, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    ne = cfg.encdec.num_encoder_layers
    nd = cfg.num_layers
    keys = jax.random.split(key, 5)
    enc_keys = jax.random.split(keys[0], ne)
    dec_keys = jax.random.split(keys[1], nd)
    return {
        "src_proj": dense(keys[2], cfg.d_model, cfg.d_model, dtype=dtype),
        "encoder": jax.vmap(lambda k: _enc_layer_init(k, cfg, dtype))(enc_keys),
        "enc_norm": norm(cfg.d_model, cfg.norm, dtype),
        "embed": embedding(keys[3], cfg.padded_vocab, cfg.d_model, dtype),
        "decoder": jax.vmap(lambda k: _dec_layer_init(k, cfg, dtype))(dec_keys),
        "final_norm": norm(cfg.d_model, cfg.norm, dtype),
        "lm_head": dense(keys[4], cfg.d_model, cfg.padded_vocab, dtype=dtype),
    }


def axes(cfg: ModelConfig):
    return {
        "src_proj": dense_axes(("embed", "embed")),
        "encoder": stack_axes(_enc_layer_axes(cfg)),
        "enc_norm": norm_axes(cfg.norm),
        "embed": embedding_axes(),
        "decoder": stack_axes(_dec_layer_axes(cfg)),
        "final_norm": norm_axes(cfg.norm),
        "lm_head": dense_axes(("embed", "vocab")),
    }


# ------------------------------------------------------------- apply -------
def encode(params, cfg: ModelConfig, source_embeds, *, impl: str = "auto",
           remat: bool = False, remat_policy: str | None = None):
    from repro.models.transformer import remat_wrapper
    w = params["src_proj"]["w"]
    x = source_embeds.astype(w.dtype) @ w          # activations in the params' dtype
    maybe_remat = remat_wrapper(remat, remat_policy)

    @maybe_remat
    def layer(x, p):
        h = apply_norm(p["ln1"], x, cfg.norm)
        x = x + attn_mod.attn_apply(p["attn"], cfg, h, causal=False,
                                    rope_theta=cfg.rope_theta, impl=impl)
        h = apply_norm(p["ln2"], x, cfg.norm)
        return x + mlp_apply(p["mlp"], h, cfg.act), None

    x, _ = jax.lax.scan(layer, x, params["encoder"])
    return apply_norm(params["enc_norm"], x, cfg.norm)


def apply(params, cfg: ModelConfig, batch, *, impl: str = "auto",
          remat: bool = False, remat_policy: str | None = None):
    """Teacher-forced full forward.  batch: {"source_embeds", "tokens"}.

    Returns (decoder hidden states, aux=0).
    """
    memory = encode(params, cfg, batch["source_embeds"], impl=impl,
                    remat=remat, remat_policy=remat_policy)
    from repro.models.transformer import remat_wrapper
    with scope("embed"):
        x = params["embed"]["table"][batch["tokens"]]
    maybe_remat = remat_wrapper(remat, remat_policy)

    @maybe_remat
    def layer(x, p):
        h = apply_norm(p["ln1"], x, cfg.norm)
        x = x + attn_mod.attn_apply(p["self"], cfg, h, causal=True,
                                    rope_theta=cfg.rope_theta, impl=impl)
        h = apply_norm(p["lnx"], x, cfg.norm)
        with scope("cross_attention"):
            kv = attn_mod.cross_kv(p["cross"], cfg, memory)
            y = attn_mod.attn_apply(p["cross"], cfg, h, causal=False,
                                    rope_theta=0.0, kv_override=kv, impl=impl)
        x = x + y
        h = apply_norm(p["ln2"], x, cfg.norm)
        return x + mlp_apply(p["mlp"], h, cfg.act), None

    x, _ = jax.lax.scan(layer, x, params["decoder"])
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return x, jnp.zeros((), jnp.float32)


# ------------------------------------------------------------- decode ------
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Self-attention KV cache (stacked over decoder layers) + cross KV."""
    nd = cfg.num_layers
    self_c = attn_mod.init_kv_cache(cfg, batch, max_len, dtype=dtype)
    self_c = jax.tree.map(lambda a: jnp.broadcast_to(a, (nd,) + a.shape), self_c)
    src = cfg.encdec.max_source_len
    cross = {
        "k": jnp.zeros((nd, batch, src, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((nd, batch, src, cfg.num_kv_heads, cfg.head_dim), dtype),
    }
    return {"self": self_c, "cross": cross}


def precompute_cross(params, cfg: ModelConfig, memory, dtype=jnp.bfloat16):
    """Fill the cross-attention cache from encoder memory."""
    def per_layer(p):
        k, v = attn_mod.cross_kv(p, cfg, memory)
        return k.astype(dtype), v.astype(dtype)

    ks, vs = jax.vmap(per_layer)(params["decoder"]["cross"])
    return {"k": ks, "v": vs}


def decode_step(params, cfg: ModelConfig, token, cache, index, *,
                positions3=None, return_hidden: bool = False):
    """One decoder step with self KV cache + precomputed cross KV."""
    with scope("embed"):
        x = params["embed"]["table"][token]

    def body(x, slices):
        p, sc, ck, cv = slices
        h = apply_norm(p["ln1"], x, cfg.norm)
        y, nc = attn_mod.decode_attend(p["self"], cfg, h, sc, index, window=0,
                                       rope_theta=cfg.rope_theta)
        x = x + y
        h = apply_norm(p["lnx"], x, cfg.norm)
        # cross attention over the fixed encoder memory
        q = jnp.einsum("bsd,dhk->bshk", h, p["cross"]["q"]["w"])
        if cfg.attn_bias:
            q = q + p["cross"]["q"]["b"]
        out = attn_mod.dense_attention(q, ck, cv, causal=False, window=0,
                                       softcap=0.0)
        b = out.shape[0]
        o = out.reshape(b, 1, cfg.num_heads * cfg.head_dim) @ p["cross"]["o"]["w"]
        x = x + o
        h = apply_norm(p["ln2"], x, cfg.norm)
        return x + mlp_apply(p["mlp"], h, cfg.act), nc

    x, new_self = jax.lax.scan(
        body, x, (params["decoder"], cache["self"], cache["cross"]["k"],
                  cache["cross"]["v"]))
    x = apply_norm(params["final_norm"], x, cfg.norm)
    new_cache = {"self": new_self, "cross": cache["cross"]}
    if return_hidden:
        return x, new_cache
    return logits_from_hidden(params, cfg, x), new_cache
